"""Per-layer metrics, one small reader each under `benchmark/layer_metrics/`.

`<metric>.json` holds `{"what": ..., "reader": ...}`; unit, layer, `moves`
and cells are `BENCHMARK.json`'s and are not repeated. A reader is either

  {"path": [...], "over": [...], "scale": x}
      the value at `path` in what the runner collected, divided by the value
      at `over` where given, times `scale` (default 1); or
  {"python": "<file>.py"}
      a file beside it with `read(collected) -> float | None`; or
  {"same_as": "<metric>"}
      that metric's reader: the same reading under the name of another
      end-to-end metric it moves (the contract gives a metric one `moves`).

A reader that finds nothing to read returns None and the metric is left out
of the line; `run.py` names it on a `warning` line.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Optional

from lib.manifest import BENCH

DIR = os.path.join(BENCH, "layer_metrics")


def _dig(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def _python_reader(filename: str):
    path = os.path.join(DIR, filename)
    spec = importlib.util.spec_from_file_location(f"layer_metric_{filename[:-3]}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def read(name: str, collected: dict) -> Optional[float]:
    with open(os.path.join(DIR, name + ".json")) as f:
        reader = json.load(f)["reader"]
    if "same_as" in reader:
        return read(reader["same_as"], collected)
    try:
        if "python" in reader:
            value = _python_reader(reader["python"])(collected)
        else:
            value = _dig(collected, reader["path"])
            if value is not None and "over" in reader:
                value = value / _dig(collected, reader["over"])
    except (KeyError, IndexError, TypeError, ZeroDivisionError):
        return None
    if value is None:
        return None
    return float(value) * reader.get("scale", 1.0)


def read_all(names, collected: dict) -> dict:
    """Every name's value, None where its reader found nothing."""
    return {name: read(name, collected) for name in names}

