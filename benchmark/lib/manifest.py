"""`BENCHMARK.json` and the files it names: loading, the driver's character
rules, and where a cell's configuration, traffic mix and metric readers live.
Everything here runs before anything touches the chip."""

from __future__ import annotations

import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {
    "command", "paths", "run_seconds", "configs", "workloads", "end_to_end",
    "per_layer",
}


class ManifestError(ValueError):
    pass


def _line(text, what: str) -> None:
    if not isinstance(text, str) or not 1 <= len(text) <= 200 or "\n" in text \
            or "\t" in text:
        raise ManifestError(f"{what}: 1 to 200 characters on one line, got {text!r}")


def check_name(text, what: str) -> None:
    if not isinstance(text, str) or not NAME.match(text):
        raise ManifestError(f"{what}: not a name by the driver's rules: {text!r}")


def _keys(entry: dict, required: set, optional: set, what: str) -> None:
    keys = set(entry)
    if not required <= keys or not keys <= required | optional:
        raise ManifestError(
            f"{what}: keys {sorted(keys)}, expected {sorted(required)} "
            f"and at most {sorted(optional)}"
        )


def _metric(entry: dict, per_layer: bool, end_to_end: set, cells: set) -> None:
    what = f"metric {entry.get('name')!r}"
    required = {"name", "unit", "better", "source"}
    required |= {"layer", "moves"} if per_layer else {"bound"}
    _keys(entry, required, {"workloads"}, what)
    check_name(entry["name"], what)
    if not UNIT.match(entry["unit"]):
        raise ManifestError(f"{what}: unit {entry['unit']!r}")
    if entry["better"] not in ("lower", "higher"):
        raise ManifestError(f"{what}: better {entry['better']!r}")
    allowed = SOURCES if per_layer else ("host_clock", "device_trace")
    if entry["source"] not in allowed:
        raise ManifestError(f"{what}: source {entry['source']!r} not in {allowed}")
    if per_layer:
        _line(entry["layer"], f"{what} layer")
        if entry["moves"] not in end_to_end:
            raise ManifestError(f"{what}: moves unknown metric {entry['moves']!r}")
    else:
        limit = 0.1
        if not 0.01 <= entry["bound"] <= limit:
            raise ManifestError(f"{what}: bound {entry['bound']} outside [0.01, {limit}]")
    for cell in entry.get("workloads", ()):
        if cell not in cells:
            raise ManifestError(f"{what}: lists unknown cell {cell!r}")


def validate(manifest: dict) -> None:
    """Raise ManifestError where the driver would refuse the file."""
    if set(manifest) != TOP_KEYS:
        raise ManifestError(f"top-level keys {sorted(manifest)} != {sorted(TOP_KEYS)}")
    if not 1 <= len(manifest["command"]) <= 32:
        raise ManifestError("command: 1 to 32 words")
    for word in manifest["command"]:
        _line(word, "command word")
        if word.startswith("/") or ".." in word.split("/"):
            raise ManifestError(f"command word leaves the repo: {word!r}")
    if not 1 <= len(manifest["paths"]) <= 16:
        raise ManifestError("paths: 1 to 16 directories")
    for path in manifest["paths"]:
        if not PATH.match(path) or path.startswith("/") or ".." in path.split("/"):
            raise ManifestError(f"path {path!r}")
    if not isinstance(manifest["run_seconds"], int) or not 1 <= manifest["run_seconds"] <= 51:
        raise ManifestError(f"run_seconds {manifest['run_seconds']!r}")

    configs = {}
    if not 1 <= len(manifest["configs"]) <= 24:
        raise ManifestError("configs: 1 to 24")
    for entry in manifest["configs"]:
        what = f"config {entry.get('name')!r}"
        _keys(entry, {"name", "source", "file", "reduced", "why"}, set(), what)
        check_name(entry["name"], what)
        _line(entry["source"], f"{what} source")
        _line(entry["why"], f"{what} why")
        if not PATH.match(entry["file"]) or not any(
            entry["file"].startswith(p.rstrip("/") + "/") for p in manifest["paths"]
        ):
            raise ManifestError(f"{what}: file {entry['file']!r} not under paths")
        if len(entry["reduced"]) > 16:
            raise ManifestError(f"{what}: more than 16 reduced keys")
        for key in entry["reduced"]:
            check_name(key, f"{what} reduced key")
        if entry["name"] in configs:
            raise ManifestError(f"{what}: named twice")
        configs[entry["name"]] = entry
    files = [c["file"] for c in configs.values()]
    if len(set(files)) != len(files):
        raise ManifestError("two configurations share a file")

    cells, pairs = set(), set()
    if not 2 <= len(manifest["workloads"]) <= 24:
        raise ManifestError("workloads: 2 to 24 cells")
    for entry in manifest["workloads"]:
        what = f"cell {entry.get('name')!r}"
        _keys(entry, {"name", "config", "traffic", "chips", "why"}, set(), what)
        check_name(entry["name"], what)
        check_name(entry["traffic"], f"{what} traffic")
        _line(entry["why"], f"{what} why")
        if entry["config"] not in configs:
            raise ManifestError(f"{what}: unknown config {entry['config']!r}")
        if entry["chips"] not in (1, 4):
            raise ManifestError(f"{what}: chips {entry['chips']!r}")
        pair = (entry["config"], entry["traffic"])
        if entry["name"] in cells or pair in pairs:
            raise ManifestError(f"{what}: name or (config, traffic) appears twice")
        cells.add(entry["name"])
        pairs.add(pair)
    unused = set(configs) - {w["config"] for w in manifest["workloads"]}
    if unused:
        raise ManifestError(f"configurations no cell uses: {sorted(unused)}")
    four = sum(1 for w in manifest["workloads"] if w["chips"] == 4)
    if four > max(1, len(manifest["workloads"]) // 4):
        raise ManifestError(f"{four} cells ask for four chips")

    if not 1 <= len(manifest["end_to_end"]) <= 16:
        raise ManifestError("end_to_end: 1 to 16 metrics")
    if not 1 <= len(manifest["per_layer"]) <= 128:
        raise ManifestError("per_layer: 1 to 128 metrics")
    end_to_end = {m.get("name") for m in manifest["end_to_end"]}
    if "setup_s" not in end_to_end:
        raise ManifestError("end_to_end lacks setup_s")
    names = [m.get("name") for m in manifest["end_to_end"] + manifest["per_layer"]]
    if len(set(names)) != len(names):
        raise ManifestError("two metrics share a name")
    for entry in manifest["end_to_end"]:
        _metric(entry, False, end_to_end, cells)
    for entry in manifest["per_layer"]:
        _metric(entry, True, end_to_end, cells)
    for cell in cells:
        mine = metrics_of(manifest, cell)
        for name, entry in mine["per_layer"].items():
            if entry["moves"] not in mine["end_to_end"]:
                raise ManifestError(
                    f"metric {name!r} is reported in cell {cell!r}, where "
                    f"{entry['moves']!r}, which it should move, is not: list "
                    f"its cells under \"workloads\""
                )
        if "setup_s" not in mine["end_to_end"] or len(mine["end_to_end"]) < 2:
            raise ManifestError(f"cell {cell!r}: needs setup_s and one more end-to-end metric")
        if not mine["per_layer"]:
            raise ManifestError(f"cell {cell!r}: no per-layer metric")
    if len(json.dumps(manifest)) > 64 * 1024:
        raise ManifestError("BENCHMARK.json over 64 KiB")


def load(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    validate(manifest)
    return manifest


def _in_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def metrics_of(manifest: dict, cell: str) -> dict:
    """The cell's metrics by name: those that list the cell under `workloads`
    or have no such list. `validate` holds every per-layer metric to the cells
    of the end-to-end metric it moves."""
    end_to_end = {
        m["name"]: m for m in manifest["end_to_end"] if _in_cell(m, cell)
    }
    per_layer = {
        m["name"]: m
        for m in manifest["per_layer"]
        if _in_cell(m, cell)
    }
    return {"end_to_end": end_to_end, "per_layer": per_layer}


def cell(manifest: dict, name: str) -> dict:
    """The cell's entry with its configuration file and traffic mix loaded."""
    for entry in manifest["workloads"]:
        if entry["name"] == name:
            break
    else:
        known = [w["name"] for w in manifest["workloads"]]
        raise ManifestError(f"unknown workload {name!r}; BENCHMARK.json has {known}")
    config_entry = next(c for c in manifest["configs"] if c["name"] == entry["config"])
    with open(os.path.join(ROOT, config_entry["file"])) as f:
        config = json.load(f)
    traffic_path = os.path.join(BENCH, "traffic", entry["traffic"] + ".json")
    if not os.path.exists(traffic_path):
        raise ManifestError(
            f"traffic mix {entry['traffic']!r}: no benchmark/traffic/"
            f"{entry['traffic']}.json"
        )
    with open(traffic_path) as f:
        traffic = json.load(f)
    return {**entry, "config_file": config, "traffic_mix": traffic}
