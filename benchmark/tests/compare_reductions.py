"""One kept trace reduced twice: by another `xplane.py` (the parent's, say)
and by `lib/xplane.py`, with every per-layer reader run over both. Run on the
machine with the chip, after the runs whose traces were kept (the readers ask
JAX for the device's kind):

    python3 benchmark/run.py --workload <cell> --seed <n> --trace 1 --keep-trace <dir>
    python3 benchmark/tests/compare_reductions.py --kept <dir> \
        --other <a copy of another commit's benchmark/lib/xplane.py> --out <file.jsonl>

`--keep-trace` leaves `<cell>-seed<n>.xplane.pb` and beside it
`.collected.pkl`, what the readers were handed. A line of the output holds, for
each reduction, `busy_s`, `window_s`, the idle share, the five largest
`op_seconds` and every per-layer metric of the cell, and what the trace shows
at the window's two edges: the device operations that cross each, and how far
the profile reaches past them.
"""

from __future__ import annotations

import argparse
import glob
import importlib.util
import json
import os
import pickle
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

from lib import layer_metrics, manifest as manifest_lib, xplane  # noqa: E402


def load_other(path: str):
    spec = importlib.util.spec_from_file_location("other_xplane", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reading(reduced: dict, names, collected: dict) -> dict:
    metrics = layer_metrics.read_all(names, {**collected, "trace": reduced})
    ops = sorted(reduced["op_seconds"].items(), key=lambda kv: -kv[1])
    return {
        "busy_s": reduced["busy_s"],
        "window_s": reduced["window_s"],
        "idle_share": reduced["idle_share"],
        "op_seconds_sum": sum(reduced["op_seconds"].values()),
        "top_ops": ops[:5],
        "no_program_s": sum(s for name, s in ops if name.startswith("no program/")),
        "modules": reduced["modules"],
        "metrics": {k: v for k, v in metrics.items() if v is not None},
    }


def edges(events, reduced: dict, profiled_s) -> dict:
    """Where the device planes' events lie about the window's edges, in
    seconds: on one clock with the marker, operations cross both edges of a
    device that never idles, and the profile reaches past both."""
    opens, closes, _ = xplane.window_of(events, reduced["host_window_s"])
    ops = [e for e in events if e[1] == xplane.OPS_LINE]
    return {
        "ops": len(ops),
        "ops_across_the_opening": sum(1 for e in ops if e[3] < opens < e[3] + e[4]),
        "ops_across_the_close": sum(1 for e in ops if e[3] < closes < e[3] + e[4]),
        "first_op_before_the_opening_s": (opens - min(e[3] for e in ops)) / 1e9,
        "last_op_after_the_close_s": (max(e[3] + e[4] for e in ops) - closes) / 1e9,
        "window_from": reduced["window_from"],
        "host_window_s": reduced["host_window_s"],
        "profiled_s": profiled_s,
        "busy_outside_s": reduced["busy_outside_s"],
    }


def compare(path: str, other) -> dict:
    with open(path.replace(".xplane.pb", ".collected.pkl"), "rb") as f:
        kept = pickle.load(f)
    collected = kept["collected"]
    host_window_s = collected["trace"]["host_window_s"]
    # What the other reduction was handed for this profile before the window
    # was marked: `start_trace()`'s return to the call of `stop_trace()`.
    profiled_s = collected["trace"].get("profiled_s") or host_window_s + 2 * xplane.GUARD_S
    names = manifest_lib.metrics_of(manifest_lib.load(), kept["workload"])["per_layer"]
    events = xplane.load_events(path)
    mine = xplane.reduce_events(events, host_window_s)
    theirs = other.reduce_events(other.load_events(path), profiled_s)
    return {
        "workload": kept["workload"], "seed": kept["seed"],
        "trace_bytes": os.path.getsize(path),
        "edges": edges(events, mine, collected["trace"].get("profiled_s")),
        "other": reading(theirs, names, collected),
        "mine": reading(mine, names, collected),
        "as_reported": kept["metrics"],
    }


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--kept", required=True)
    parser.add_argument("--other", required=True)
    parser.add_argument("--out", required=True)
    args = parser.parse_args()
    other = load_other(args.other)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "a") as out:
        for path in sorted(glob.glob(os.path.join(args.kept, "*.xplane.pb"))):
            row = compare(path, other)
            out.write(json.dumps(row) + "\n")
            out.flush()
            print(row["workload"], row["seed"],
                  "other idle", row["other"]["idle_share"], "mine", row["mine"]["idle_share"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
