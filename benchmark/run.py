"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process that holds the cell's chips: it builds weights on the device
from the seed, warms the cell's own programs, measures for `--seconds` and
prints, as the last line of standard output, one JSON object with `correct`,
`attempted`, `failed`, `metrics`, `device` and, when traced, `breakdown`.
With `--trace 0` the metrics are the cell's end-to-end metrics; with
`--trace 1` a few seconds in the middle of the window are profiled and the
metrics are its per-layer metrics. Other useful numbers go on earlier lines
(`{"info": ...}`) and into `benchmark/out/`.

It exits with a code other than 0 and prints no result when JAX finds no TPU
or fewer chips than the cell asks for, when the device's kind has no
published peak, when the program is not in the checkout, when something
compiled inside the window, or when a traced run saw no device operation or
none of the cell's per-layer readers found a value.

    --rehearse        toy widths on the CPU backend, kernels interpreted: finds
                      wrong paths before a chip call; every line is tagged
                      "rehearsal": true, every time reads "not measured", and
                      no result line is printed
    --sweep 2,3,4     serving cells: one deployment under one arrival rate
                      (or client count) after another, to find the knee; no
                      result line
    --reference-seed  weights of the reference, to show `correct` go false
    --control         serving cells: also put the int8 control's logit gaps on
                      the same prompts and tokens, and the sample with one
                      served token altered, through the comparison that decides
                      `correct` (a `control` info line); exits 3 where either
                      comes out correct. The driver never asks for it
    --keep-trace DIR  a traced run: keep the raw `.xplane.pb` and what the
                      readers were handed in DIR, for
                      `tests/compare_reductions.py`. The driver never asks
                      for it

See benchmark/README.md for how a cell, a configuration, a mix or a per-layer
metric is added as files.
"""

from __future__ import annotations

import time

PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import pickle  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]

from lib import device, layer_metrics, manifest as manifest_lib  # noqa: E402

TIME_SUFFIXES = ("_s", "_ms", "_per_s", "_share", "_mfu", "_by_third")


def _not_measured(value, key: str = ""):
    """A rehearsal's clock times the CPU backend and the interpreter: no
    time, rate or share leaves it under any name."""
    if key.endswith(TIME_SUFFIXES) and value is not None:
        return "not measured"
    if isinstance(value, dict):
        return {k: _not_measured(v, k) for k, v in value.items()}
    return value


# What a traced run says of its window on the `summary` line.
TRACE_WINDOW = ("window_s", "window_from", "host_window_s", "profiled_s", "busy_s",
                "idle_s", "busy_outside_s", "trace_bytes")


def load_runner(name: str):
    manifest_lib.check_name(name, "runner")
    path = os.path.join(HERE, "runners", name + ".py")
    spec = importlib.util.spec_from_file_location(f"runner_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument("--sweep", default="")
    parser.add_argument("--reference-seed", type=int, default=None)
    parser.add_argument("--control", action="store_true")
    parser.add_argument("--keep-trace", default=None, metavar="DIR")
    args = parser.parse_args(argv)

    # Everything that can be wrong without a chip is found before one is
    # touched: the manifest by the driver's rules, the cell and its files.
    manifest = manifest_lib.load()
    cell = manifest_lib.cell(manifest, args.workload)
    mine = manifest_lib.metrics_of(manifest, cell["name"])
    for name in mine["per_layer"]:
        if not os.path.exists(os.path.join(layer_metrics.DIR, name + ".json")):
            sys.exit(f"benchmark: per-layer metric {name!r} has no reader file")
    if not os.path.isdir(os.path.join(ROOT, "ray_tpu")):
        sys.exit("benchmark: the program (ray_tpu/) is not in this checkout")
    seconds = float(args.seconds if args.seconds is not None else manifest["run_seconds"])

    device.prepare_environment(args.rehearse, cell["chips"])
    # A rehearsal compiles in seconds and the CPU is left uncached, as the
    # program leaves it.
    cache_dir = None if args.rehearse else device.place_cache()
    found = device.find_devices(cell["chips"], args.rehearse)
    tag = {"rehearsal": True} if args.rehearse else {}
    out_dir = os.path.join(HERE, "out", cell["name"])
    os.makedirs(out_dir, exist_ok=True)
    lines_path = os.path.join(out_dir, f"seed{args.seed}-trace{args.trace}.jsonl")
    with open(lines_path, "w") as lines:

        def emit(kind: str, **fields) -> None:
            if args.rehearse:
                fields = _not_measured(fields)
            line = json.dumps({"info": kind, **tag, **fields})
            print(line, flush=True)
            lines.write(line + "\n")
            lines.flush()

        emit(
            "start", workload=cell["name"], seed=args.seed, seconds=seconds,
            trace=args.trace, device=found, compile_cache_dir=cache_dir,
            compile_cache_entries=device.cache_entries(),
        )
        ctx = types.SimpleNamespace(
            config=cell["config_file"], traffic=cell["traffic_mix"],
            chips=cell["chips"], seed=args.seed, seconds=seconds,
            trace=bool(args.trace), rehearse=args.rehearse,
            sweep=[float(v) for v in args.sweep.split(",") if v],
            reference_seed=args.seed if args.reference_seed is None else args.reference_seed,
            control=args.control, device=found, out_dir=out_dir,
            bench_dir=HERE, emit=emit, compiles=device.CompileCounter(),
            keep_trace=args.keep_trace and os.path.join(
                args.keep_trace, f"{cell['name']}-seed{args.seed}.xplane.pb"
            ),
        )
        result = load_runner(cell["config_file"]["runner"]).run(ctx)
        if result.get("sweep"):
            return 0

        collected = result["collected"]
        # Process start to the window's opening, less what the runner says
        # `setup_s` leaves out (serving: the compile step, README.md).
        excluded = result.get("setup_excluded_s", 0.0)
        collected["setup_s"] = result["window_open"] - PROCESS_START - excluded
        trace = collected.get("trace")
        # Read by the runner before its reference ran on the chip, where it
        # can; a process's peak never falls again.
        peak = collected.get("memory_peak_bytes") or device.memory_peak_bytes(cell["chips"])
        report = {**found, "memory_peak_bytes": peak}
        missing = []
        if args.trace:
            metrics = layer_metrics.read_all(mine["per_layer"], collected)
            # A reader that found nothing leaves its metric out of the line
            # (the contract's rule), and loudly: a program or kernel renamed
            # under `ray_tpu/` must not make a metric vanish unseen.
            missing = sorted(name for name, value in metrics.items() if value is None)
            metrics = {name: v for name, v in metrics.items() if v is not None}
            units = mine["per_layer"]
            if missing:
                emit("warning", what="per-layer metrics without a value, left out",
                     missing=missing)
            if trace is not None:
                # Both of one window, the one the trace itself marks; the
                # host's clock around the same stretch rides beside them.
                for key in ("busy_s", "window_s", "host_window_s"):
                    report[key] = trace[key]
        else:
            values = {**result["end_to_end"], "setup_s": collected["setup_s"]}
            metrics = {name: values[name] for name in mine["end_to_end"]}
            units = mine["end_to_end"]
        if ctx.keep_trace and trace is not None:
            # Beside the raw trace `reduce_trace` moved there: what the
            # readers were handed, for `tests/compare_reductions.py`.
            with open(ctx.keep_trace.replace(".xplane.pb", ".collected.pkl"), "wb") as f:
                pickle.dump({"workload": cell["name"], "seed": args.seed,
                             "collected": collected, "metrics": metrics}, f)
        final = {
            "correct": bool(result["correct"]),
            "attempted": result["attempted"],
            "failed": result["failed"],
            "metrics": {
                name: {"value": value, "unit": units[name]["unit"]}
                for name, value in metrics.items()
            },
            "device": report,
        }
        if trace is not None:
            final["breakdown"] = trace["breakdown"]
        # Each number compared beside its limit, last on the line and last
        # on standard error: what the driver keeps of a run that is not correct.
        final["compared"] = {
            name: {"value": value, "limit": limit}
            for name, (value, limit) in result.get("compared", {}).items()
        }
        emit("summary", setup_s=collected["setup_s"], setup_excluded_s=excluded,
             problems=result["problems"],
             compiles_total=ctx.compiles.count,
             compile_cache_entries=device.cache_entries(),
             trace_modules=(trace or {}).get("modules"),
             trace_window=trace and {key: trace[key] for key in TRACE_WINDOW},
             lines=lines_path,
             memory_stats=device.memory_stats(cell["chips"]))
    if args.rehearse:
        print(json.dumps({"info": "rehearsal_done", **tag,
                          "note": "a CPU rehearsal proves no chip run",
                          "correct": final["correct"], "problems": result["problems"],
                          "passed_that_must_fail": result.get("passed_that_must_fail", []),
                          "would_report": sorted(final["metrics"])}), flush=True)
        return 0
    if collected["compiles_in_window"]:
        sys.exit(f"benchmark: {collected['compiles_in_window']} compilations inside the window")
    if args.trace and trace is None:
        sys.exit("benchmark: the traced window holds no device operation")
    if not metrics or any(v is None for v in metrics.values()):
        sys.exit(f"benchmark: metrics without a value: {missing or metrics}")
    if missing:
        print(f"benchmark: per-layer metrics left out: {missing}", file=sys.stderr)
    # The proxy's connection tasks that outlive `serve.shutdown` are reported
    # by asyncio as the interpreter exits, after these lines and many times
    # their length: that one message is kept off standard error, and nothing
    # else, so that these stay its last.
    logging.getLogger("asyncio").addFilter(
        lambda record: "Task was destroyed but it is pending" not in record.getMessage()
    )
    for name, pair in final["compared"].items():
        print(f"compared {name} {pair['value']} limit {pair['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(final), flush=True)
    if result.get("passed_that_must_fail"):
        print(f"benchmark: came out correct and must not: {result['passed_that_must_fail']}",
              file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
