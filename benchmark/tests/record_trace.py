"""How `tests/data/tiny_chip_trace.xplane.pb` was recorded, and a look inside
any trace. Run on the machine with the chip:

    python3 benchmark/tests/record_trace.py --record chiprun_out/tiny_chip_trace
    python3 benchmark/tests/record_trace.py --dump <file.xplane.pb>

`--record` traces three executions of one small jitted program (two matmuls
and a `fori_loop`) with a pause between them, so the trace holds busy
intervals, idle gaps, a `while` with operations nested inside it, and one
program on the `XLA Modules` line. `--dump` prints planes, lines, and for the
busiest event names of each line a count, the summed time and one event's
stats: what a reader of names in `lib/xplane.py` or a metric's regex is
written against.
"""

from __future__ import annotations

import argparse
import collections
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def record(out_dir: str) -> str:
    import jax
    import jax.numpy as jnp

    from lib import xplane

    def tiny_program(x):
        y = jnp.tanh(x @ x)
        return jax.lax.fori_loop(0, 3, lambda _, z: jnp.tanh(z @ x) + 1.0, y)

    step = jax.jit(tiny_program)
    x = jnp.ones((512, 512), jnp.bfloat16)
    step(x).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(out_dir, profiler_options=options)
    for _ in range(3):
        step(x).block_until_ready()
        time.sleep(0.002)
    jax.profiler.stop_trace()
    path = xplane.newest_xplane(out_dir)
    print("recorded", path, os.path.getsize(path), "bytes on", jax.devices()[0].device_kind)
    return path


def dump(path: str, top: int = 25) -> None:
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            if not events:
                continue
            by_name = collections.defaultdict(lambda: [0, 0.0, None])
            for e in events:
                row = by_name[e.name]
                row[0] += 1
                row[1] += e.duration_ns
                row[2] = row[2] or e
            first = min(e.start_ns for e in events)
            last = max(e.start_ns + e.duration_ns for e in events)
            print(f"  LINE {line.name!r}: {len(events)} events, {len(by_name)} names, "
                  f"span {(last - first) / 1e6:.3f} ms")
            for name, (count, ns, e) in sorted(by_name.items(), key=lambda kv: -kv[1][1])[:top]:
                stats = {k: (str(v)[:120]) for k, v in list(e.stats)[:8]}
                print(f"    {ns / 1e6:10.3f} ms {count:6d}x {name[:100]!r} {stats}")


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--record")
    parser.add_argument("--dump")
    args = parser.parse_args()
    path = record(args.record) if args.record else args.dump
    dump(path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
