"""From the profiler's `.xplane.pb` to device busy and idle time, time by
operation, time by program, idle gaps and exposed collectives.

A trace is read into plain events, `(plane, line, name, start_ns, dur_ns)`,
and everything else is arithmetic on those, so the arithmetic is tested on
hand-made events and on a small trace recorded on the chip
(`benchmark/tests/`). On a TPU each chip is a plane `/device:TPU:<n>`; its
line `XLA Ops` holds one event per executed HLO operation, named by the whole
instruction as XLA prints it (`%fusion.13 = f32[...] fusion(...), kind=...`;
a `while` holds its body's operations nested inside it), and its line
`XLA Modules` one event per executed program, named
`<jitted function>(<fingerprint>)`. Asynchronous copies run beside the
operations on a line of their own and are not counted as busy time.

An operation is reported as `<program>/<instruction> <opcode>`, for example
`jit_step/fusion.13 fusion`. A Mosaic (Pallas) kernel is a `custom-call` with
the target `tpu_custom_call` and is reported with that as its opcode: XLA
names the instruction after the flax module it sits in (`h_11.3`), not after
the kernel, so a kernel is told by its opcode and the program it runs in.

Every figure is of ONE window, on the trace's own clock. The runner marks what
it times with a host annotation (`TracedWindow`, `benchmark.window`), which the
profiler writes on the host plane of the same profile; `reduce_events` takes
the window from that event and cuts every device interval to it before
anything is summed. So busy time, time by operation, collectives and gaps are
sums over the same clipped intervals: `0 <= busy_s <= window_s`,
`sum(op_seconds) == busy_s`, and busy time plus the gaps is the window. A
trace without the marker (one recorded before PR 56) is cut to a window of
the length handed in that ends with the last device event.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
import time
from collections import defaultdict
from typing import Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = re.compile(r"^/host:CPU$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# The host annotation that marks the window, and the line its event is
# given in the events read (on the host plane it sits on its thread's line).
WINDOW_MARKER = "benchmark.window"
# Kept clear of the marker on both sides, inside the profile: what the device
# planes hold begins up to 20 ms after `start_trace()` has returned and ends
# between 6 ms before and 10 ms after `stop_trace()` is called (37 profiles on
# the chip, PERF.md section 6, PR 56), and a window that opens before the
# device is traced would read its first milliseconds as idle.
GUARD_S = 0.1
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
)
INSTRUCTION = re.compile(r"^%?(?P<name>[^\s=]+) = .*? (?P<opcode>[a-z][\w\-]*)\(")
MOSAIC = 'custom_call_target="tpu_custom_call"'


def short_name(event_name: str) -> str:
    """`%h_3.2 = (bf16[...]) custom-call(...), custom_call_target=
    "tpu_custom_call"` -> `h_3.2 tpu_custom_call`; a name that is no HLO
    instruction is kept as it is."""
    found = INSTRUCTION.match(event_name)
    if not found:
        return event_name[:80]
    opcode = "tpu_custom_call" if MOSAIC in event_name else found["opcode"]
    return f"{found['name']} {opcode}"
Event = Tuple[str, str, str, float, float]


def newest_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def start_trace(trace_dir: str) -> None:
    """Start the profiler into an empty `trace_dir`, without the Python
    tracer: it slows the host it is meant to observe. Only the process that
    holds the chip can trace it."""
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def stop_trace() -> None:
    import jax

    jax.profiler.stop_trace()


class TracedWindow:
    """The one place that times a trace. `open()` starts the profiler and,
    once it runs, enters the annotation that marks the window in the profile
    itself; `close()` leaves it, stops the profiler, and returns the host
    clock's reading of the same stretch, which rides beside the marker's own
    duration as `host_window_s` so that two clocks which disagree are seen.
    Both calls on one thread: an annotation belongs to the thread it is
    entered on."""

    def __init__(self, trace_dir: str):
        self.trace_dir = trace_dir
        self.opened: Optional[float] = None
        # From `start_trace()`'s return to the call of `stop_trace()`, on the
        # host's clock: what was taken for the window until PR 56.
        self.profiled_s: Optional[float] = None
        self._profiling: Optional[float] = None
        self._marker = None

    def open(self) -> None:
        import jax

        start_trace(self.trace_dir)
        self._profiling = time.monotonic()
        time.sleep(GUARD_S)
        self._marker = jax.profiler.TraceAnnotation(WINDOW_MARKER)
        self.opened = time.monotonic()
        self._marker.__enter__()

    def close(self) -> float:
        self._marker.__exit__(None, None, None)
        host_window_s = time.monotonic() - self.opened
        time.sleep(GUARD_S)
        self.profiled_s = time.monotonic() - self._profiling
        stop_trace()
        return host_window_s


def reduce_trace(trace_dir: str, host_window_s: float, profiled_s: Optional[float] = None,
                 keep: Optional[str] = None) -> Optional[dict]:
    """`reduce_events` of the trace a `TracedWindow` left in `trace_dir`,
    which is then removed: a trace of five seconds is tens of megabytes.
    `profiled_s` is the window's own and rides along; `keep` is a path to
    move the raw `.xplane.pb` to first (`run.py --keep-trace`, for
    `tests/compare_reductions.py`)."""
    path = newest_xplane(trace_dir)
    reduced = None
    if path is not None:
        reduced = reduce_events(load_events(path), host_window_s)
        if reduced is not None:
            reduced["trace_bytes"] = os.path.getsize(path)
            reduced["profiled_s"] = profiled_s
        if keep:
            os.makedirs(os.path.dirname(keep), exist_ok=True)
            shutil.move(path, keep)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return reduced


def load_events(path: str, planes: re.Pattern = DEVICE_PLANE,
                lines: Iterable[str] = (OPS_LINE, MODULES_LINE)) -> List[Event]:
    """The device planes' operations and program runs, and the window's
    marker from the host plane where the trace has one (as an event of the
    line `WINDOW_MARKER`)."""
    from jax.profiler import ProfileData

    wanted = set(lines)
    events: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        if HOST_PLANE.match(plane.name):
            events.extend(
                (plane.name, WINDOW_MARKER, WINDOW_MARKER, float(e.start_ns),
                 float(e.duration_ns))
                for line in plane.lines for e in line.events
                if e.name == WINDOW_MARKER
            )
        if not planes.match(plane.name):
            continue
        for line in plane.lines:
            if line.name not in wanted:
                continue
            shorten = short_name if line.name == OPS_LINE else str
            events.extend(
                (plane.name, line.name, shorten(e.name), float(e.start_ns),
                 float(e.duration_ns))
                for e in line.events
            )
    return events


def merge(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Union of half-open intervals, sorted and disjoint."""
    out: List[List[float]] = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def total(intervals: Iterable[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: List[Tuple[float, float]], b: List[Tuple[float, float]]):
    """The parts of merged intervals `a` that no interval of merged `b`
    covers."""
    out, j = [], 0
    for start, end in a:
        while j < len(b) and b[j][1] <= start:
            j += 1
        k, at = j, start
        while k < len(b) and b[k][0] < end:
            if b[k][0] > at:
                out.append((at, b[k][0]))
            at = max(at, b[k][1])
            k += 1
        if at < end:
            out.append((at, end))
    return out


def self_times(ops: List[Event]) -> dict:
    """Seconds by operation name on one device, each operation charged only
    the time none of the operations nested inside it covers, so the sum over
    names is the busy time and a `while` does not count its body twice."""
    by_name: dict = defaultdict(float)
    stack: List[list] = []  # [name, end, self_ns]

    def close(until: float) -> None:
        while stack and stack[-1][1] <= until:
            name, _, own = stack.pop()
            by_name[name] += own

    for _, _, name, start, dur in sorted(ops, key=lambda e: (e[3], -e[4])):
        close(start)
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return {name: ns / 1e9 for name, ns in by_name.items()}


def module_name(event_name: str) -> str:
    """`jit__decode_step(1234567)` -> `jit__decode_step`."""
    return re.sub(r"\(\d+\)$", "", event_name)


def _median(values: List[float]) -> float:
    ordered = sorted(values)
    mid = len(ordered) // 2
    return ordered[mid] if len(ordered) % 2 else (ordered[mid - 1] + ordered[mid]) / 2.0


def window_of(events: List[Event], host_window_s: float) -> Tuple[float, float, str]:
    """(start, end, where it comes from) of the one window every figure is
    of, in the trace's nanoseconds: the marker's own event where the trace
    has one. A trace without it is `host_window_s` long and ends with its
    last device event: `stop_trace()` was called as the host's window closed,
    so what such a profile holds beyond the window's length lies at its
    head, where the device's tracer ran before `start_trace()` returned."""
    marks = [e for e in events if e[1] == WINDOW_MARKER]
    if marks:
        _, _, _, start, dur = max(marks, key=lambda e: e[4])
        return start, start + dur, "marker"
    end = max(e[3] + e[4] for e in events if e[1] in (OPS_LINE, MODULES_LINE))
    return end - host_window_s * 1e9, end, "last_device_event"


def clip(events: List[Event], start: float, end: float) -> List[Event]:
    """The events' parts inside `[start, end]`, in the order given; an event
    of no duration is kept where it lies inside."""
    out = []
    for plane, line, name, at, dur in events:
        a, b = max(at, start), min(at + dur, end)
        if b > a or (dur == 0 and start <= at <= end):
            out.append((plane, line, name, a, b - a))
    return out


def reduce_events(events: List[Event], host_window_s: float) -> Optional[dict]:
    """Everything the per-layer metrics and the breakdown read, averaged
    over the device planes found. Every interval is cut to `window_of` the
    events before anything is summed, so busy time, time by operation,
    collectives and gaps are of that window and of nothing else. None when no
    operation ran on a device inside it: there is nothing to report, and the
    caller reports nothing."""
    planes = sorted({e[0] for e in events if e[1] == OPS_LINE})
    if not planes:
        return None
    opens, closes, window_from = window_of(events, host_window_s)
    busy_s, exposed_s, collective_s = [], [], []
    outside = [0.0, 0.0]  # busy seconds the profile holds before and after
    op_seconds: dict = defaultdict(float)
    gap_seconds: dict = defaultdict(float)
    module_runs: dict = defaultdict(list)
    module_parts: dict = defaultdict(lambda: [0.0, 0.0, 0])  # seconds, runs, cut runs
    for plane in planes:
        programs = _Programs(
            [e for e in events if e[0] == plane and e[1] == MODULES_LINE]
        )
        # Outermost first, as the trace has them: after the cut a `while` and
        # its body may start together at the window's edge, and `self_times`
        # keeps the order of what it cannot tell apart.
        ops = sorted((e for e in events if e[0] == plane and e[1] == OPS_LINE),
                     key=lambda e: (e[3], -e[4]))
        # An operation is named with the program it ran in: `fusion.7` of
        # the decode step is not `fusion.7` of a prefill.
        ops = [(p, l, f"{programs.at(start)}/{name}", start, dur)
               for p, l, name, start, dur in ops]
        whole = merge((e[3], e[3] + e[4]) for e in ops)
        outside[0] += total((a, min(b, opens)) for a, b in whole if a < opens)
        outside[1] += total((max(a, closes), b) for a, b in whole if b > closes)
        ops = clip(ops, opens, closes)
        busy = merge((e[3], e[3] + e[4]) for e in ops)
        busy_s.append(total(busy) / 1e9)
        for name, seconds in self_times(ops).items():
            op_seconds[name] += seconds / len(planes)
        kinds = [(_op(e[2]), e) for e in ops]
        collectives = merge(
            (e[3], e[3] + e[4]) for op, e in kinds if COLLECTIVE.match(op)
        )
        compute = merge(
            (e[3], e[3] + e[4]) for op, e in kinds
            if not COLLECTIVE.match(op) and not _is_container(op)
        )
        collective_s.append(total(collectives) / 1e9)
        exposed_s.append(total(subtract(collectives, compute)) / 1e9)
        # A run that an edge cuts is in busy time by its part inside and in
        # no median: a run read short would bias every `*_device_ms`. What
        # multiplies a run's work by the runs traced (the rooflines) takes
        # `window_runs`, which counts a cut run by its share inside.
        for _, _, name, start, dur in programs.modules:
            part = min(start + dur, closes) - max(start, opens)
            if part < 0 or (part == 0 and dur > 0):
                continue
            parts = module_parts[module_name(name)]
            parts[0] += part / 1e9
            parts[1] += part / dur if dur else 1.0
            if start >= opens and start + dur <= closes:
                module_runs[module_name(name)].append(dur / 1e9)
            else:
                parts[2] += 1
        # A gap is named by the programs that ran before and after it, the
        # first and the last by the window's edge on their other side.
        edges = [(opens, opens)] + busy + [(closes, closes)]
        for i, ((_, prev_end), (next_start, _)) in enumerate(zip(edges, edges[1:])):
            if next_start <= prev_end:
                continue
            before = None if i == 0 else programs.run_at(prev_end - 1.0)
            after = None if i == len(busy) else programs.run_at(next_start)
            if before is not None and before == after:
                name = f"inside {programs.name(before)}"
            else:
                name = (f"{'window opens' if before is None else programs.name(before)} -> "
                        f"{'window closes' if after is None else programs.name(after)}")
            gap_seconds[name] += (next_start - prev_end) / 1e9 / len(planes)
    mean_busy = sum(busy_s) / len(planes)
    if not mean_busy:
        return None
    window_s = (closes - opens) / 1e9
    idle_s = sum(gap_seconds.values())

    def top(table: dict) -> list:
        return [[k, v] for k, v in sorted(table.items(), key=lambda kv: -kv[1])[:10]]

    return {
        "devices": len(planes),
        "window_s": window_s,
        "window_from": window_from,
        "host_window_s": host_window_s,
        "busy_s": mean_busy,
        "idle_s": idle_s,
        "idle_share": idle_s / window_s,
        "busy_outside_s": [seconds / 1e9 / len(planes) for seconds in outside],
        "collective_s": sum(collective_s) / len(planes),
        "collective_exposed_s": sum(exposed_s) / len(planes),
        "op_seconds": dict(op_seconds),
        "modules": {
            name: {
                "runs": len(module_runs[name]) // len(planes),
                "median_s": _median(module_runs[name]) if module_runs[name] else None,
                "total_s": seconds / len(planes),
                "window_runs": runs / len(planes),
                "cut_runs": cut,
            }
            for name, (seconds, runs, cut) in module_parts.items()
        },
        "breakdown": {"device_ops": top(op_seconds), "idle_gaps": top(gap_seconds)},
    }


def _op(qualified: str) -> str:
    """`jit_b/all-reduce.1 all-reduce` -> `all-reduce`; a name without an
    opcode is taken whole."""
    return qualified.rsplit("/", 1)[-1].rsplit(" ", 1)[-1]


def _is_container(op: str) -> bool:
    return op.startswith(("while", "call", "conditional"))


class _Programs:
    """Which program ran on one device at a given time (programs on one
    device do not overlap)."""

    def __init__(self, modules: List[Event]):
        self.modules = sorted(modules, key=lambda e: e[3])
        self.starts = [m[3] for m in self.modules]

    def run_at(self, when: float) -> int:
        """Index of the execution running at `when`, or -1."""
        i = bisect.bisect_right(self.starts, when) - 1
        if i >= 0 and when < self.modules[i][3] + self.modules[i][4]:
            return i
        return -1

    def name(self, run: int) -> str:
        return module_name(self.modules[run][2]) if run >= 0 else "no program"

    def at(self, when: float) -> str:
        return self.name(self.run_at(when))


def op_share(reduced: dict, pattern: str) -> Optional[float]:
    """Share of device busy time in operations whose name matches."""
    rx = re.compile(pattern)
    matched = [s for name, s in reduced["op_seconds"].items() if rx.search(name)]
    if not matched or not reduced["busy_s"]:
        return None
    return sum(matched) / reduced["busy_s"]


def window_runs(module: dict) -> float:
    """How often a program of `reduced["modules"]` ran in the window, a run
    that an edge cuts counted by its share inside: what a reader multiplies a
    run's work by where it divides by seconds that were cut the same way."""
    return module.get("window_runs", module["runs"])


def module_median_s(reduced: dict, pattern: str) -> Optional[float]:
    """Median device duration over the executions of the programs whose name
    matches, weighted by how often each ran."""
    rx = re.compile(pattern)
    found = [m for name, m in reduced["modules"].items()
             if rx.search(name) and m["runs"]]
    runs = sum(m["runs"] for m in found)
    if not runs:
        return None
    return sum(m["median_s"] * m["runs"] for m in found) / runs
