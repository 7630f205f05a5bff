"""Fleet time ledger — engine step wall time by measured phase.

Every flight-recorder step record (llm/engine.py) carries `duration_s`
and `phases`: the seconds the step's phase clock
(`llm.observability.StepPhaseClock`) charged to schedule / prepare /
wait / commit / other between the step's entry and the record. One
`perf_counter` reading closes a phase and opens the next, so the phases
sum to `duration_s` by measurement; `step_ledger` renames them into
columns and guesses nothing.

Columns (the partition):

- ``idle_s``      — steps that did no work (phase "idle": no dispatch,
                    no prefill, no commit): the loop polled and found
                    nothing runnable.
- ``schedule_s``  — deadline sweep, admission, prefix match, fabric
                    probe and restores, chunk and decode planning.
- ``prepare_s``   — filling input buffers (and the proposer, under
                    speculation) up to the return of each program's
                    dispatch call.
- ``host_wait_s`` — the host blocked on a program's results. Not device
                    time: the device may have finished earlier (async
                    loop: it usually has, the fetch is a step late) and
                    a program queued behind another waits its turn
                    inside this column.
- ``commit_s``    — tokens appended, blocks published, emission,
                    finishes, after results were on the host.
- ``other_s``     — the rest of the step: gauges and the record itself.
                    A record without `phases` lands here whole.

Overlay (NOT part of the partition — do not add it to the sum):

- ``host_exposed_s`` — the step's share of the phase clock's
                    `host_exposed`: at each program's dispatch, the time
                    since the device had nothing queued, as the host
                    sees it. It runs across the non-wait columns (and
                    the `between` before the step), so it overlaps them;
                    a chained dispatch samples 0, so it reads 0 where the
                    device runs dry under one (PERF.md section 7.9a).

`replica_ledger` sums step ledgers over a flight-record ring and adds a
``loop_s`` column for the wall-clock span not covered by any step record
(LLMServer._loop overhead, sleeps between steps): span from the first
step's start to the last step's end, minus the sum of step durations.
With that column, ledger columns sum to ~100% of the replica's measured
wall span — the acceptance check `make obs-smoke` asserts.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

# Partition columns and the phase each reads. `replica_ledger` adds
# "loop_s" (inter-step wall not inside any step record) at the end.
_PHASE_COLUMNS = (
    ("schedule_s", "schedule"),
    ("prepare_s", "prepare"),
    ("host_wait_s", "wait"),
    ("commit_s", "commit"),
    ("other_s", "other"),
)
LEDGER_COLUMNS = ("idle_s",) + tuple(col for col, _ in _PHASE_COLUMNS)

REPLICA_COLUMNS = LEDGER_COLUMNS + ("loop_s",)


def step_ledger(record: dict) -> dict:
    """One flight-record step's `duration_s` by LEDGER_COLUMNS, from the
    step's measured `phases` (they sum to the duration; what rounding or
    a record without phases leaves over lands in `other_s`), plus the
    `host_exposed_s` overlay."""
    duration = float(record.get("duration_s") or 0.0)
    out = {col: 0.0 for col in LEDGER_COLUMNS}
    out["duration_s"] = duration
    out["host_exposed_s"] = float(record.get("host_exposed_s") or 0.0)
    if record.get("phase") == "idle":
        out["idle_s"] = duration
        return out
    phases = record.get("phases") or {}
    for col, phase in _PHASE_COLUMNS:
        out[col] = float(phases.get(phase) or 0.0)
    out["other_s"] += duration - sum(out[col] for col in LEDGER_COLUMNS)
    return out


def _committed_tokens(steps: Sequence[dict]) -> int:
    total = 0
    for record in steps:
        for entry in record.get("commits") or ():
            if isinstance(entry, dict):
                total += int(entry.get("tokens") or 0)
    return total


def replica_ledger(
    steps: Sequence[dict],
    *,
    model_params: Optional[int] = None,
    peak_flops_per_s: Optional[float] = None,
) -> dict:
    """Aggregate step ledgers over one replica's flight-record ring.

    Returns column sums (REPLICA_COLUMNS, incl. the inter-step
    ``loop_s``), per-column fractions of the measured wall span,
    goodput (committed tokens / span), and an MFU estimate when both
    `model_params` and a peak-FLOPs figure are known.
    """
    columns = {col: 0.0 for col in REPLICA_COLUMNS}
    steps = [s for s in steps if s.get("duration_s") is not None]
    if not steps:
        return {
            "steps": 0,
            "wall_s": 0.0,
            "columns": columns,
            "fractions": {},
            "ledger_sum_s": 0.0,
            "coverage": None,
            "host_exposed_s": 0.0,
            "committed_tokens": 0,
            "goodput_tokens_per_s": 0.0,
            "mfu": None,
        }

    host_exposed = 0.0
    duration_total = 0.0
    for record in steps:
        step = step_ledger(record)
        for col in LEDGER_COLUMNS:
            columns[col] += step[col]
        host_exposed += step["host_exposed_s"]
        duration_total += step["duration_s"]

    # Replica wall = wall-clock span from the first recorded step's start
    # to the last one's end. duration_s is perf_counter-measured, so the
    # coverage ratio below is a real cross-clock check, not a tautology.
    first = steps[0]
    last = steps[-1]
    span = None
    if first.get("time") is not None and last.get("time") is not None:
        span = (float(last["time"]) + float(last.get("duration_s") or 0.0)) - (
            float(first["time"])
        )
    if span is None or span <= 0.0:
        span = duration_total
    columns["loop_s"] = max(span - duration_total, 0.0)

    ledger_sum = sum(columns[col] for col in REPLICA_COLUMNS)
    wall = max(span, 1e-9)
    fractions = {col: columns[col] / wall for col in REPLICA_COLUMNS}
    tokens = _committed_tokens(steps)
    goodput = tokens / wall
    if peak_flops_per_s is None:
        peak_flops_per_s = default_peak_flops_per_s()
    return {
        "steps": len(steps),
        "wall_s": span,
        "columns": columns,
        "fractions": fractions,
        "ledger_sum_s": ledger_sum,
        # ledger_sum / wall — the ~100% acceptance number.
        "coverage": ledger_sum / wall,
        "host_exposed_s": host_exposed,
        "committed_tokens": tokens,
        "goodput_tokens_per_s": goodput,
        "mfu": mfu_estimate(model_params, goodput, peak_flops_per_s),
    }


def mfu_estimate(
    model_params: Optional[int],
    tokens_per_s: float,
    peak_flops_per_s: Optional[float],
) -> Optional[float]:
    """Decode-side model-FLOPs-utilization: ~2 FLOPs per parameter per
    generated token (forward pass), over device peak. None when either
    the parameter count or the peak figure is unknown (e.g. CPU runs
    have no meaningful peak)."""
    if not model_params or not peak_flops_per_s or peak_flops_per_s <= 0:
        return None
    return (2.0 * float(model_params) * float(tokens_per_s)) / float(
        peak_flops_per_s
    )


def default_peak_flops_per_s() -> Optional[float]:
    """Per-device peak FLOP/s for MFU accounting. No portable API exposes
    this, so it comes from the RAY_TPU_PEAK_FLOPS env var (set it to the
    accelerator's spec number, e.g. 275e12 for TPU v4 bf16); None means
    MFU is reported as unknown rather than guessed."""
    raw = os.environ.get("RAY_TPU_PEAK_FLOPS")
    if not raw:
        return None
    try:
        value = float(raw)
    except ValueError:
        return None
    return value if value > 0 else None


def fleet_ledger(replicas: dict) -> dict:
    """Merge per-replica ledgers ({replica_name: replica_ledger()}) into
    one fleet view: column sums, busiest-column ranking, total goodput
    (sum of per-replica goodputs — replicas run concurrently, so
    tokens/s adds), and the worst per-replica coverage (the number the
    obs-smoke gate checks)."""
    columns = {col: 0.0 for col in REPLICA_COLUMNS}
    tokens = 0
    goodput = 0.0
    wall = 0.0
    coverages = []
    mfus = []
    for ledger in replicas.values():
        for col in REPLICA_COLUMNS:
            columns[col] += ledger["columns"].get(col, 0.0)
        tokens += ledger["committed_tokens"]
        goodput += ledger["goodput_tokens_per_s"]
        wall = max(wall, ledger["wall_s"])
        if ledger.get("coverage") is not None:
            coverages.append(ledger["coverage"])
        if ledger.get("mfu") is not None:
            mfus.append(ledger["mfu"])
    total = sum(columns.values())
    fractions = (
        {col: columns[col] / total for col in REPLICA_COLUMNS}
        if total > 0
        else {}
    )
    ranked = sorted(
        ((col, columns[col]) for col in REPLICA_COLUMNS),
        key=lambda kv: kv[1],
        reverse=True,
    )
    return {
        "replicas": len(replicas),
        "columns": columns,
        "fractions": fractions,
        "bottlenecks": [col for col, v in ranked if v > 0],
        "committed_tokens": tokens,
        "goodput_tokens_per_s": goodput,
        "wall_s": wall,
        "min_coverage": min(coverages) if coverages else None,
        "max_coverage": max(coverages) if coverages else None,
        "mfu": max(mfus) if mfus else None,
    }
