"""Per-request serving observability: lifecycle spans + flight recorder.

Two pieces the engine hooks into (gated by EngineConfig.instrument):

  * RequestTrace — one per in-flight request. The trace context is captured
    once at submission (the LLMServer.generate actor-task span, which chains
    back through the replica task to the Serve handle caller), and every
    lifecycle phase — queue wait, prefill (full/partial/CoW), decode
    stretches, preemption + resume, terminal state — is emitted as a span
    against it from the engine loop thread via tracing.emit_span, so a
    streamed request yields one connected trace in tracing.traces().
    Decode is recorded per STRETCH (admission → preempt/finish), never per
    token: the hot loop only bumps plain floats at step boundaries.

  * FlightRecorder — a bounded ring of structured per-step records (step
    index, phase, batch size, tokens in/out, buckets, prefix-cache hits,
    preemptions, duration and the measured phase seconds that partition
    it; with speculative decoding on, verify steps add a "speculation"
    record — proposer mode, fed bucket, proposed / accepted / emitted
    counts) plus warmup compile events (per round: wall seconds and how
    much of them JAX spent tracing and lowering and in the compile
    step) and step failures from the PR 3
    poison-isolation path. Exposed through LLMServer.flight_record() and
    the dashboard /api/llm panel.

  * StepPhaseClock — the one clock of the step loop: every instant between
    a step's entry and the next step's entry belongs to exactly one of
    schedule / prepare / wait / commit / other / between, each boundary is
    one perf_counter reading, and the same boundaries open and close
    `jax.profiler.TraceAnnotation`s (`llm.step`, `llm.step.<phase>`) so a
    profiler session shows the host phases beside the device's `XLA Ops`.
    Around `prepare`, the phase that paces a host-bound step, it also
    reads the step thread's own CPU clock: the seconds of `prepare` the
    thread ran, and by difference the seconds it was off the CPU, waiting
    for the interpreter or blocked in a call that released it. Beside the
    partition it keeps `host_exposed`: host time during which the device
    had no program queued; and it counts the steps that held the thread
    over STALL_SECONDS outside `wait`.

  * CompileClock — process-wide totals of JAX's own compile events
    (tracing, lowering, the compile step, persistent-cache misses), so
    set-up is read on the same clock as the steps.

The request latency histograms live here too so every engine shares one
registered metric per name (vLLM reports the same trio — TTFT, time per
output token, e2e — as the primary serving SLO metrics).
"""

from __future__ import annotations

import gc
import logging
import threading
import time
import uuid
from collections import deque
from typing import Callable, Dict, List, Optional

import jax.monitoring
from jax.profiler import TraceAnnotation

from ray_tpu.util import tracing

logger = logging.getLogger(__name__)

# Bucket rationale: requests cover ~1 ms (cache-hit prefill of a short
# prompt on warm programs) to minutes (long decode under preemption), so
# request-level histograms use a 1-2.5-5 decade ladder across ms → minute.
REQUEST_SECONDS_BOUNDARIES = [
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
]
# Per-output-token latency: decode steps are ~100 µs – 100 ms per token
# depending on batch width and hardware; the ladder starts a decade lower.
PER_TOKEN_SECONDS_BOUNDARIES = [
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
    0.05, 0.1, 0.25, 0.5, 1.0,
]
# One engine step (a single jitted program dispatch + host bookkeeping).
STEP_SECONDS_BOUNDARIES = [
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
    0.5, 1.0, 2.5,
]


class RequestTrace:
    """Phase-span emitter for one request; all mutation happens at phase
    boundaries (admission, prefill end, preemption, finish) — zero work in
    the per-token decode path."""

    __slots__ = (
        "request_id",
        "trace_id",
        "parent_span_id",
        "root_span_id",
        "submit_s",
        "queue_start",
        "queue_waits",
        "first_token_s",
        "stretch_start",
        "stretch_base",
        "prefills",
        "preempts",
        "error",
        "egress",
    )

    def __init__(self, request_id: str, parent_ctx: Optional[tuple]):
        self.request_id = request_id
        if parent_ctx is not None:
            self.trace_id, self.parent_span_id = parent_ctx
        else:
            self.trace_id = uuid.uuid4().hex[:16]
            self.parent_span_id = None
        self.root_span_id = tracing.new_span_id()
        now = time.time()
        self.submit_s = now
        self.queue_start: Optional[float] = now  # in queue from submission
        self.queue_waits = 0
        self.first_token_s: Optional[float] = None
        self.stretch_start: Optional[float] = None
        self.stretch_base = 0  # generated-token count when the stretch began
        self.prefills = 0
        self.preempts = 0
        self.error: Optional[str] = None
        # Whoever hands this request's tokens on (LLMServer's request
        # state): its `handoff_s` / `handoff_max_s` close the root span.
        self.egress = None

    def _emit(self, name, start_s, end_s, attributes=None) -> None:
        tracing.emit_span(
            name,
            start_s,
            end_s,
            trace_id=self.trace_id,
            parent_span_id=self.root_span_id,
            attributes=attributes,
        )

    def on_admitted(self, now: float) -> float:
        """Close the current queue-wait span; returns the wait in seconds
        (initial admission and every preempt-resume each count one wait)."""
        start = self.queue_start if self.queue_start is not None else now
        self.queue_start = None
        self.queue_waits += 1
        self._emit(
            "llm.queue", start, now, {"wait": self.queue_waits - 1}
        )
        return now - start

    def on_prefilled(
        self, start_s: float, now: float, kind: str, bucket: int,
        n_tokens: int, cached_tokens: int, n_generated: int,
        chunk: int = 0, final: bool = True,
    ) -> None:
        """One prefill program ran for this request (kind: full | partial |
        cow; `chunk` indexes the dispatch within the current admission
        under chunked prefill). Only the FINAL chunk produces a token, so
        only it sets first-token time and opens a decode stretch: tokens
        generated from here to the next preempt/finish belong to it (the
        prefill's own first token is attributed to the prefill span, not
        the stretch). Continuation chunks just record their span — TTFT
        keeps exactly one observation per request either way."""
        self.prefills += 1
        self._emit(
            "llm.prefill",
            start_s,
            now,
            {
                "kind": kind,
                "bucket": bucket,
                "tokens": n_tokens,
                "cached_tokens": cached_tokens,
                "chunk": chunk,
                "final": final,
            },
        )
        if not final:
            return
        if self.first_token_s is None:
            self.first_token_s = now
        self.stretch_start = now
        self.stretch_base = n_generated

    def _close_stretch(self, now: float, n_generated: int) -> None:
        if self.stretch_start is None:
            return
        tokens = n_generated - self.stretch_base
        if tokens > 0:
            self._emit(
                "llm.decode", self.stretch_start, now, {"tokens": tokens}
            )
        self.stretch_start = None
        self.stretch_base = n_generated

    def on_preempt(self, now: float, n_generated: int) -> None:
        """Recompute-style preemption: close the decode stretch, mark the
        event, and re-enter the queue (the resume prefill reopens it)."""
        self._close_stretch(now, n_generated)
        self.preempts += 1
        self._emit("llm.preempt", now, now, {"preemption": self.preempts})
        self.queue_start = now

    def on_finish(self, now: float, seq) -> None:
        """Terminal state: close any open stretch and the request root span.
        Dead-lettered requests (finish_reason="error") close with error
        status and the step exception that killed them."""
        self._close_stretch(now, len(seq.generated))
        attrs = {
            "request_id": self.request_id,
            "prompt_tokens": len(seq.request.prompt_ids),
            "generated_tokens": len(seq.generated),
            "finish_reason": seq.finish_reason,
            "preemptions": self.preempts,
            "prefills": self.prefills,
            "status": "error" if self.error is not None else "ok",
        }
        if self.first_token_s is not None:
            attrs["ttft_s"] = self.first_token_s - self.submit_s
        if self.error is not None:
            attrs["error"] = self.error
        if self.egress is not None:
            # Commit -> the thread that streams the request, over the
            # tokens taken so far (the last ones are taken after this).
            attrs["handoff_s"] = self.egress.handoff_s
            attrs["handoff_max_s"] = self.egress.handoff_max_s
        tracing.emit_span(
            "llm.request",
            self.submit_s,
            now,
            trace_id=self.trace_id,
            parent_span_id=self.parent_span_id,
            span_id=self.root_span_id,
            attributes=attrs,
        )


# Phases of the step loop, in the order a synchronous step passes through
# them. `between` runs from a step's return to the next step's entry while
# a request is live (lock, loop-thread hand-off, stream delivery); `other`
# is what a step does outside the four named phases (gauges, the flight
# record), so the six sum to the wall time from one entry to the next.
STEP_PHASES = ("schedule", "prepare", "wait", "commit", "other", "between")
_ANNOTATED_PHASES = frozenset(("schedule", "prepare", "wait", "commit"))
# A step that holds the step thread this long outside `wait` is a stall:
# fifty to a hundred times a decode step's host work, and under the two
# that are known (a full collection of the heap warm-up leaves, over a
# second; one of 1.3 to 3.6 s with no collection in it).
STALL_SECONDS = 0.25


def _full_collections() -> int:
    return gc.get_stats()[2]["collections"]


class StepPhaseClock:
    """Partition of the step loop's wall time, how much of `prepare` the
    step thread ran, and the device's idle window as the host sees it.

    At every moment exactly one phase is current; `switch` reads
    `perf_counter` once, charges the time since the previous reading to the
    phase that was current and opens the next, so the totals sum to the
    wall time by construction. With no request live the current phase is
    None and nothing accumulates. Single writer: the thread that steps the
    engine.

    Where `prepare` opens and where it closes, `switch` also reads the
    calling thread's CPU clock (`thread_time`): `prepare_cpu` is what the
    thread ran of `prepare`, and the rest of the phase it was off the CPU,
    waiting for the interpreter behind another thread or blocked in a
    call that released it. Only `prepare`, and not every boundary: on a
    sandboxed host (gVisor, where the serving cells run) a reading of
    that clock is a trapped system call of 6 to 11 us where
    `perf_counter` is 0.09, and eight a step cost a 3 ms step 2 to 3%
    (PERF.md, PR 37); the clock also advances in 10 ms ticks there, so
    the seconds are a 100 Hz sample: sound over a window, not for a step.

    `host_exposed` is sampled where a program's dispatch call returns: the
    time since the device last had nothing left to run, as far as the host
    can know: the newest program it dispatched had become host-readable
    (programs finish in dispatch order, so that covers every older one). A
    dispatch made while a program is still out, as a chained async decode
    is, samples 0. At pipeline depth 0 the samples add up to the
    non-wait phases.

    A stall is judged where a step returns, over the stretch since the
    step before it returned (the `between` in front of a step counts as
    the step's): more than STALL_SECONDS of it outside `wait` adds one to
    `stall_steps` and hands `on_stall` the stretch by phase, what the
    thread ran of its `prepare`, and whether a collection of the oldest
    generation ran in it.
    """

    def __init__(self, on_stall: Optional[Callable[[dict], None]] = None):
        self.totals: Dict[str, float] = dict.fromkeys(STEP_PHASES, 0.0)
        self.prepare_cpu = 0.0  # seconds of `prepare` the step thread ran
        self.dispatch_steps = 0  # steps that dispatched a program
        self.dispatches = 0
        self.exposed_total = 0.0
        self.exposed_samples = 0
        self.stall_steps = 0
        self._on_stall = on_stall
        self._phase: Optional[str] = None
        self._t = 0.0
        self._cpu = 0.0  # the thread's CPU clock where `prepare` opened
        self._annotation: Optional[TraceAnnotation] = None
        self._step_annotation: Optional[TraceAnnotation] = None
        self._index = 0
        self._batch = 0
        self._entry_t = 0.0
        self._entry_totals = self.totals
        self._entry_prepare_cpu = 0.0
        self._entry_exposed = 0.0
        self._dispatches_at_entry = 0
        self._ready_seq = 0  # newest dispatch known to have finished
        self._idle_since: Optional[float] = None
        # Where the stretch a stall is judged over began, the step before's
        # return: `between`'s total and the full collections then.
        self._mark_between = 0.0
        self._mark_collections = 0

    def switch(self, phase: Optional[str]) -> float:
        """Close the current phase and open `phase`; returns the reading."""
        now = time.perf_counter()
        if self._phase is not None:
            self.totals[self._phase] += now - self._t
            if self._phase == "prepare":
                cpu = time.thread_time()
                self.prepare_cpu += cpu - self._cpu
                self._cpu = cpu  # `prepare` may open again at once
        if phase == "prepare" and self._phase != "prepare":
            self._cpu = time.thread_time()
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None
        if phase in _ANNOTATED_PHASES:
            self._annotation = TraceAnnotation("llm.step." + phase)
            self._annotation.__enter__()
        self._phase = phase
        self._t = now
        return now

    def enter_step(self, index: int) -> None:
        if self._step_annotation is not None:
            # The last step raised: close what it left open. The time
            # since then stays with the phase it was in.
            self.exit_step(live=True)
        if self._phase is None:
            # Out of an idle stretch, whose collections are no step's.
            self._mark_collections = _full_collections()
        self._index = index
        self._batch = 0
        self._step_annotation = TraceAnnotation("llm.step", step=index)
        self._step_annotation.__enter__()
        self._entry_t = self.switch("schedule")
        self._entry_totals = dict(self.totals)
        self._entry_prepare_cpu = self.prepare_cpu
        self._entry_exposed = self.exposed_total
        self._dispatches_at_entry = self.dispatches

    def exit_step(self, live: bool) -> None:
        now = self.switch("between" if live else None)
        if self.dispatches > self._dispatches_at_entry:
            self.dispatch_steps += 1
        if not live:
            # The next dispatch follows an idle stretch, not host work.
            self._idle_since = None
        if self._step_annotation is not None:
            self._step_annotation.__exit__(None, None, None)
            self._step_annotation = None
        self._judge_stall(now)

    def _judge_stall(self, now: float) -> None:
        """At a step's return: the stretch is the `between` that ran up to
        the step's entry (nothing is added to it inside a step) and the
        step itself."""
        totals, entry = self.totals, self._entry_totals
        between = totals["between"] - self._mark_between
        held = now - self._entry_t + between - (totals["wait"] - entry["wait"])
        collections = _full_collections()
        if held > STALL_SECONDS:
            self.stall_steps += 1
            if self._on_stall is not None:
                phases = {
                    phase: round(totals[phase] - entry[phase], 6)
                    for phase in STEP_PHASES
                }
                phases["between"] = round(between, 6)
                self._on_stall(
                    {
                        "step": self._index,
                        "batch_size": self._batch,
                        "held_s": round(held, 6),
                        "phases": phases,
                        "prepare_cpu_s": round(
                            self.prepare_cpu - self._entry_prepare_cpu, 6
                        ),
                        "full_collection": (
                            collections != self._mark_collections
                        ),
                        "time": time.time(),
                    }
                )
        self._mark_between = totals["between"]
        self._mark_collections = collections

    def dispatched(self) -> None:
        """A program's dispatch call returned (GPTRunner's hook): prepare
        ends, wait begins and host_exposed takes its sample. The dispatch
        is number `dispatches` from here on, for `ready`. Outside a step
        (warm-up drives the runner directly, uninstrumented steps never
        enter) nothing is on the clock."""
        if self._step_annotation is None:
            return
        now = self.switch("wait")
        if self._ready_seq < self.dispatches:
            self.exposed_samples += 1  # queued behind a running program
        elif self._idle_since is not None:
            self.exposed_total += now - self._idle_since
            self.exposed_samples += 1
        self.dispatches += 1

    def ready(self, seq: Optional[int] = None) -> None:
        """The results of dispatch `seq` (the newest when None) are on the
        host: commit begins, and the device is idle from now if nothing
        newer is out."""
        now = self.switch("commit")
        self._ready_seq = max(self._ready_seq, seq or self.dispatches)
        if self._ready_seq == self.dispatches:
            self._idle_since = now

    def describe_decode(self, batch: int, context_tokens: int) -> None:
        """What the decode dispatch of this step asks the paged kernel to
        read, on the step's annotation (the batch on a stall's record too)."""
        self._batch = batch
        if self._step_annotation is not None:
            self._step_annotation.set_metadata(
                batch=batch, context_tokens=context_tokens
            )

    def step_record(self) -> dict:
        """Seconds of the current step so far, by phase: the flight
        record's `duration_s` and `phases`, which sum to it,
        `prepare_cpu_s`, the seconds of its `prepare` the step thread ran,
        and `host_exposed_s`, the step's share of `exposed_total`."""
        now = self.switch(self._phase)
        return {
            "duration_s": round(now - self._entry_t, 6),
            "prepare_cpu_s": round(
                self.prepare_cpu - self._entry_prepare_cpu, 6
            ),
            "host_exposed_s": round(
                self.exposed_total - self._entry_exposed, 6
            ),
            "phases": {
                phase: round(self.totals[phase] - self._entry_totals[phase], 6)
                for phase in STEP_PHASES
                if phase != "between"
            },
        }

    def stats(self) -> dict:
        out = {f"step_{phase}_s": s for phase, s in self.totals.items()}
        out["step_prepare_cpu_s"] = self.prepare_cpu
        # A total, like the others, so that a reader divides a window's
        # difference of it: prepare's seconds off the CPU.
        out["step_prepare_offcpu_s"] = self.totals["prepare"] - self.prepare_cpu
        out["dispatch_steps"] = self.dispatch_steps
        out["host_exposed_total_s"] = self.exposed_total
        out["stall_steps"] = self.stall_steps
        return out


class _IntervalUnion:
    """Seconds covered by the intervals added so far. JAX reports a timed
    region when it ends, so a traced function arrives after the functions
    traced inside it and contains them: a plain sum would count the inner
    ones twice."""

    def __init__(self):
        self.total = 0.0
        self._tail: List[tuple] = []  # disjoint, ascending

    def add(self, start: float, end: float) -> None:
        tail = self._tail
        while tail and tail[-1][1] > start:
            known_start, known_end = tail.pop()
            self.total -= known_end - known_start
            start, end = min(start, known_start), max(end, known_end)
        tail.append((start, end))
        self.total += end - start


class CompileClock:
    """Totals of the compile events JAX itself times, for this process.

    JAX reports every traced function, every lowering and every pass
    through its compile step (a compilation or a read from the persistent
    cache) as a time span to the listeners registered with
    `jax.monitoring`, and every program it had to compile and write to
    that cache as a plain event. Spans nest: a traced function holds the
    functions traced inside it and the small programs compiled for the
    concrete values it computes on the way. So `compile_step_s` is the
    time covered by compile steps and `trace_lower_s` the time covered by
    tracing and lowering outside any compile step: the two never count a
    second twice and sum to at most the wall time. Listeners are
    process-wide and cannot be taken off again, so there is one clock a
    process (`compile_clock()`), registered when the first instrumented
    engine is built; its totals count from then, and from then on every
    JAX event in the process costs one set lookup (a lock only for the
    events counted here). Events arrive on whichever thread compiles.
    """

    COMPILE_STEP = "/jax/core/compile/backend_compile_duration"
    SPANS = frozenset((
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        COMPILE_STEP,
    ))
    CACHE_MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        self._lock = threading.Lock()
        self._compiling = _IntervalUnion()  # all three kinds of span
        self._compile_step = _IntervalUnion()
        self._cache_misses = 0
        jax.monitoring.register_event_time_span_listener(self._span)
        jax.monitoring.register_event_listener(self._event)

    def _span(self, event: str, start: float, end: float, **_) -> None:
        if event in self.SPANS:
            with self._lock:
                self._compiling.add(start, end)
                if event == self.COMPILE_STEP:
                    self._compile_step.add(start, end)

    def _event(self, event: str, **_) -> None:
        if event == self.CACHE_MISS:
            with self._lock:
                self._cache_misses += 1

    def totals(self) -> dict:
        with self._lock:
            step = self._compile_step.total
            return {
                "trace_lower_s": self._compiling.total - step,
                "compile_step_s": step,
                "cache_misses": self._cache_misses,
            }

    def since(self, before: dict) -> dict:
        """The totals' growth since `before` (an earlier `totals()`)."""
        return {
            key: round(value - before[key], 6)
            for key, value in self.totals().items()
        }


_compile_clock: Optional[CompileClock] = None
_compile_clock_lock = threading.Lock()


def compile_clock() -> CompileClock:
    global _compile_clock
    with _compile_clock_lock:
        if _compile_clock is None:
            _compile_clock = CompileClock()
        return _compile_clock


class FlightRecorder:
    """Bounded rings of what the engine loop actually did.

    Writers are the engine step path (serialized by LLMServer's lock or the
    caller's single thread); deque appends are atomic, so readers snapshot
    safely from any thread. Failures are recorded even with instrumentation
    off — a crashed step must always leave a trace."""

    def __init__(self, capacity: int = 256):
        self.steps: deque = deque(maxlen=capacity)
        self.compile_events: deque = deque(maxlen=128)
        self.failures: deque = deque(maxlen=128)
        # Overload plane: bounded-admission rejections and deadline
        # expiries. Recorded even with instrumentation off, like
        # failures — shed/expired traffic is precisely the traffic an
        # operator will be asked to explain after the fact.
        self.sheds: deque = deque(maxlen=128)
        self.expiries: deque = deque(maxlen=128)
        # Steps that held the step thread over STALL_SECONDS outside
        # `wait` (StepPhaseClock): few, and each one is asked after.
        self.stalls: deque = deque(maxlen=32)

    def record_step(self, record: dict) -> None:
        self.steps.append(record)

    def record_stall(self, record: dict) -> None:
        """One stalled step, as StepPhaseClock judged it: the stretch from
        the step before's return to this one's by phase, the seconds of
        its `prepare` the step thread ran, the decode batch, and whether a
        collection of the oldest generation ran in it. Logged too: a
        stall is rare, and whoever reads the log has no flight record."""
        self.stalls.append(record)
        logger.warning(
            "llm step %d held the step thread %.3f s outside wait "
            "(batch %d, full collection %s): %s, of prepare the thread "
            "ran %.3f s",
            record["step"], record["held_s"], record["batch_size"],
            record["full_collection"], record["phases"],
            record["prepare_cpu_s"],
        )

    def record_compile(
        self,
        program: str,
        bucket: int,
        seconds: float,
        split: Optional[dict] = None,
    ) -> None:
        """Warmup compile blame: which program/bucket cost how many
        seconds before the engine reported ready, and `split`, what
        CompileClock saw during the round: seconds tracing and lowering,
        seconds in the compile step (a compilation or a read from the
        cache) and programs the cache did not hold, and `loaded`: whether
        the round's programs were read from the program store and not
        traced (None where it made none). The rest of `compile_s` is the
        round's execution."""
        self.compile_events.append(
            {
                "program": program,
                "bucket": bucket,
                "compile_s": round(seconds, 6),
                **(split or {}),
                "time": time.time(),
            }
        )

    def record_failure(
        self,
        step: int,
        error: str,
        request_id: Optional[str] = None,
        action: str = "retry",
    ) -> None:
        """One failed engine step and what the loop did about it:
        "dead_letter" (poison isolation), "retry" (unattributable,
        below threshold), or "wedged" (threshold tripped)."""
        self.failures.append(
            {
                "step": step,
                "error": error,
                "request_id": request_id,
                "action": action,
                "time": time.time(),
            }
        )

    def record_shed(
        self,
        request_id: Optional[str],
        reason: str,
        queue_len: int,
        step: int,
    ) -> None:
        """One submission rejected by bounded admission (or dead on
        arrival): why, and how deep the backlog stood when it was shed."""
        self.sheds.append(
            {
                "request_id": request_id,
                "reason": reason,
                "queue_len": queue_len,
                "step": step,
                "time": time.time(),
            }
        )

    def record_expiry(
        self,
        request_id: str,
        phase: str,
        step: int,
        tokens_generated: int,
    ) -> None:
        """One admitted request dropped at its deadline: "queued" means it
        never cost a prefill program; "running" means it was aborted
        mid-stream with its blocks reclaimed this step."""
        self.expiries.append(
            {
                "request_id": request_id,
                "phase": phase,
                "step": step,
                "tokens_generated": tokens_generated,
                "time": time.time(),
            }
        )

    def snapshot(self, steps_limit: Optional[int] = None) -> dict:
        steps: List[dict] = list(self.steps)
        if steps_limit is not None and steps_limit >= 0:
            # NOT steps[-steps_limit:]: a 0 limit must mean zero records,
            # but [-0:] slices the whole list.
            steps = (
                steps[max(len(steps) - steps_limit, 0) :]
                if steps_limit
                else []
            )
        return {
            "steps": steps,
            "compile_events": list(self.compile_events),
            "failures": list(self.failures),
            "sheds": list(self.sheds),
            "expiries": list(self.expiries),
            "stalls": list(self.stalls),
        }
