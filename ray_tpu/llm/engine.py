"""LLM inference engine: continuous batching over the paged KV cache.

`LLMEngine` is the single-threaded core — one `step()` admits prefills,
feeds each in-flight prompt its next block-aligned chunk under the
per-step token budget (EngineConfig.max_prefill_tokens_per_step — long
prompts stream in over several steps instead of monopolizing one), runs
one iteration-level decode, streams tokens, and retires finished
sequences. `LLMServer` wraps it for actor use: a background step loop, a
blocking `generate`, and a `generate_stream` generator that pairs with
`.options(num_returns="streaming")` on the actor handle.

Observability (ray_tpu.util.metrics + util.tracing + llm.observability):
tokens/sec counters, decode batch occupancy, cache utilization, and queue
depth, plus — when EngineConfig.instrument is on — per-request lifecycle
spans (queue/prefill/decode/preempt, connected to the submitting task's
trace), TTFT / time-per-output-token / queue / e2e latency histograms, and
a flight-recorder ring of per-step records, all exported through the
standard Prometheus registry / tracing.traces() / flight_record().
"""

from __future__ import annotations

import asyncio
import contextvars
import functools
import hashlib
import threading
import time
import uuid
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Deque, Dict, List, Optional

import numpy as np

from ray_tpu._private.fault_injection import maybe_fail
from ray_tpu.exceptions import EngineOverloadedError, PoisonRequestError
from ray_tpu.llm.cache import (
    BlockAllocator,
    StateSlots,
    WindowBlocks,
    blocks_for_tokens,
    window_class_of,
)
from ray_tpu.llm import program_store
from ray_tpu.llm.config import EngineConfig
from ray_tpu.llm.model_runner import build_runner
from ray_tpu.llm.observability import (
    PER_TOKEN_SECONDS_BOUNDARIES,
    REQUEST_SECONDS_BOUNDARIES,
    STEP_SECONDS_BOUNDARIES,
    FlightRecorder,
    RequestTrace,
    StepPhaseClock,
    compile_clock,
)
from ray_tpu.llm.scheduler import (
    FINISH_EOS,
    FINISH_ERROR,
    FINISH_EXPIRED,
    FINISH_LENGTH,
    Request,
    Scheduler,
    Sequence,
)
from ray_tpu.llm.spec import build_proposer
from ray_tpu.models.gpt import GPTConfig
from ray_tpu.util import tracing
from ray_tpu.util.metrics import Counter, Gauge, Histogram, get_or_create


# Why a step at pipeline depth 1 did not chain (stats()
# "pipeline_flushes"): a member of the in-flight batch finished, was
# aborted, expired or was preempted; a prompt joined the decode batch; the
# look-ahead block could not be reserved without preempting; the record
# was already fetched or a second one is in flight (a retried step); or
# speculation is on, whose every decode commits in its own step.
FLUSH_CAUSES = ("left", "joined", "lookahead", "retry", "speculation")


class _InflightStep:
    """One dispatched-but-uncommitted decode step.

    Holds everything the commit needs: the batch exactly as it was
    dispatched (slot order matters — the chained token input is
    slot-aligned), the on-device `next_tokens` with its async host copy
    in flight, and the engine step index at dispatch time (failure
    attribution: a commit-time exception is pinned on the step that
    DISPATCHED the program, which at pipeline depth 1 is one step before
    it surfaces). `commit_idx` is the partial-commit resume pointer —
    after a poison dead-letter mid-commit, the retry resumes the loop
    exactly where it stopped.
    """

    __slots__ = (
        "seqs", "rids", "tokens_dev", "tokens_host",
        "dispatch_step", "commit_idx", "clock_seq", "t_prepare", "lanes",
        "serial",
    )

    def __init__(
        self, seqs, rids, tokens_dev, dispatch_step, clock_seq, t_prepare,
        serial,
    ):
        self.seqs: List[Sequence] = seqs
        self.rids: List[str] = rids
        self.tokens_dev = tokens_dev
        self.tokens_host: Optional[np.ndarray] = None
        self.dispatch_step = dispatch_step
        self.commit_idx = 0
        # The engine's number of this dispatch among its decodes and
        # chunks: records commit in this order (`_commit_dispatched`).
        self.serial = serial
        # StepPhaseClock's number of this dispatch (None uninstrumented):
        # its fetch tells the clock which program finished. And its
        # reading where the dispatch's `prepare` began.
        self.clock_seq = clock_seq
        self.t_prepare = t_prepare
        # The decode lane of each of `seqs` where it is not its index: a
        # model with recurrent layers decodes a sequence in the lane of
        # its state slot.
        self.lanes: Optional[List[int]] = None


class _PendingChunk:
    """One dispatched prefill chunk whose output the host has not read.

    What a chunk changes without its value (num_cached, num_chunks, the
    window class, block publication) changed when it was dispatched; this
    holds what waits for the value: the output array (a last chunk's first
    token, a routed model's counts), whom it belongs to, and what the
    spans and the TTFT observation need. At pipeline depth 0, under
    speculation and in a prefill-role engine the record is read in the
    statement after its dispatch; otherwise it is read behind the decode
    dispatch that took the token from the device (`_commit_chunks`).
    `dispatch_step` and `clock_seq` are an `_InflightStep`'s: a failed
    chunk program surfaces at the read and is pinned on the step that
    dispatched it."""

    __slots__ = (
        "seq", "rid", "take", "offset", "out", "dispatch_step", "clock_seq",
        "serial", "final", "index", "preemptions", "t0", "kind", "bucket",
        "fed",
    )

    def __init__(
        self, seq, rid, take, offset, out, dispatch_step, clock_seq, serial,
        final, t0, kind, bucket,
    ):
        self.seq: Sequence = seq
        self.rid: str = rid
        self.take = take
        self.offset = offset
        self.out = out
        self.dispatch_step = dispatch_step
        self.clock_seq = clock_seq
        self.serial = serial
        self.final = final
        self.index = seq.num_chunks  # of this admission, before the advance
        # A sequence preempted since the dispatch is not owed the value:
        # its resume re-prefills from an empty table.
        self.preemptions = seq.num_preemptions
        # Instrumented: the wall time its `prepare` began, and the span's
        # kind and bucket.
        self.t0 = t0
        self.kind = kind
        self.bucket = bucket
        # Whether a decode dispatch took the token from `out`, unread.
        self.fed = False


class LLMEngine:
    """Not thread-safe; callers serialize access (LLMServer holds a lock)."""

    def __init__(
        self,
        model_config: Optional[GPTConfig] = None,
        engine_config: Optional[EngineConfig] = None,
        params=None,
        seed: int = 0,
        draft_params=None,
    ):
        self.model_config = model_config or GPTConfig()
        self.engine_config = engine_config or EngineConfig()
        # Set-up's clock (rides `instrument`): JAX's compile events are
        # counted from here on; warm-up is LLMServer's, which writes
        # `_warmup_s`.
        self._compile_clock = (
            compile_clock() if self.engine_config.instrument else None
        )
        self._warmup_s = 0.0
        # What the program store had read and traced when this engine was
        # built: `stats()` says what it has since.
        self._program_store = program_store.default()
        self._programs_before = self._program_store.totals()
        if self.engine_config.draft_model_config is not None:
            # Fail fast with a message that names the DRAFT model before
            # any runner (and its device pools) is built: the draft mirror
            # pool shards on the same head axis as the target's, so both
            # head counts must divide the tp degree.
            from ray_tpu.ops.attention import validate_tp_heads

            validate_tp_heads(
                self.engine_config.draft_model_config.num_heads,
                self.engine_config.tensor_parallel_size,
                role="draft model",
            )
        # A model whose layers carry a recurrent state beside the paged
        # cache says so on its configuration. Nothing snapshots that state
        # at a block boundary, so a cached prefix cannot be resumed from:
        # the block cache is built without prefix sharing (no hashing, no
        # hits), every running sequence owns a state slot, and the
        # features that assume a cache-only model are refused here, by
        # what the model declares.
        self._recurrent = bool(
            getattr(self.model_config, "recurrent_state", False)
        )
        # A model with sliding-window layers declares a second cache class
        # with a horizon (`cache_classes`): its blocks are freed from below
        # as a sequence advances, so a cached prefix cannot be resumed from
        # either (a hit would need the window class to still hold the
        # horizon's tokens before the boundary), and the same features are
        # refused, in the same words.
        window_class = window_class_of(self.model_config)
        ecfg = self.engine_config
        refused = {
            "speculation": ecfg.speculation != "off",
            "kv_fabric": ecfg.kv_fabric is not None,
            'kv_cache_dtype="int8"': ecfg.kv_cache_dtype == "int8",
            "tensor_parallel_size > 1": ecfg.tensor_parallel_size > 1,
        }
        if window_class is not None and ecfg.enable_prefix_caching:
            # Asked for, not defaulted (None): refused like the rest. A
            # recurrent model keeps PR 32's answer, the cache built
            # without sharing whatever was asked.
            raise ValueError(
                "enable_prefix_caching=True is not supported for a model "
                f"with sliding-window layers ({type(self.model_config).__name__}): "
                "a prefix hit would need the window class to still hold the "
                f"{window_class.horizon} tokens before the boundary; leave "
                "it None (off for this model) or False"
            )
        keeps = {
            "recurrent layers": (
                self._recurrent,
                "need the recurrent state rolled back, stored or sharded "
                "beside the K/V it belongs to",
            ),
            "sliding-window layers": (
                window_class is not None,
                "need the window class's freed blocks rolled back, stored "
                "or sharded beside the full class's",
            ),
        }
        for layers, (has, need) in keeps.items():
            for what, asked in refused.items():
                if has and asked:
                    raise ValueError(
                        f"{what} is not supported for a model with "
                        f"{layers} ({type(self.model_config).__name__}): a "
                        "rejected draft, a spilled block or a sharded pool "
                        f"would {need}"
                    )
        self.runner = build_runner(
            self.model_config, self.engine_config, params=params, seed=seed
        )
        # Speculative decoding (ray_tpu.llm.spec): None when off. The
        # proposer only produces guesses; _run_verify scores them against
        # this engine's own model, so outputs never depend on it.
        self._spec = build_proposer(
            self.engine_config, seed=seed, draft_params=draft_params
        )
        self.allocator = BlockAllocator(
            self.engine_config.num_blocks,
            self.engine_config.block_size,
            enable_prefix_caching=(
                self.engine_config.enable_prefix_caching is not False
                and not self._recurrent
                and window_class is None
            ),
            eviction_policy=self.engine_config.prefix_eviction_policy,
        )
        self.scheduler = Scheduler(
            self.allocator,
            self.engine_config.max_decode_slots,
            self.engine_config.max_blocks_per_seq,
            state_slots=(
                StateSlots(self.runner.state_slots)
                if self._recurrent
                else None
            ),
            window=(
                WindowBlocks(
                    ecfg.window_class_blocks(window_class.horizon),
                    ecfg.block_size,
                    window_class.horizon,
                )
                if window_class is not None
                else None
            ),
        )
        self._window = self.scheduler.window
        # A runner of a model with routed experts returns a decode step's
        # routing counts behind its tokens (`count_routing`), and carries
        # counters and shapes of its own for `stats()`.
        self._counts_routing = hasattr(self.runner, "count_routing")
        # KV fabric (EngineConfig.kv_fabric): shared host-DRAM spill tier.
        # None keeps every hook cold — the allocator, scheduler, and step
        # loop behave bit-for-bit as before the fabric existed.
        self._fabric = None
        fcfg = self.engine_config.kv_fabric
        if fcfg is not None:
            block_bytes = self.runner.kv_block_bytes()
            if fcfg.byte_budget < block_bytes:
                raise ValueError(
                    f"kv_fabric.byte_budget ({fcfg.byte_budget} bytes) is "
                    f"smaller than one KV block ({block_bytes} bytes for "
                    "this model/engine config) — a fabric that cannot hold "
                    "a single block can never serve a hit; raise the "
                    "budget or drop the kv_fabric knob"
                )
            # Imported lazily: the kvfabric package's disagg module imports
            # this module, so a top-level import would cycle.
            from ray_tpu.llm.kvfabric.store import KVFabricClient

            self._fabric = KVFabricClient(
                fcfg.name,
                fcfg.byte_budget,
                rpc_timeout_s=fcfg.rpc_timeout_s,
                # A store RPC that exceeds its bound degrades to a miss AND
                # is counted distinctly (llm_engine_fabric_timeouts): a
                # hung store actor must never stall admission or eviction,
                # and an operator must be able to tell "store is slow"
                # from "store is cold". Bound method on a not-yet-finished
                # self is safe — the callback only fires on later RPCs.
                on_timeout=self._note_fabric_timeout,
            )
            # Spill on device eviction: demote a keyed block's content to
            # the host tier just before the allocator discards it.
            self.allocator.on_evict = self._spill_block
            # Admission extends the prefix match past the device cache.
            self.scheduler.fabric_probe = self._fabric.contains
        # A prefill-role engine's whole output is the KV blocks it
        # publishes: push every newly filled block eagerly, so the reply
        # to the caller is the barrier the decode-role admission needs.
        self._publish_on_fill = (
            self._fabric is not None
            and self.engine_config.engine_role == "prefill"
        )
        self._on_token: Dict[str, Callable[[int], None]] = {}
        self._on_finish: Dict[str, Callable[[Sequence], None]] = {}
        # Called where a decode's emission ends (`_commit_head`), before
        # the step goes on to its admissions and its chunks, whose
        # synchronous fetch the committed tokens need not wait out.
        self.on_commit: Optional[Callable[[], None]] = None
        # Tokens emitted since `llm_engine_generated_tokens` was last
        # written (once a step, with the rest of the metric family; a step
        # that raises leaves its count to the next one that returns).
        self._step_tokens_emitted = 0

        # Engines share one registered metric per name (several engines can
        # coexist in-process, one per Serve app); each engine is its own
        # series via the `engine` tag.
        self._metric_tags = {"engine": uuid.uuid4().hex[:8]}
        self._tokens_generated = get_or_create(
            Counter,
            "llm_engine_generated_tokens",
            "Tokens generated (prefill+decode)",
            tag_keys=("engine",),
        )
        self._preemptions = get_or_create(
            Counter,
            "llm_engine_preemptions",
            "Sequences preempted on cache pressure",
            tag_keys=("engine",),
        )
        self._occupancy = get_or_create(
            Gauge,
            "llm_engine_batch_occupancy",
            "Active decode slots / max_decode_slots, last step",
            tag_keys=("engine",),
        )
        self._cache_util = get_or_create(
            Gauge,
            "llm_engine_cache_utilization",
            "Allocated KV blocks / usable",
            tag_keys=("engine",),
        )
        self._queue_depth = get_or_create(
            Gauge,
            "llm_engine_queue_depth",
            "Requests waiting for a decode slot",
            tag_keys=("engine",),
        )
        self._prefix_hits = get_or_create(
            Counter,
            "llm_engine_prefix_cache_hit_tokens",
            "Prompt tokens served from the prefix cache instead of computed",
            tag_keys=("engine",),
        )
        self._prefix_hit_rate = get_or_create(
            Gauge,
            "llm_engine_prefix_cache_hit_rate",
            "Cumulative prefix-cache hit tokens / prefill tokens",
            tag_keys=("engine",),
        )
        self._evictable_blocks = get_or_create(
            Gauge,
            "llm_engine_evictable_blocks",
            "Cached-but-unreferenced KV blocks (reusable until evicted)",
            tag_keys=("engine",),
        )
        self._dead_letter_count = get_or_create(
            Counter,
            "llm_engine_dead_letter_requests",
            "Requests failed in isolation after poisoning an engine step",
            tag_keys=("engine",),
        )
        self._shed_count = get_or_create(
            Counter,
            "llm_engine_shed_requests",
            "Submissions rejected fast by bounded admission "
            "(max_queue_len / max_queue_tokens) or dead-on-arrival "
            "deadlines — typed overload sheds, not failures",
            tag_keys=("engine",),
        )
        self._expired_count = get_or_create(
            Counter,
            "llm_engine_expired_requests",
            "Admitted requests dropped at their end-to-end deadline "
            "(queued: before any prefill ran; decoding: aborted "
            "mid-stream with blocks reclaimed)",
            tag_keys=("engine",),
        )
        self._prefill_backlog = get_or_create(
            Gauge,
            "llm_engine_prefill_backlog_tokens",
            "Prompt tokens admitted or queued but not yet fed through a "
            "prefill program (chunked prefill drains this at "
            "max_prefill_tokens_per_step per engine step)",
            tag_keys=("engine",),
        )
        self._spec_proposed = get_or_create(
            Counter,
            "llm_engine_spec_proposed_tokens",
            "Speculative tokens scored by the verify program",
            tag_keys=("engine",),
        )
        self._spec_accepted = get_or_create(
            Counter,
            "llm_engine_spec_accepted_tokens",
            "Speculative tokens that matched the target argmax and were "
            "committed (excludes the always-emitted correction/bonus token)",
            tag_keys=("engine",),
        )
        self._spec_acceptance = get_or_create(
            Gauge,
            "llm_engine_spec_acceptance_rate",
            "Cumulative accepted / proposed speculative tokens",
            tag_keys=("engine",),
        )
        self._fabric_spills = get_or_create(
            Counter,
            "llm_engine_fabric_spill_blocks",
            "KV blocks demoted to the fabric host tier (eviction spill, "
            "prefill-role publication, drain flush)",
            tag_keys=("engine",),
        )
        self._fabric_restores = get_or_create(
            Counter,
            "llm_engine_fabric_restore_blocks",
            "KV blocks restored from the fabric into device slots",
            tag_keys=("engine",),
        )
        self._fabric_hits = get_or_create(
            Counter,
            "llm_engine_fabric_hit_blocks",
            "Admission-probe hits: blocks found in the fabric past the "
            "device prefix match",
            tag_keys=("engine",),
        )
        self._fabric_hit_rate = get_or_create(
            Gauge,
            "llm_engine_fabric_hit_rate",
            "Cumulative fabric-restored tokens / prefill tokens (the "
            "fabric's own share of the prefix-cache hit rate)",
            tag_keys=("engine",),
        )
        self._fabric_bytes_used = get_or_create(
            Gauge,
            "llm_engine_fabric_bytes_used",
            "Fabric store occupancy in bytes (the store is shared across "
            "engines on the fabric; refreshed on stats scrape)",
            tag_keys=("engine",),
        )
        self._fabric_timeouts = get_or_create(
            Counter,
            "llm_engine_fabric_timeouts",
            "Fabric store RPCs that exceeded kv_fabric.rpc_timeout_s and "
            "degraded to a miss/no-op (a hung store never stalls "
            "admission or eviction)",
            tag_keys=("engine",),
        )
        # Request-level latency histograms (the serving SLO trio + queue):
        # observed only at lifecycle boundaries, never per token.
        self._h_ttft = get_or_create(
            Histogram,
            "llm_request_ttft_seconds",
            "Submission to first generated token",
            boundaries=REQUEST_SECONDS_BOUNDARIES,
            tag_keys=("engine",),
        )
        self._h_tpot = get_or_create(
            Histogram,
            "llm_request_time_per_output_token_seconds",
            "Mean inter-token latency after the first token, per request",
            boundaries=PER_TOKEN_SECONDS_BOUNDARIES,
            tag_keys=("engine",),
        )
        self._h_queue = get_or_create(
            Histogram,
            "llm_request_queue_time_seconds",
            "Waiting-for-a-decode-slot time (one sample per admission, "
            "including preempt-resume re-admissions)",
            boundaries=REQUEST_SECONDS_BOUNDARIES,
            tag_keys=("engine",),
        )
        self._h_e2e = get_or_create(
            Histogram,
            "llm_request_e2e_seconds",
            "Submission to terminal state",
            boundaries=REQUEST_SECONDS_BOUNDARIES,
            tag_keys=("engine",),
        )
        self._h_step = get_or_create(
            Histogram,
            "llm_engine_step_seconds",
            "One engine phase dispatch (prefill per chunk per sequence, "
            "decode or speculative verify per batched step); chunk=cont "
            "marks a mid-prompt prefill chunk, chunk=final the dispatch "
            "that completes a prompt (n/a for decode/verify)",
            boundaries=STEP_SECONDS_BOUNDARIES,
            tag_keys=("engine", "phase", "attn_impl", "chunk"),
        )
        # Which paged-attention implementation the runner resolved (pallas
        # fused kernel vs XLA reference): tagged onto the step histograms
        # and per-step flight records so the observability plane can
        # attribute a speedup (or regression) to the kernel in production.
        self._attn_impl = self.runner.attn_impl
        # How many chips this replica's mesh spans: stamped on stats() and
        # every flight-recorder step record so a fleet operator can tell a
        # tp=4 replica's step times from a single-chip one at a glance.
        self._tp = self.runner.tensor_parallel_size
        # Pre-merged tag dicts so the step loop never builds dicts. Full
        # prefill runs model.apply with no paged caches — the knob cannot
        # affect it — so its series is tagged "n/a" rather than letting
        # unrelated latency differences read as kernel effects; only the
        # partial-prefill and decode programs dispatch on attn_impl. The
        # chunk tag splits prefill dispatches into mid-prompt chunks
        # ("cont") vs the dispatch that completes a prompt ("final", which
        # is also every unchunked prefill); decode/verify never chunk.
        self._step_tags = {
            phase: {
                **self._metric_tags,
                "phase": phase,
                "attn_impl": (
                    "n/a" if phase == "prefill" else self._attn_impl
                ),
                "chunk": (
                    "n/a" if phase in ("decode", "verify") else "final"
                ),
            }
            for phase in ("prefill", "partial_prefill", "decode", "verify")
        }
        self._chunk_step_tags = {
            phase: {**self._step_tags[phase], "chunk": "cont"}
            for phase in ("prefill", "partial_prefill")
        }
        # Resolved once: None = chunking off (whole prompts in one
        # dispatch), else the per-step prompt-token budget.
        self._prefill_budget = self.engine_config.prefill_token_budget
        # Observability plane (EngineConfig.instrument): per-request phase
        # spans + the per-step flight-recorder ring. The recorder object
        # always exists (step FAILURES are recorded regardless), but
        # per-step records and spans are compiled out when instrument=False.
        self._instrument = self.engine_config.instrument
        self.flight_recorder = FlightRecorder(
            self.engine_config.flight_recorder_capacity
        )
        self._req_traces: Dict[str, RequestTrace] = {}
        if self._instrument or self._spec is not None:
            # Preemption must also drop a stateful proposer's per-request
            # resources (draft KV blocks) — the resume re-prefills both
            # caches — so the hook installs whenever either plane needs it.
            self.scheduler.on_preempt = self._note_preempt
        # Poison-request isolation: records of requests failed in isolation
        # after an attributable step exception, newest last.
        self._dead_letters: deque = deque(
            maxlen=self.engine_config.dead_letter_capacity
        )
        # Overload control plane. The shed ring mirrors the dead-letter
        # ring for bounded-admission rejections (shed_requests());
        # _deadline_count gates the per-step expiry sweep so an engine
        # that has never seen a deadline pays one int compare per step —
        # the default path stays bit-for-bit.
        self._sheds: deque = deque(maxlen=self.engine_config.shed_capacity)
        self._shed_total = 0
        self._expired_total = 0
        self._fabric_timeout_total = 0
        self._deadline_count = 0
        # Request whose per-sequence section of step() is currently running;
        # a step exception raised there is attributed to it.
        self._current_rid: Optional[str] = None
        self._steps = 0
        self._decode_tokens = 0
        self._decode_slot_steps = 0
        self._prefill_tokens = 0
        self._prefill_chunk_dispatches = 0  # prefill program dispatches
        self._chunked_prefill_requests = 0  # prompts that took > 1 chunk
        self._cache_hit_tokens = 0
        self._fabric_spilled_total = 0
        self._fabric_restored_total = 0
        self._fabric_hit_total = 0
        self._fabric_restored_tokens = 0
        self._spec_proposed_total = 0
        self._spec_accepted_total = 0
        self._spec_emitted_total = 0
        self._verify_steps = 0
        # The step loop's pipeline depth: how many decode records a step
        # may leave in flight when it returns. 0 commits every dispatch
        # in the step that made it; 1 (EngineConfig.async_scheduling)
        # commits it in the next. `_inflight` holds the records, oldest
        # first; it is transiently one longer than the depth between a
        # dispatch and the commit of the record before it.
        self._pipeline_depth = 1 if self.engine_config.async_scheduling else 0
        self._inflight: Deque[_InflightStep] = deque()
        # Chunk programs dispatched and not read, oldest first, and of
        # those the last chunks whose token no decode dispatch has taken
        # yet, by sequence. At depth 1 a chunk's output stays on the
        # device until the decode dispatch its prompt joins has been made
        # (step()'s docstring). What depends on the value keeps the read
        # where it was: depth 0, speculation (the proposer reads committed
        # history) and a prefill-role engine (publication reads the device
        # anyway) read every chunk at once (`_defers_chunks`), and both
        # stay empty.
        self._pending_chunks: Deque[_PendingChunk] = deque()
        self._unfed: Dict[Sequence, _PendingChunk] = {}
        # Decode and chunk dispatches made, in one count: the order in
        # which their records commit.
        self._dispatch_serial = 0
        # Last chunks committed, and those of them whose token had
        # reached its first decode dispatch without having been read.
        self._prompts_prefilled = 0
        self._first_tokens_on_device = 0
        # First tokens committed in the current step, for its flight record.
        self._step_first_tokens = 0
        # Dispatch index of the record being committed right now: a
        # commit-time failure is attributed against the step that
        # dispatched the failing program (failure_step()).
        self._attribution_step: Optional[int] = None
        # The step loop's one clock (instrument-gated like the records it
        # feeds): it partitions the wall time into
        # schedule / prepare / wait / commit / other / between, mirrors the
        # phases as profiler annotations and samples host_exposed at every
        # program dispatch. The runner calls the hook where a program's
        # dispatch call has returned and nothing has been fetched yet:
        # the boundary between prepare and wait.
        self._clock = StepPhaseClock(
            on_stall=self.flight_recorder.record_stall
        )
        self.runner.on_dispatched = self._clock.dispatched
        # The commits of the current step, for its flight record.
        self._step_commits: List[dict] = []
        # What the decode dispatches asked the paged kernel to read: a
        # reader divides the kernel's device time by these.
        self._decode_dispatches = 0
        self._decode_context_tokens = 0
        # A model with sliding-window layers: what the decode dispatches
        # asked of the window class (each lane's context up to the
        # horizon), and the tokens each class held for the running
        # sequences, summed over steps.
        self._decode_window_tokens = 0
        self._held_tokens_full = 0
        self._held_tokens_window = 0
        # How often depth 1 engages, and why it does not: decode
        # dispatches made from an in-flight record's device tokens, and
        # the steps that could not chain, counted where step() and
        # _try_chain decide (FLUSH_CAUSES). Both stay 0 at depth 0.
        self._chained_dispatches = 0
        self._pipeline_flushes = dict.fromkeys(FLUSH_CAUSES, 0)
        # Preallocated per-step decode/verify input buffers, zero-filled
        # and repopulated each dispatch instead of np.zeros-allocated
        # (the engine allocates none in the steady decode loop — asserted
        # by test). Safe to reuse: runner.verify blocks on the program
        # before the next fill, and runner.decode makes one small host
        # copy of each input at dispatch.
        slots = self.engine_config.max_decode_slots
        nb = self.engine_config.max_blocks_per_seq
        self._dec_tokens = np.zeros((slots,), np.int32)
        self._dec_positions = np.zeros((slots,), np.int32)
        self._dec_block_tables = np.zeros((slots, nb), np.int32)
        self._dec_window_tables = (
            np.zeros((slots, nb), np.int32)
            if self._window is not None
            else None
        )
        self._dec_context_lens = np.zeros((slots,), np.int32)
        self._verify_inputs = (
            {
                s: (
                    np.zeros((slots, s), np.int32),
                    np.zeros((slots, nb), np.int32),
                    np.zeros((slots,), np.int32),
                    np.zeros((slots,), np.int32),
                )
                for s in self.engine_config.verify_buckets()
            }
            if self._spec is not None
            else {}
        )
        # A stepping engine exports its whole metric family: counters and
        # histograms that happen not to fire after a registry reset (test
        # isolation) must still re-register, or their series vanish from
        # the exposition. step() walks this once: one int compare each,
        # nothing on the token path.
        self._metric_family = (
            self._preemptions, self._prefix_hits, self._tokens_generated,
            self._dead_letter_count, self._shed_count, self._expired_count,
            self._h_ttft, self._h_tpot,
            self._h_queue, self._h_e2e, self._h_step,
        )
        if self._spec is not None:
            self._metric_family += (
                self._spec_proposed, self._spec_accepted,
                self._spec_acceptance,
            )
        if self._fabric is not None:
            self._metric_family += (
                self._fabric_spills, self._fabric_restores,
                self._fabric_hits, self._fabric_hit_rate,
                self._fabric_bytes_used, self._fabric_timeouts,
            )
        self._start = time.monotonic()

    # ---------------- request lifecycle ----------------

    def add_request(
        self,
        prompt_ids: List[int],
        max_new_tokens: Optional[int] = None,
        eos_id: Optional[int] = None,
        request_id: Optional[str] = None,
        on_token: Optional[Callable[[int], None]] = None,
        on_finish: Optional[Callable[[Sequence], None]] = None,
        deadline_s: Optional[float] = None,
    ) -> str:
        ecfg = self.engine_config
        if max_new_tokens is None:
            max_new_tokens = ecfg.default_max_new_tokens
        if ecfg.engine_role == "prefill":
            # A prefill-role engine never decodes: the request finishes at
            # its first sampled token, after every full prompt block has
            # been published to the fabric for the decode-role engine.
            max_new_tokens = 1
        prompt_ids = [int(t) for t in prompt_ids]
        if not prompt_ids:
            raise ValueError("prompt_ids must be non-empty")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        total = len(prompt_ids) + max_new_tokens
        if total > ecfg.max_model_len:
            raise ValueError(
                f"prompt ({len(prompt_ids)}) + max_new_tokens "
                f"({max_new_tokens}) = {total} exceeds max_model_len "
                f"{ecfg.max_model_len}"
            )
        # A preempted sequence re-prefills prompt+generated (up to total-1
        # tokens), so the whole lifetime must fit the bucket table and the
        # block pool — otherwise the request could never be (re)admitted and
        # the engine would spin without progress.
        # Under a chunk budget no dispatch feeds more than the budget.
        largest_bucket = ecfg.buckets()[-1]
        largest_chunk = total - 1
        if self._prefill_budget is not None:
            largest_chunk = min(largest_chunk, self._prefill_budget)
        if largest_chunk > largest_bucket:
            raise ValueError(
                f"prompt + max_new_tokens - 1 = {total - 1} exceeds the "
                f"largest prefill bucket {largest_bucket}; raise "
                "prefill_buckets or shorten the request"
            )
        need_blocks = blocks_for_tokens(total, ecfg.block_size)
        if need_blocks > self.allocator.num_usable:
            raise ValueError(
                f"request needs {need_blocks} cache blocks but the pool "
                f"only has {self.allocator.num_usable}; raise num_blocks"
            )
        request_id = request_id or uuid.uuid4().hex
        if self.scheduler.is_active(request_id):
            raise ValueError(f"request_id {request_id!r} is already active")
        if deadline_s is not None:
            # Dead-on-arrival: the deadline (monotonic, set at the client
            # boundary) passed in transit. Admitting it would spend a
            # prefill program on tokens no caller can use.
            now = time.monotonic()
            if now >= deadline_s:
                self._record_shed(request_id, "expired_at_submit", 0.0)
                raise TimeoutError(
                    f"request {request_id} arrived "
                    f"{now - deadline_s:.3f}s past its deadline"
                )
        cap_len = ecfg.max_queue_len
        cap_tok = ecfg.max_queue_tokens
        if cap_len is not None or cap_tok is not None:
            qlen = len(self.scheduler.waiting)
            reason = None
            if cap_len is not None and qlen >= cap_len:
                reason = f"queue_len {qlen} >= max_queue_len {cap_len}"
            elif cap_tok is not None:
                qtok = self.scheduler.prefill_backlog_tokens()
                if qtok + len(prompt_ids) > cap_tok:
                    reason = (
                        f"queued tokens {qtok} + prompt {len(prompt_ids)} "
                        f"> max_queue_tokens {cap_tok}"
                    )
            if reason is not None:
                # Rough drain hint, never a guarantee: one admission wave
                # (~max_prefills_per_step worth of steps) per queued
                # request ahead of the caller, capped so callers never
                # sleep longer than the router's own backoff ceiling.
                retry_after = min(
                    2.0, 0.05 * (1.0 + qlen / ecfg.max_decode_slots)
                )
                self._record_shed(request_id, reason, retry_after)
                raise EngineOverloadedError(
                    engine=self._metric_tags["engine"],
                    reason=reason,
                    queue_len=qlen,
                    retry_after_s=retry_after,
                )
        req = Request(
            request_id=request_id,
            prompt_ids=prompt_ids,
            max_new_tokens=max_new_tokens,
            eos_id=eos_id,
            deadline_s=deadline_s,
        )
        if on_token is not None:
            self._on_token[request_id] = on_token
        if on_finish is not None:
            self._on_finish[request_id] = on_finish
        self.scheduler.add(Sequence(req))
        if deadline_s is not None:
            self._deadline_count += 1
        if self._instrument:
            # Submission runs on the caller's thread (an actor-task context
            # when reached through LLMServer), so the ambient trace context
            # chains this request's lifecycle spans under the Serve
            # handle → replica → engine-actor task spans. The engine loop
            # thread later emits against the captured context explicitly.
            self._req_traces[request_id] = RequestTrace(
                request_id, tracing.capture_context()
            )
        return request_id

    def abort(self, request_id: str) -> bool:
        seq = self.scheduler.abort(request_id)
        if seq is not None:
            self._finished(seq)
            return True
        return False

    def has_work(self) -> bool:
        # An in-flight async record is work even when the scheduler is
        # empty (every member aborted mid-flight): one more step drains
        # it, so callers' step loops never strand a dispatched program.
        return (
            self.scheduler.has_work()
            or bool(self._inflight)
            or bool(self._pending_chunks)
        )

    # ---------------- poison-request isolation ----------------

    def culprit_for(self, exc: BaseException) -> Optional[str]:
        """Which active request a step exception is attributable to: the
        exception's own request_id (PoisonRequestError and injected faults
        carry one) or the request whose per-sequence section of step() was
        running. None when the failure can't be pinned on one request."""
        rid = getattr(exc, "request_id", None) or self._current_rid
        if rid and self.scheduler.is_active(rid):
            return rid
        return None

    def failure_step(self) -> int:
        """Step index a failure surfacing NOW should be attributed to.
        At pipeline depth 1 a decode program's commit runs one step
        after its dispatch, so an exception raised inside the commit loop
        belongs to the in-flight record's DISPATCH index (where the
        failing program and its batch actually ran) — not the current
        step counter. Outside a commit, and at depth 0, this is simply
        the current step."""
        if self._attribution_step is not None:
            return self._attribution_step
        return self._steps

    def fail_request(self, request_id: str, exc: BaseException) -> bool:
        """Fail one request in isolation: release its KV blocks, record a
        dead letter, and fire its finish callback (finish_reason="error").
        Returns False when the request is not active."""
        seq = self.scheduler.abort(request_id)
        if seq is None:
            return False
        seq.finish_reason = FINISH_ERROR
        prompt = seq.request.prompt_ids
        self._dead_letters.append(
            {
                "request_id": request_id,
                "prompt_hash": hashlib.sha1(
                    ",".join(map(str, prompt)).encode()
                ).hexdigest()[:16],
                "prompt_len": len(prompt),
                "tokens_generated": len(seq.generated),
                "error": repr(exc),
                "step": self.failure_step(),
                "time": time.time(),
            }
        )
        self._dead_letter_count.inc(tags=self._metric_tags)
        rt = self._req_traces.get(request_id)
        if rt is not None:
            # The request span closes with error status + the step
            # exception that killed it (dead-letter attribution).
            rt.error = repr(exc)
        self._finished(seq)
        return True

    def dead_letters(self) -> List[dict]:
        """Records of requests failed in isolation, oldest first (bounded
        by EngineConfig.dead_letter_capacity)."""
        return list(self._dead_letters)

    # ---------------- overload control ----------------

    def _record_shed(
        self, request_id: Optional[str], reason: str, retry_after_s: float
    ) -> None:
        """One rejected submission: ring entry (shed_requests()), counter,
        and a flight-recorder shed record — every rejection leaves the
        same three traces a dead letter does, so overload is auditable
        after the fact, not just observable live."""
        qlen = len(self.scheduler.waiting)
        self._sheds.append(
            {
                "request_id": request_id,
                "reason": reason,
                "queue_len": qlen,
                "retry_after_s": retry_after_s,
                "step": self._steps,
                "time": time.time(),
            }
        )
        self._shed_total += 1
        self._shed_count.inc(tags=self._metric_tags)
        self.flight_recorder.record_shed(
            request_id, reason, qlen, self._steps
        )

    def shed_requests(self) -> List[dict]:
        """Records of submissions rejected by bounded admission (or dead
        on arrival), oldest first (bounded by EngineConfig.shed_capacity)
        — the dead_letters() analogue for the overload plane."""
        return list(self._sheds)

    def _note_fabric_timeout(self) -> None:
        """KVFabricClient on_timeout hook: one store RPC exceeded its
        bound and degraded to a miss/no-op."""
        self._fabric_timeout_total += 1
        self._fabric_timeouts.inc(tags=self._metric_tags)

    def _expire_deadlines(self) -> None:
        """Per-step deadline enforcement (monotonic clock, matching
        Request.deadline_s — never wall time, which steps under NTP).
        Runs at the top of step(), so a queued request whose deadline
        passed is dropped BEFORE schedule_prefills can feed it to a
        prefill program, and a decoding one goes through the normal
        finish teardown — KV blocks, draft-mirror blocks, and any
        lookahead reservation reclaimed within this step. The sweep
        precedes the chain attempt: an expiry is a batch-composition
        change, so the pipeline flushes and _commit_head's inactive-skip
        drops the in-flight orphan token.
        Engines that have never seen a deadline pay one int compare."""
        if not self._deadline_count:
            return
        now = time.monotonic()
        for seq in self.scheduler.expire_waiting(now):
            self._record_expiry(seq, "queued")
            self._finished(seq)
        for seq in self.scheduler.expired_running(now):
            self.scheduler.finish(seq, FINISH_EXPIRED)
            self._record_expiry(seq, "running")
            self._finished(seq)

    def _record_expiry(self, seq: Sequence, phase: str) -> None:
        self._expired_total += 1
        self._expired_count.inc(tags=self._metric_tags)
        rt = self._req_traces.get(seq.request.request_id)
        if rt is not None:
            # The request span closes with error status: an expiry is a
            # terminal deadline miss, not a clean finish.
            rt.error = "deadline expired"
        self.flight_recorder.record_expiry(
            seq.request.request_id, phase, self._steps, len(seq.generated)
        )

    def close_traces(self, exc: BaseException) -> None:
        """Close every in-flight request's trace with error status. The
        wedge and shutdown broadcasts end requests WITHOUT _finished()
        running, which would otherwise strand their emitted phase spans
        under a root span that never gets written — exactly during the
        incident the trace exists to explain."""
        now = time.time()
        error = repr(exc)
        for rid, rt in list(self._req_traces.items()):
            rt.error = error
            seq = self.scheduler._active.get(rid)
            if seq is not None:
                rt.on_finish(now, seq)
        self._req_traces.clear()

    # ---------------- stepping ----------------

    def step(self) -> dict:
        """One engine iteration: commit what is in flight, admit prefills,
        feed each in-flight prompt its next chunk under the per-step token
        budget, decode every decode-ready sequence one token, emit tokens,
        retire finished sequences. A sequence mid-chunk stays `prefilling`
        — it never enters the decode batch, so a chunk failure (or a step
        retry) simply re-plans from its committed num_cached; no requeue
        is needed to keep the running set consistent.

        A decode is a dispatch (`_dispatch_decode`) and a commit
        (`_commit_head`); the pipeline depth says which step commits. At
        depth 0 the commit follows its dispatch at once and the step
        returns with nothing in flight. At depth 1
        (EngineConfig.async_scheduling) the record stays in flight and
        steady state CHAINS: the in-flight decode's on-device
        `next_tokens` feed the next dispatch directly (positions and
        context_lens advance +1 — deterministic, value-free), THEN the
        in-flight step's values are fetched and committed one step
        behind, so the device is already running step N+1 while the host
        emits step N's tokens and plans admissions. Everything
        value-dependent is a pipeline-flush boundary (commit everything,
        then schedule normally): speculation (the proposer reads
        committed token history), any batch-composition change (finish /
        abort / preemption / a prompt joining — the chained token input
        is slot-aligned), block pressure the lookahead cannot cover
        without preempting (preemption must never run under an in-flight
        write), and a partially committed record left by a poison retry
        (the one way depth 0 enters a step with a record in flight).

        At depth 1 finishes are detected one step late, at commit: a
        chained dispatch may decode one token PAST a sequence's
        EOS/length stop. That overshoot token lands in the null block or
        a lookahead block freed with the sequence, is skipped at its
        record's commit, and never reaches a client. Greedy outputs are
        token-identical at both depths across every feature knob.

        At depth 1 a prompt's chunk is a dispatch and a commit too
        (`_PendingChunk`, `_commit_chunks`). What a chunk changes without
        its value advances where it is dispatched, as a chained decode's
        positions do: num_cached, num_chunks, the window class's blocks,
        block publication. What needs the value waits for the read: the
        first token's append and emission, the finish check, the TTFT
        observation and the request's spans (TTFT is observed when the
        token is on the host, not at the dispatch). A last chunk's token
        reaches the decode that the prompt joins ON THE DEVICE: the
        dispatch sets that lane of its token input from the chunk's
        output (`runner.join_token`, outside the decode program), from
        committed state in the step that ran the chunk where that step
        dispatches its own decode, chained at the top of the next step
        where the chunk followed a chained decode: a join chains (the
        joiner keeps the lane it will have, the others keep theirs) and
        counts no flush; where that next step flushes instead, its own
        decode dispatch takes the token, from committed state. The step
        thread reads a chunk's output only after the decode dispatch that
        consumes it: at the end of the step that dispatched that decode
        from committed state, first thing after a chained dispatch, in
        either case behind the commit of the decode that ran before the
        chunk and in front of the decode that fed on it; a chunk that is
        not a prompt's last is read there as well, for its counters and
        its failure. The overshoot rule covers the first token: a prompt
        that its first token ends (max_new_tokens 1, EOS) has been fed to
        one decode, which lands in the null block or a block freed with
        the sequence and is skipped at its record's commit. A failed
        chunk program surfaces at its read, pinned on the chunk's request
        and dispatch step. Depth 0, speculation and a prefill-role engine
        read every chunk in the statement after its dispatch.

        Instrumented, the step runs on the phase clock: entry opens
        `schedule`, the return opens `between` (or stops the clock when
        nothing is live); a step that raises leaves its phase open and
        the next step's entry closes it. Every dispatch is called from
        this frame or one helper below it: JAX walks the Python stack at
        every traced operation, so each frame between warm-up and a
        program costs set-up seconds (PR 24: one frame more read +14 s on
        a 146 s set-up under Serve)."""
        ecfg = self.engine_config
        preempted_before = self.scheduler.num_preemptions
        self._current_rid = None
        self._attribution_step = None
        maybe_fail("llm.step")
        instrument = self._instrument
        clock = self._clock if instrument else None
        if clock is not None:
            clock.enter_step(self._steps)
        # Wall clock for record identity ("time" field); the duration and
        # its phases are the clock's perf_counter readings — wall time
        # steps under NTP and would corrupt duration_s exactly when an
        # operator is staring at the recorder.
        t_step = time.time() if instrument else 0.0
        bytes_before = self._host_transfer_bytes() if instrument else 0
        self._step_commits = []
        self._step_first_tokens = 0

        # Deadline sweep BEFORE admission and before the chain attempt: a
        # queued request whose deadline passed must never reach
        # schedule_prefills (resource-true expiry), and an expiry changes
        # the batch composition, so _try_chain refuses and the pipeline
        # flushes — the expired sequence's in-flight token is dropped by
        # _commit_head's inactive-skip, never emitted.
        self._expire_deadlines()
        # Chained dispatch FIRST — before any commit, admission, or
        # metric work: the whole point is that the device gets its next
        # program while the host still owes this step's bookkeeping. A
        # record whose tokens were fetched (a poison retry, mid-commit)
        # or a second in-flight record never chains; both flush below.
        chained_seqs: Optional[List[Sequence]] = None
        if self._pipeline_depth and self._spec is None and self._inflight:
            chained_seqs = self._try_chain()
        # Chained: commit the record the chain fed from and the chunks
        # dispatched behind it, whose tokens the chained dispatch has just
        # taken from the device; the chained record stays in flight for
        # the next iteration. Else a flush boundary: commit everything in
        # dispatch order, then schedule normally from fully committed state.
        self._commit_dispatched(keep=0 if chained_seqs is None else 1)

        admitted = self.scheduler.schedule_prefills(
            ecfg.max_prefills_per_step
        )
        # KV-fabric restores commit BETWEEN admission and chunk planning:
        # each committed block advances its sequence's num_cached, so the
        # chunk plan below (and the first chunk's hit-token accounting,
        # which reads the offset) already sees the restored prefix.
        step_restored = 0
        if self._fabric is not None:
            step_restored = self._apply_fabric_restores(admitted)
        # Mixed-step dispatch: this step's chunk plan spans newly admitted
        # prompts AND prompts already mid-prefill from earlier steps,
        # oldest first, capped by the token budget (None = whole prompts,
        # the pre-chunking behavior).
        plans = self.scheduler.schedule_prefill_chunks(self._prefill_budget)
        prefill_info: List[dict] = []
        step_hit_tokens = self._run_prefill_chunks(plans, prefill_info)

        spec_info: Optional[dict] = None
        if chained_seqs is not None:
            decoding = chained_seqs
        else:
            decoding = self.scheduler.schedule_decode()
            if decoding:
                if self._spec is not None:
                    if self._pipeline_depth:
                        self._pipeline_flushes["speculation"] += 1
                    spec_info = self._run_verify(decoding)
                if spec_info is None:
                    # Speculation off, or no sequence had proposals this
                    # step: the plain decode program is already compiled
                    # and exactly equivalent for one fed token per slot.
                    self._dispatch_decode(decoding)
                    if not self._pipeline_depth or self._spec is not None:
                        # Depth 0; and speculation at any depth, whose
                        # acceptance is value-dependent: commit now.
                        self._commit_head(follows_dispatch=True)
            if self._pending_chunks:
                # This step's chunks, and those a flush found unread,
                # read behind the decode dispatch that took their tokens
                # from the device (or behind nothing: no sequence decodes
                # yet).
                self._commit_chunks()
        return self._finish_step(
            t_step=t_step, bytes_before=bytes_before,
            preempted_before=preempted_before, plans=plans,
            step_hit_tokens=step_hit_tokens, step_restored=step_restored,
            prefill_info=prefill_info, decoding=decoding,
            spec_info=spec_info, chained=chained_seqs is not None,
        )

    def _finish_step(
        self,
        *,
        t_step: float,
        bytes_before: int,
        preempted_before: int,
        step_hit_tokens: int,
        step_restored: int,
        plans: List[tuple],
        prefill_info: List[dict],
        decoding: List[Sequence],
        spec_info: Optional[dict],
        chained: bool,
    ) -> dict:
        """The step's bookkeeping, after its last program is dispatched:
        the metric family, the gauges, the flight record, the clock's
        exit and the dict step() returns."""
        clock = self._clock if self._instrument else None
        if clock is not None:
            clock.switch("other")
        self._steps += 1
        window = self._window
        if window is not None:
            for seq in self.scheduler.running:
                self._held_tokens_full += seq.num_cached
                self._held_tokens_window += window.held_tokens(
                    seq.window_first, seq.num_cached
                )
        for metric in self._metric_family:
            metric._ensure_registered()
        if self._step_tokens_emitted:
            self._tokens_generated.inc(
                self._step_tokens_emitted, tags=self._metric_tags
            )
            self._step_tokens_emitted = 0
        preempted = self.scheduler.num_preemptions - preempted_before
        if preempted:
            self._preemptions.inc(preempted, tags=self._metric_tags)
        if step_hit_tokens:
            self._cache_hit_tokens += step_hit_tokens
            self._prefix_hits.inc(step_hit_tokens, tags=self._metric_tags)
        occupancy = len(decoding) / self.engine_config.max_decode_slots
        self._occupancy.set(occupancy, tags=self._metric_tags)
        self._cache_util.set(self.allocator.utilization(), tags=self._metric_tags)
        self._queue_depth.set(len(self.scheduler.waiting), tags=self._metric_tags)
        self._prefix_hit_rate.set(
            self._cache_hit_tokens / max(self._prefill_tokens, 1),
            tags=self._metric_tags,
        )
        self._evictable_blocks.set(
            self.allocator.num_evictable, tags=self._metric_tags
        )
        if self._fabric is not None:
            self._fabric_hit_rate.set(
                self._fabric_restored_tokens / max(self._prefill_tokens, 1),
                tags=self._metric_tags,
            )
        backlog = self.scheduler.prefill_backlog_tokens()
        self._prefill_backlog.set(backlog, tags=self._metric_tags)
        if clock is not None:
            parts = []
            if plans:
                parts.append("prefill")
            if decoding:
                parts.append("verify" if spec_info is not None else "decode")
            elif self._step_commits:
                # Drain-only iteration: nothing dispatched, but a stale
                # in-flight record committed (e.g. every member finished
                # or aborted since its dispatch).
                parts.append("commit")
            record = {
                "step": self._steps - 1,
                "phase": "+".join(parts) or "idle",
                "attn_impl": self._attn_impl,
                "tensor_parallel_size": self._tp,
                # Explicit host<->device bytes this step moved (program
                # inputs + sampled tokens, target AND draft runner):
                # flat in tensor_parallel_size — the tp acceptance tests
                # assert the series is identical at tp=1 and tp=2, i.e.
                # no per-token gather hides in the decode loop.
                "host_transfer_bytes": (
                    self._host_transfer_bytes() - bytes_before
                ),
                "batch_size": len(decoding),
                "num_prefills": len(plans),
                "prefills": prefill_info,
                # Acceptance invariant: with chunking on, tokens_in (the
                # prompt tokens actually fed this step) never exceeds
                # prefill_budget — asserted from these records in tests.
                "tokens_in": sum(p["tokens"] for p in prefill_info),
                "prefill_budget": self._prefill_budget,
                "prefill_backlog_tokens": backlog,
                # Tokens COMMITTED this iteration (prefill finals + decode
                # or verify commits) — a dispatched-but-uncommitted token
                # is not out yet.
                "tokens_out": self._step_first_tokens
                + sum(c["tokens"] for c in self._step_commits),
                "cache_hit_tokens": step_hit_tokens,
                "preempted": preempted,
                "queue_depth": len(self.scheduler.waiting),
                "time": t_step,
                # Which dispatch each commit of this step belongs to (its
                # own at depth 0).
                "commits": self._step_commits,
                # duration_s, the measured phase seconds that sum to it
                # (observability.ledger reads its columns from these) and
                # host_exposed_s, this step's share of stats()
                # host_exposed_total_s.
                **clock.step_record(),
            }
            if self._pipeline_depth:
                record["loop"] = "async"
                record["chained"] = chained
                record["inflight_depth"] = len(self._inflight)
            if spec_info is not None:
                # Verify record: which proposer ran, how wide the fed
                # bucket was, and the proposed/accepted/emitted counts —
                # the per-step acceptance story for the flight recorder.
                record["speculation"] = spec_info
            if self._fabric is not None:
                record["fabric_restored_blocks"] = step_restored
            self.flight_recorder.record_step(record)
            clock.exit_step(self.has_work())
        return {
            "num_prefilled": len(plans),
            "num_decoding": len(decoding),
            "occupancy": occupancy,
            "cache_utilization": self.allocator.utilization(),
            "queue_depth": len(self.scheduler.waiting),
            "preempted": preempted,
            "cache_hit_tokens": step_hit_tokens,
            "evictable_blocks": self.allocator.num_evictable,
            "prefill_backlog_tokens": backlog,
        }

    def _cache_class_stats(self) -> dict:
        """Per cache class of a model with a window class: its layers,
        horizon, blocks (the null block left out), blocks in use and the
        bytes a cached token costs in it."""
        token_bytes = self.runner.kv_token_bytes()
        out = {}
        for cls, allocator in zip(
            self.model_config.cache_classes,
            (self.allocator, self._window.allocator),
        ):
            out[cls.name] = {
                "layers": cls.layers,
                "horizon": cls.horizon,
                "blocks": allocator.num_usable,
                "blocks_in_use": allocator.num_allocated,
                "bytes_per_token": cls.layers * token_bytes,
            }
        return out

    def _host_transfer_bytes(self) -> int:
        """Cumulative explicit host<->device bytes across the target
        runner AND the draft-model runner (whose mirror pool shards the
        same way): the per-step delta rides the flight records."""
        total = self.runner.host_transfer_bytes()
        spec_runner = (
            getattr(self._spec, "runner", None)
            if self._spec is not None
            else None
        )
        if spec_runner is not None:
            total += spec_runner.host_transfer_bytes()
        return total

    # ---------------- KV fabric ----------------

    def _apply_fabric_restores(self, admitted: List[Sequence]) -> int:
        """Resolve each newly admitted sequence's fabric restore plan
        (Scheduler._admit probed the fabric and pre-allocated the target
        slots): fetch the planned chain of payloads in one batch RPC and
        commit them in chain order — copy the content into the slot FIRST,
        then advance num_cached and register the chain key, so a
        half-written block is never discoverable under its key. The chain
        stops at the first miss or failed copy-in; the remaining slots
        simply stay plain prefill targets (no rollback needed — they are
        already legitimate mid-chain members of the block table, and
        num_cached never claimed them). Returns blocks restored."""
        bs = self.engine_config.block_size
        restored = 0
        hit_blocks = 0
        for seq in admitted:
            plan = seq.pending_restore
            if not plan:
                continue
            seq.pending_restore = []
            self._current_rid = seq.request.request_id
            hit_blocks += len(plan)
            payloads = self._fabric.get_many([h for _, h in plan])
            for (block, h), payload in zip(plan, payloads):
                if payload is None:
                    break  # chain broken: later blocks cannot commit either
                try:
                    self.runner.restore_block(block, payload)
                except Exception:
                    break  # failed copy-in: the slot stays a prefill target
                seq.num_cached += bs
                seq.block_hashes.append(h)
                self.allocator.register(block, h)
                restored += 1
                self._fabric_restored_tokens += bs
        self._current_rid = None
        if hit_blocks:
            self._fabric_hit_total += hit_blocks
            self._fabric_hits.inc(hit_blocks, tags=self._metric_tags)
        if restored:
            self._fabric_restored_total += restored
            self._fabric_restores.inc(restored, tags=self._metric_tags)
        return restored

    def _spill_block(self, block: int, block_hash: int) -> None:
        """BlockAllocator.on_evict hook: demote the dying block's device
        content to the fabric's host tier, keyed by its chain hash. Best
        effort end to end — the allocator contains hook exceptions and
        the client degrades to a no-op — so eviction always completes."""
        if self._fabric.put(block_hash, self.runner.extract_block(block)):
            self._fabric_spilled_total += 1
            self._fabric_spills.inc(tags=self._metric_tags)

    def flush_kv_fabric(self) -> int:
        """Demote every cached-but-unreferenced device block into the
        fabric in one batch RPC — the drain path's cache preservation:
        a victim replica's reusable prefixes survive as fabric entries
        instead of dying with the engine actor. Returns how many of the
        flushed blocks are resident afterwards; 0 without a fabric."""
        if self._fabric is None:
            return 0
        items = [
            (h, self.runner.extract_block(block))
            for block, h in self.allocator.evictable_items()
        ]
        n = self._fabric.put_many(items)
        if n:
            self._fabric_spilled_total += n
            self._fabric_spills.inc(n, tags=self._metric_tags)
        return n

    def _run_verify(self, decoding: List[Sequence]) -> Optional[dict]:
        """Speculative verify phase: ask the proposer for up to k tokens
        per running sequence, score them all in ONE target-model step
        (GPTRunner.verify — the partial-prefill shape batched over the
        decode slots), accept each sequence's longest proposal prefix that
        agrees with the target argmax plus the correction/bonus token, and
        roll back the rejected tail (Scheduler.rollback: context-length
        rewind + block-table trim). Emits 1..k+1 tokens per sequence per
        step; greedy outputs are token-identical to the plain decode loop
        by construction (out[i] IS the token decode would have produced).

        Returns the flight-recorder speculation record, or None when no
        sequence had usable proposals this step — the caller then runs the
        plain (already-compiled) decode program, which is exactly
        equivalent for one fed token per slot."""
        ecfg = self.engine_config
        clock = self._clock if self._instrument else None
        # Prepare starts before the proposer: proposal cost (draft-model
        # steps, host-side matching) is part of what the verify phase
        # must amortize, so it belongs in the phase=verify histogram.
        t_verify = clock.switch("prepare") if clock is not None else 0.0
        k = ecfg.num_speculative_tokens
        proposals = self._spec.propose(decoding, k)
        plans: List[List[int]] = []
        max_fed = 1
        for seq, props in zip(decoding, proposals):
            props = [int(t) for t in props[:k]]
            # Never speculate past the request budget (the bonus token
            # must still fit) or the cache capacity; blocks are reserved
            # opportunistically — speculation never preempts a neighbor.
            cap = min(
                len(props),
                seq.request.max_new_tokens - len(seq.generated) - 1,
                ecfg.max_model_len - seq.num_cached - 1,
            )
            props = props[: max(cap, 0)]
            if props:
                props = props[
                    : self.scheduler.reserve_speculative(seq, len(props))
                ]
            plans.append(props)
            max_fed = max(max_fed, 1 + len(props))
        if max_fed == 1:
            return None  # the caller's plain decode takes over prepare
        s_bucket = ecfg.verify_bucket_for(max_fed)
        # Preallocated per-bucket input buffers (zero-fill + repopulate);
        # reuse is safe — runner.verify blocks on the program's results.
        tokens, block_tables, context_lens, true_lens = self._verify_inputs[
            s_bucket
        ]
        tokens.fill(0)
        block_tables.fill(0)
        context_lens.fill(0)
        true_lens.fill(0)
        for i, (seq, props) in enumerate(zip(decoding, plans)):
            tokens[i, 0] = seq.last_token
            if props:
                tokens[i, 1 : 1 + len(props)] = props
            block_tables[i, : len(seq.block_table)] = seq.block_table
            context_lens[i] = seq.num_cached
            true_lens[i] = 1 + len(props)
        out = self.runner.verify(
            tokens, block_tables, context_lens, true_lens
        )
        if clock is not None:
            clock.ready()
        proposed = accepted = emitted = 0
        for i, (seq, props) in enumerate(zip(decoding, plans)):
            # Per-sequence commit section; nothing mutates before the
            # injection point, so a poisoned request dead-letters alone
            # and an unattributable failure retries the whole step from
            # consistent state (propose() is deterministic on retry).
            rid = seq.request.request_id
            self._current_rid = rid
            maybe_fail("engine.verify", detail=rid)
            base = seq.num_cached
            n_ok = 0
            while n_ok < len(props) and int(out[i, n_ok]) == props[n_ok]:
                n_ok += 1
            # out[i, n_ok] is the correction after a mismatch, or the
            # bonus token when every proposal matched — either way the
            # target's own argmax, so it is always committed.
            new_tokens = props[:n_ok] + [int(out[i, n_ok])]
            eos_id = seq.request.eos_id
            if eos_id is not None and eos_id in new_tokens:
                new_tokens = new_tokens[: new_tokens.index(eos_id) + 1]
            self.scheduler.rollback(seq, base + len(new_tokens))
            seq.generated.extend(new_tokens)
            self.scheduler.note_filled_blocks(seq)
            proposed += len(props)
            # Accepted = proposed tokens actually COMMITTED: an eos inside
            # the matched prefix truncates the commit, and the counter
            # must not claim the dropped tail.
            accepted += min(n_ok, len(new_tokens))
            emitted += len(new_tokens)
            self._emit(seq)
            self._maybe_finish(seq)
        self._current_rid = None
        self._decode_tokens += emitted
        self._decode_slot_steps += ecfg.max_decode_slots
        self._step_commits.append(
            {
                "dispatch_step": self._steps,
                "time": time.time(),
                "tokens": emitted,
            }
        )
        self._verify_steps += 1
        self._spec_proposed_total += proposed
        self._spec_accepted_total += accepted
        self._spec_emitted_total += emitted
        if proposed:
            self._spec_proposed.inc(proposed, tags=self._metric_tags)
        if accepted:
            self._spec_accepted.inc(accepted, tags=self._metric_tags)
        self._spec_acceptance.set(
            self._spec_accepted_total / max(self._spec_proposed_total, 1),
            tags=self._metric_tags,
        )
        if clock is not None:
            # One observation per batched verify dispatch (proposer +
            # program + the whole commit loop), never per token.
            self._h_step.observe(
                clock.switch("other") - t_verify,
                tags=self._step_tags["verify"],
            )
        return {
            "mode": self._spec.name,
            "fed_bucket": s_bucket,
            "proposed": proposed,
            "accepted": accepted,
            "emitted": emitted,
        }

    # ---------------- decode: dispatch and commit ----------------

    def _still_decoding(self, seq: Sequence, rid: str) -> bool:
        """Whether a dispatched slot still holds its running request."""
        return (
            seq.is_running
            and not seq.prefilling
            and self.scheduler.is_active(rid)
        )

    def _try_chain(self) -> Optional[List[Sequence]]:
        """Chain the in-flight decode into the next dispatch if — and
        only if — its tokens are still on the device and it is the one
        record in flight, the next decode batch would be the dispatched
        batch (same sequences, same slot order: the chained token input
        is slot-aligned on device) and at most prompts whose first token
        is on the device too (`_unfed`), AND every write can be covered
        without preempting anyone (reserve_decode_lookahead). On success
        the chained program is already dispatched when this returns; on
        any mismatch it counts the cause, returns None and the caller
        flushes."""
        rec = self._inflight[0]
        flushes = self._pipeline_flushes
        if rec.tokens_host is not None or len(self._inflight) > 1:
            flushes["retry"] += 1
            return None
        if not all(map(self._still_decoding, rec.seqs, rec.rids)):
            flushes["left"] += 1
            return None
        current = [s for s in self.scheduler.running if not s.prefilling]
        joiners: List[Sequence] = []
        if current != rec.seqs:  # a Sequence equals only itself
            # A prompt whose last chunk is dispatched and unread joins in
            # place: its token is on the device like the record's, and
            # the record's sequences keep their lanes (a state slot is a
            # lane for life; an index lane holds while the joiners come
            # behind the record's sequences).
            unfed = self._unfed
            joiners = [s for s in current if s in unfed]
            kept = len(rec.seqs)
            if (
                not joiners
                or len(current) != kept + len(joiners)
                or not (self._recurrent or current[:kept] == rec.seqs)
            ):
                flushes["joined"] += 1
                return None
        if not self.scheduler.reserve_decode_lookahead(rec.seqs, joiners):
            flushes["lookahead"] += 1
            return None
        self._chained_dispatches += 1
        self._dispatch_decode(current, chained_from=rec)
        return current

    def _dispatch_decode(
        self,
        seqs: List[Sequence],
        chained_from: Optional[_InflightStep] = None,
    ) -> None:
        """One iteration-level decode dispatch: every sequence of `seqs`
        advances one token through the batched decode program, and the
        record joins `_inflight` for `_commit_head`.

        From committed state the inputs are each sequence's last token
        and num_cached. A sequence whose last chunk is dispatched and
        unread (`_unfed`) has no token on the host: its lane's token is
        set on the device from the chunk's output, its position is where
        the chunk stopped (nothing of it is in flight, chained or not),
        and a step without such a sequence pays one dict's truth value
        for it. Chained, the tokens are the in-flight record's
        on-device `next_tokens` — no host sync anywhere on this path —
        and the in-flight token for slot i has not committed yet, so its
        write position is num_cached + 1 and its context covers
        num_cached + 1 tokens; both advance deterministically without
        knowing the token's value. Unused slots then carry whatever the
        previous program sampled — they scatter into the null block
        exactly like the zero padding.

        The runner's hook leaves the clock in `wait`: the commit that
        follows, or the step's tail, moves it on."""
        clock = self._clock if self._instrument else None
        t_prepare = clock.switch("prepare") if clock is not None else 0.0
        # Preallocated input buffers: zero-fill + repopulate, never
        # allocate (runner.decode copies them at dispatch).
        ahead = 0 if chained_from is None else 1
        tokens = self._dec_tokens
        positions = self._dec_positions
        block_tables = self._dec_block_tables
        context_lens = self._dec_context_lens
        positions.fill(0)
        block_tables.fill(0)
        context_lens.fill(0)
        if not ahead:
            tokens.fill(0)
        context_tokens = 0
        recurrent = self._recurrent
        window = self._window
        extra = {}
        if window is not None:
            # The lanes' tables in the window class, and what the dispatch
            # asks of it: each lane's context as far as its layers see it.
            window_tables = extra["window_tables"] = self._dec_window_tables
            window_tables.fill(0)
            window_tokens = 0
        for i, seq in enumerate(seqs):
            if recurrent:
                # A sequence's decode lane is its state slot, dispatch
                # after dispatch: the program updates the state pools in
                # place, lane for lane.
                i = seq.state_slot
            cached = seq.num_cached + ahead
            if not ahead:
                tokens[i] = seq.last_token
            positions[i] = cached
            block_tables[i, : len(seq.block_table)] = seq.block_table
            context_lens[i] = cached
            context_tokens += cached
            if window is not None:
                table = seq.window_table
                window_tables[i, : len(table)] = table
                window_tokens += min(cached, window.horizon)
        tokens_in = chained_from.tokens_dev if ahead else tokens
        joins = ()
        if self._unfed:
            unfed = self._unfed
            joins = [
                (seq.state_slot if recurrent else i, seq)
                for i, seq in enumerate(seqs)
                if seq in unfed
            ]
            for lane, seq in joins:
                if ahead:
                    positions[lane] -= 1
                    context_lens[lane] -= 1
                    context_tokens -= 1
                    if window is not None and seq.num_cached < window.horizon:
                        window_tokens -= 1
                tokens_in = self.runner.join_token(
                    tokens_in, lane, unfed[seq].out
                )
        # What this dispatch asks the paged kernel to read: len(seqs)
        # sequences, context_tokens cached positions in all (the sum of
        # context_lens), in every layer.
        self._decode_dispatches += 1
        self._decode_context_tokens += context_tokens
        if clock is not None:
            clock.describe_decode(len(seqs), context_tokens)
        if window is not None:
            self._decode_window_tokens += window_tokens
        tokens_dev = self.runner.decode(
            tokens_in, positions, block_tables, context_lens, **extra,
        )
        for _, seq in joins:
            self._unfed.pop(seq).fed = True
        rids = [s.request.request_id for s in seqs]
        clock_seq = clock.dispatches if clock is not None else None
        self._dispatch_serial += 1
        rec = _InflightStep(
            seqs, rids, tokens_dev, self._steps, clock_seq, t_prepare,
            self._dispatch_serial,
        )
        if recurrent:
            rec.lanes = [s.state_slot for s in seqs]
        self._inflight.append(rec)

    def _commit_head(self, follows_dispatch: bool = False) -> None:
        """Fetch and commit the OLDEST in-flight record: per-sequence
        poison site, num_cached advance, block publication, emission,
        finish detection. `follows_dispatch` says that the step which
        dispatched the record commits it at once (depth 0, speculation),
        with the step's tail next and not admission.

        Sequences that went inactive since dispatch (finished at the
        previous commit, aborted, preempted on a flush) are skipped —
        their fetched token is the EOS/length overshoot or an orphan, and
        it is dropped before any emission. On a mid-loop exception the
        record stays at the head with commit_idx advanced past the
        already-committed slots, so the server's step retry resumes the
        commit exactly where it stopped; failure_step() attributes the
        exception against this record's DISPATCH index."""
        rec = self._inflight[0]
        ecfg = self.engine_config
        clock = self._clock if self._instrument else None
        fetch = rec.tokens_host is None
        t0 = 0.0
        if clock is not None:
            t0 = clock.switch("wait" if fetch else "commit")
        self._attribution_step = rec.dispatch_step
        if fetch:
            # Materialize the async copy (in flight since dispatch). A
            # failed decode PROGRAM surfaces here, attributed above. The
            # device array goes now: freeing it releases the GIL, which
            # after the emission loop the streams' consumer threads take
            # with the device idle (1 ms a step in the chat cell, PR 30).
            rec.tokens_host = np.asarray(rec.tokens_dev)
            rec.tokens_dev = None
            if self._counts_routing:
                # The step's routing counts ride the same fetch.
                self.runner.count_routing(rec.tokens_host)
            if clock is not None:
                # The tokens are on host: the device is idle from now
                # if nothing newer is out.
                clock.ready(rec.clock_seq)
        next_tokens = rec.tokens_host
        committed = 0
        while rec.commit_idx < len(rec.seqs):
            i = rec.commit_idx
            seq = rec.seqs[i]
            if not self._still_decoding(seq, rec.rids[i]):
                rec.commit_idx += 1
                continue
            # Per-sequence section; placed before any mutation so a
            # failure here leaves this sequence (and every later one,
            # whose commit the retry resumes) consistent.
            self._current_rid = rec.rids[i]
            maybe_fail("llm.decode.seq", detail=rec.rids[i])
            seq.num_cached += 1
            if self._window is not None:
                self.scheduler.advance_window(seq)
            lane = i if rec.lanes is None else rec.lanes[i]
            seq.generated.append(int(next_tokens[lane]))
            if seq.num_cached % ecfg.block_size == 0:
                # A block just filled: publish it to the prefix cache
                # before a finish below could release it.
                self.scheduler.note_filled_blocks(seq)
            rec.commit_idx += 1
            committed += 1
            self._emit(seq)
            self._maybe_finish(seq)
        self._current_rid = None
        self._attribution_step = None
        self._inflight.popleft()
        if self.on_commit is not None:
            self.on_commit()
        self._decode_tokens += committed
        self._decode_slot_steps += ecfg.max_decode_slots
        self._step_commits.append(
            {
                "dispatch_step": rec.dispatch_step,
                "time": time.time(),
                "tokens": committed,
            }
        )
        if clock is not None:
            # One observation per batched decode dispatch, never per
            # token; deferred, the commit's half (the chain hides the other).
            if follows_dispatch:
                took = clock.switch("other") - rec.t_prepare
            else:
                took = clock.switch("schedule") - t0
            self._h_step.observe(took, tags=self._step_tags["decode"])

    @property
    def _defers_chunks(self) -> bool:
        """Whether a chunk's output stays on the device past its dispatch:
        read from the state that says what depends on the value (warm-up
        steps at depth 0 with the engine's own depth put aside)."""
        return bool(
            self._pipeline_depth
            and self._spec is None
            and not self._publish_on_fill
        )

    def _commit_dispatched(self, keep: int) -> None:
        """Commit what earlier dispatches left out, oldest dispatch first,
        down to the `keep` newest decode records: each decode record and,
        in front of it, the unread chunks that ran before it. With a
        record kept (the chained dispatch just made) the chunks that ran
        before IT are committed too: it took their tokens from the device.
        On a flush boundary (`keep` 0) the chunks behind the last record
        stay unread: no decode has taken their tokens yet, the one this
        step dispatches will, and step() reads them behind it."""
        inflight, chunks = self._inflight, self._pending_chunks
        while len(inflight) > keep:
            if chunks and chunks[0].serial < inflight[0].serial:
                self._commit_chunks(before=inflight[0].serial)
            self._commit_head()
        if keep and chunks:
            self._commit_chunks(before=inflight[0].serial)
            if self._instrument:
                self._clock.switch("schedule")

    def _commit_chunks(self, before: Optional[int] = None) -> None:
        """Read and commit the pending chunks dispatched before dispatch
        number `before` (every one when None), oldest first: the read
        waits for the program and raises if it failed, pinned on the
        chunk's request and dispatch step (the record stays at the head;
        once its request is dead-lettered the retry drops it unread).
        A chunk whose sequence finished, was aborted, expired or was
        preempted since is owed nothing and is dropped unread. Leaves the
        clock in `commit`."""
        chunks = self._pending_chunks
        clock = self._clock if self._instrument else None
        emitted = self._step_first_tokens
        while chunks and (before is None or chunks[0].serial < before):
            chunk = chunks[0]
            seq = chunk.seq
            owed = (
                seq.is_running
                and seq.num_preemptions == chunk.preemptions
                and self.scheduler.is_active(chunk.rid)
            )
            tok = 0
            if owed:
                self._current_rid = chunk.rid
                self._attribution_step = chunk.dispatch_step
                if clock is not None:
                    clock.switch("wait")
                tok = self.runner.read_chunk(chunk.out)
                if clock is not None:
                    # The value is on the host: the device is idle from
                    # now if nothing newer is out.
                    clock.ready(chunk.clock_seq)
            chunk.out = None
            chunks.popleft()
            if self._unfed.get(seq) is chunk:
                del self._unfed[seq]  # read before any decode took it
            if owed:
                self._first_token(chunk, tok)
            self._attribution_step = None
        self._current_rid = None
        if self._step_first_tokens > emitted and self.on_commit is not None:
            self.on_commit()

    def _first_token(self, chunk: _PendingChunk, tok: int) -> None:
        """What a chunk owes once its value is on the host: a last chunk's
        token appended, emitted and checked for a finish; the request's
        span, the step histogram and, once a request, TTFT."""
        seq = chunk.seq
        final = chunk.final
        if final:
            seq.generated.append(tok)
            self._step_first_tokens += 1
            self._prompts_prefilled += 1
            self._first_tokens_on_device += chunk.fed
        if self._instrument:
            t1 = time.time()
            phase = "partial_prefill" if chunk.offset else "prefill"
            # t0 and t1 are the span's timestamps (wall clock: identity
            # across actors); the histogram's delta rides on the pair.
            self._h_step.observe(
                t1 - chunk.t0,
                tags=(
                    self._step_tags[phase]
                    if final
                    else self._chunk_step_tags[phase]
                ),
            )
            rt = self._req_traces.get(chunk.rid)
            if rt is not None:
                first_admission = rt.first_token_s is None
                rt.on_prefilled(
                    chunk.t0, t1, chunk.kind, chunk.bucket, chunk.take,
                    chunk.offset, len(seq.generated),
                    chunk=chunk.index, final=final,
                )
                if final and first_admission:
                    # TTFT observes exactly once per request: at the
                    # final chunk of its FIRST admission (chunked or
                    # not), when the first token is on the host.
                    self._h_ttft.observe(
                        t1 - rt.submit_s, tags=self._metric_tags
                    )
        if final:
            self._emit(seq)
            self._maybe_finish(seq)

    def _run_prefill_chunks(
        self,
        plans: List[tuple],
        info_out: Optional[List[dict]] = None,
    ) -> int:
        """Run this step's prefill chunk plan ((sequence, token count)
        pairs from Scheduler.schedule_prefill_chunks); returns the prompt
        tokens served from the prefix cache this step. Every chunk is a
        dispatch here and a commit (`_first_token`): in the statement after
        the dispatch at depth 0, under speculation and in a prefill-role
        engine, else where `_commit_chunks` reads it, behind the decode
        dispatch that the prompt joins. What needs no value (num_cached,
        the window class, block publication) advances here either way, so
        a failure mid-plan leaves every sequence — including the culprit —
        consistent: a retry re-plans from there, a dead-letter releases
        all of the culprit's blocks via the normal abort path and its
        unread chunks are dropped. Only the FINAL chunk of a prompt
        produces a token; continuation chunks just stream K/V into the
        cache. With instrumentation, `info_out` collects one record per
        chunk for the flight recorder of the step that dispatched it."""
        instrument = self._instrument
        clock = self._clock if instrument else None
        defer = self._defers_chunks
        hit_tokens = 0
        for seq, take in plans:
            # Per-sequence section: an exception below is attributable to
            # this request (LLMServer._loop fails only it and keeps going).
            rid = seq.request.request_id
            self._current_rid = rid
            if self._window is not None and not self.scheduler.reserve_chunk(
                seq, take
            ):
                continue  # back in the queue: the window class was full
            if clock is not None:
                # Per chunk: prepare (CoW copy, input build, dispatch),
                # wait (the read, wherever it is made), commit
                # (publication, spans, emission).
                clock.switch("prepare")
            first_chunk = seq.num_chunks == 0
            final = take >= seq.prefill_len - seq.num_cached
            if first_chunk:
                maybe_fail("llm.prefill", detail=rid)
            maybe_fail("engine.prefill_chunk", detail=rid)
            offset = seq.num_cached  # cache-matched prefix + prior chunks
            was_cow = seq.pending_copy is not None
            t0 = 0.0
            kind = bucket = None
            if instrument:
                t0 = time.time()
                kind = "cow" if was_cow else ("partial" if offset else "full")
                bucket = self.engine_config.bucket_for(max(take, 1))
                rt = self._req_traces.get(rid)
                if rt is not None and rt.queue_start is not None:
                    # The queue ends when the request's FIRST chunk starts
                    # computing (one wait per admission; a preempt-resume
                    # reopens the clock and its first resumed chunk closes
                    # it again).
                    self._h_queue.observe(
                        rt.on_admitted(t0), tags=self._metric_tags
                    )
            if was_cow:
                # Copy-on-write: the last matched block is shared and this
                # prefill writes its final token's K/V into it. pending_copy
                # is cleared only AFTER the device copy lands and the
                # copy-source ref is dropped: if copy_block raises (poison
                # request, injected fault), _release must still see the
                # marker and free src — clearing first leaked the ref and
                # permanently shrank the block pool (found by lint RTL403).
                src, dst = seq.pending_copy
                self.runner.copy_block(src, dst)
                self.allocator.free([src])  # drop admission's copy-source ref
                seq.pending_copy = None
            chunk_ids = seq.prefill_ids[offset : offset + take]
            # The state slot the chunk starts from and leaves its state in
            # (a model with recurrent layers; the first chunk starts from
            # an empty state whatever the slot held).
            slot = (seq.state_slot,) if self._recurrent else ()
            # The window class's table beside the full one (a model with
            # sliding-window layers).
            extra = (
                {"window_ids": seq.window_table}
                if self._window is not None
                else {}
            )
            if offset > 0:
                out = self.runner.prefill_suffix(
                    chunk_ids, seq.block_table, offset, *slot, **extra
                )
                if first_chunk:
                    hit_tokens += offset
            else:
                # First chunk from a cold cache: the full-prefill program
                # for this chunk's bucket. Slice the table — the sequence
                # owns blocks for its WHOLE prompt, but this program's
                # block vector is sized for the chunk's bucket.
                out = self.runner.prefill(
                    chunk_ids,
                    seq.block_table[
                        : blocks_for_tokens(
                            take, self.engine_config.block_size
                        )
                    ],
                    *slot,
                    **extra,
                )
            self._dispatch_serial += 1
            chunk = _PendingChunk(
                seq, rid, take, offset, out, self._steps,
                clock.dispatches if clock is not None else None,
                self._dispatch_serial, final, t0, kind, bucket,
            )
            tok = 0
            if not defer:
                tok = self.runner.read_chunk(out)
                chunk.out = None
                if clock is not None:
                    clock.ready()
            elif clock is not None:
                clock.switch("commit")
            self._prefill_tokens += take
            self._prefill_chunk_dispatches += 1
            seq.num_cached = offset + take
            seq.num_chunks += 1
            if self._window is not None:
                self.scheduler.advance_window(seq)
            if final and seq.num_chunks > 1:
                self._chunked_prefill_requests += 1
            # Publish every block this chunk filled: a concurrent request
            # with the same prompt can share the prefix before the whole
            # prompt even finishes prefilling (its chunk runs behind this
            # one on the device).
            pre_hashes = len(seq.block_hashes)
            self.scheduler.note_filled_blocks(seq)
            if self._publish_on_fill and len(seq.block_hashes) > pre_hashes:
                # Prefill-role handoff: push this chunk's just-filled
                # blocks to the fabric NOW, so they are resident before
                # the request's reply (the barrier the decode-role
                # engine's admission relies on) can possibly seal.
                pushed = self._fabric.put_many(
                    [
                        (
                            seq.block_hashes[j],
                            self.runner.extract_block(seq.block_table[j]),
                        )
                        for j in range(pre_hashes, len(seq.block_hashes))
                    ]
                )
                if pushed:
                    self._fabric_spilled_total += pushed
                    self._fabric_spills.inc(
                        pushed, tags=self._metric_tags
                    )
            if info_out is not None and instrument:
                info_out.append(
                    {
                        "request_id": rid,
                        "kind": kind,
                        "bucket": bucket,
                        "tokens": take,
                        "cached_tokens": offset,
                        "chunk": chunk.index,
                        "final": final,
                    }
                )
            if defer:
                self._pending_chunks.append(chunk)
                if final:
                    self._unfed[seq] = chunk
            else:
                self._first_token(chunk, tok)
        self._current_rid = None
        if clock is not None and plans:
            clock.switch("schedule")
        return hit_tokens

    def _emit(self, seq: Sequence) -> None:
        cb = self._on_token.get(seq.request.request_id)
        while seq.emitted < len(seq.generated):
            token = seq.generated[seq.emitted]
            seq.emitted += 1
            self._step_tokens_emitted += 1  # exported by _finish_step
            if cb is not None:
                cb(token)

    def _maybe_finish(self, seq: Sequence) -> None:
        req = seq.request
        reason = None
        if req.eos_id is not None and seq.generated[-1] == req.eos_id:
            reason = FINISH_EOS
        elif len(seq.generated) >= req.max_new_tokens:
            reason = FINISH_LENGTH
        if reason is not None:
            self.scheduler.finish(seq, reason)
            self._finished(seq)

    def _note_preempt(self, seq: Sequence) -> None:
        """Scheduler preemption hook: drop the proposer's per-request
        state (a stateful proposer's draft blocks must not outlive the
        victim's own KV blocks — the resume re-prefills both caches),
        then close the victim's decode-stretch span, mark the preemption,
        and restart its queue-wait clock."""
        if self._spec is not None:
            self._spec.release(seq.request.request_id)
        rt = self._req_traces.get(seq.request.request_id)
        if rt is not None:
            rt.on_preempt(time.time(), len(seq.generated))

    def _finished(self, seq: Sequence) -> None:
        req_id = seq.request.request_id
        if seq.request.deadline_s is not None:
            # Terminal for any reason: this deadline no longer needs the
            # per-step sweep. Clamped so a double-finish can never drive
            # the gate negative and disable expiry for live requests.
            self._deadline_count = max(0, self._deadline_count - 1)
        if self._spec is not None:
            # Terminal for any reason (finish, abort, dead-letter): the
            # proposer's per-request resources (draft KV blocks) go with
            # the request's own KV blocks.
            self._spec.release(req_id)
        self._on_token.pop(req_id, None)
        rt = self._req_traces.pop(req_id, None)
        if rt is not None:
            now = time.time()
            rt.on_finish(now, seq)
            self._h_e2e.observe(now - rt.submit_s, tags=self._metric_tags)
            n = len(seq.generated)
            if rt.first_token_s is not None and n >= 2:
                # Mean inter-token latency after the first token (TPOT);
                # single-token requests have no decode interval to report.
                self._h_tpot.observe(
                    (now - rt.first_token_s) / (n - 1), tags=self._metric_tags
                )
        cb = self._on_finish.pop(req_id, None)
        if cb is not None:
            cb(seq)

    # ---------------- convenience ----------------

    def generate(
        self,
        prompts: List[List[int]],
        max_new_tokens: Optional[int] = None,
        eos_id: Optional[int] = None,
    ) -> List[List[int]]:
        """Run a batch of prompts to completion with continuous batching and
        return their generated token ids, in request order."""
        outputs: List[List[int]] = []
        for prompt in prompts:
            tokens: List[int] = []
            self.add_request(
                prompt,
                max_new_tokens=max_new_tokens,
                eos_id=eos_id,
                on_token=tokens.append,
            )
            outputs.append(tokens)
        while self.has_work():
            self.step()
        return outputs

    def stats(self) -> dict:
        elapsed = max(time.monotonic() - self._start, 1e-9)
        # Per-chip vs aggregate cache bytes: the pools shard on the head
        # axis, so each chip holds aggregate / tensor_parallel_size — the
        # number that decides whether a model's cache fits per-chip HBM.
        pool_bytes = self.runner.kv_pool_bytes()
        fabric_store = None
        if self._fabric is not None:
            # One store RPC per stats scrape (never per step): the store
            # is shared, so occupancy only has one true source.
            fabric_store = self._fabric.stats()
            if fabric_store:
                self._fabric_bytes_used.set(
                    float(fabric_store.get("bytes_used", 0)),
                    tags=self._metric_tags,
                )
        return {
            "engine_id": self._metric_tags["engine"],
            "attn_impl": self._attn_impl,
            "kv_cache_dtype": self.runner.kv_cache_dtype_str,
            "tensor_parallel_size": self._tp,
            "kv_pool_bytes": pool_bytes["aggregate"],
            "kv_pool_bytes_per_shard": pool_bytes["per_shard"],
            # PartitionSpec of the live pools (None at tp=1): proof the
            # cache is still head-sharded after whatever traffic ran.
            "kv_pool_sharding": self.runner.pool_sharding_spec(),
            # Weight count for the fleet ledger's MFU estimate (decode
            # FLOPs ~= 2 * model_params per generated token). Counted
            # once at runner init, not per scrape.
            "model_params": getattr(self.runner, "num_params", None),
            # Bytes of those weights as the runner holds them (matrices
            # in the compute dtype): what a decode step reads of them.
            "weight_bytes": getattr(self.runner, "weight_bytes", None),
            "host_transfer_bytes": self._host_transfer_bytes(),
            "steps": self._steps,
            "decode_tokens": self._decode_tokens,
            # The pipeline's depth (EngineConfig.async_scheduling), and
            # how many records are dispatched-but-uncommitted right now.
            "async_scheduling": bool(self._pipeline_depth),
            "inflight_steps": len(self._inflight),
            # The step loop's phase clock: seconds in each phase (they
            # sum to the wall time from the first instrumented step's
            # entry on, idle stretches left out), steps that dispatched
            # a program, and host_exposed, host time during which the
            # device had nothing queued.
            **self._clock.stats(),
            # What the decode dispatches asked the paged kernel to read
            # (sum of context_lens, in every layer), and the shape that
            # turns it into bytes and operations.
            "decode_dispatches": self._decode_dispatches,
            "decode_context_tokens": self._decode_context_tokens,
            # Of those, the ones made from an in-flight record's device
            # tokens (depth 1), and the steps that could not chain, in
            # all and by cause (FLUSH_CAUSES).
            "chained_decode_dispatches": self._chained_dispatches,
            # Prompts whose first token was committed, and those of them
            # whose token had reached the decode dispatch they joined on
            # the device, before the step thread read it (depth 1).
            "prompts_prefilled": self._prompts_prefilled,
            "first_tokens_on_device": self._first_tokens_on_device,
            "pipeline_flushes": sum(self._pipeline_flushes.values()),
            "pipeline_flushes_by_cause": dict(self._pipeline_flushes),
            "attention_shape": self.runner.attention_shape(),
            # Whether a cached prefix can be shared on this model (not
            # with recurrent layers: nothing snapshots their state at a
            # block boundary), and, for such a model, its state slots,
            # state traffic and routing counts (HybridRunner.stats).
            "prefix_caching": self.allocator.enable_prefix_caching,
            "recurrent_state": self._recurrent,
            **(self.runner.stats() if self._counts_routing else {}),
            **(
                {
                    "state_slots_in_use": (
                        self.scheduler.state_slots.num_in_use
                    ),
                    "state_slot_resets": (
                        self.scheduler.state_slots.num_resets
                    ),
                }
                if self._recurrent
                else {}
            ),
            # A model with sliding-window layers: its cache classes (the
            # full class first), what the window class freed, what the
            # decode dispatches asked of it and what each class held.
            **(
                {
                    "cache_classes": self._cache_class_stats(),
                    "window_blocks_freed": self._window.num_freed,
                    "decode_window_tokens": self._decode_window_tokens,
                    "held_tokens_full": self._held_tokens_full,
                    "held_tokens_window": self._held_tokens_window,
                }
                if self._window is not None
                else {}
            ),
            # Set-up on the same footing: wall seconds warming the
            # programs and, with `instrument` on, what JAX spent compiling
            # in this process since the first such engine was built
            # (CompileClock); and how many step programs were read from the
            # program store and how many traced and lowered (each a miss,
            # by reason) since this engine was built. All zero where the
            # store is off (the CPU backend).
            "warmup_s": self._warmup_s,
            **self._program_store.since(self._programs_before),
            **(
                {
                    f"jax_{key}": value
                    for key, value in self._compile_clock.totals().items()
                }
                if self._compile_clock is not None
                else {}
            ),
            "mean_occupancy": (
                self._decode_tokens / self._decode_slot_steps
                if self._decode_slot_steps
                else 0.0
            ),
            "preemptions": self.scheduler.num_preemptions,
            "num_preemptions": self.scheduler.num_preemptions,
            "cache_utilization": self.allocator.utilization(),
            "queue_depth": len(self.scheduler.waiting),
            "num_running": len(self.scheduler.running),
            "prefill_tokens": self._prefill_tokens,
            "prefill_token_budget": self._prefill_budget,
            "prefill_backlog_tokens": (
                self.scheduler.prefill_backlog_tokens()
            ),
            "prefill_chunk_dispatches": self._prefill_chunk_dispatches,
            "chunked_prefill_requests": self._chunked_prefill_requests,
            "prefix_cache_hit_tokens": self._cache_hit_tokens,
            "prefix_cache_hit_rate": (
                self._cache_hit_tokens / max(self._prefill_tokens, 1)
            ),
            "evictable_blocks": self.allocator.num_evictable,
            "prefix_cache_evictions": self.allocator.num_evictions,
            "cow_blocks": self.scheduler.num_cow_blocks,
            "engine_role": self.engine_config.engine_role,
            "kv_fabric": (
                self.engine_config.kv_fabric.name
                if self.engine_config.kv_fabric is not None
                else "off"
            ),
            "fabric_spill_blocks": self._fabric_spilled_total,
            "fabric_restore_blocks": self._fabric_restored_total,
            "fabric_hit_blocks": self._fabric_hit_total,
            "fabric_restored_tokens": self._fabric_restored_tokens,
            "fabric_hit_rate": (
                self._fabric_restored_tokens / max(self._prefill_tokens, 1)
            ),
            "fabric_store": fabric_store,
            "fabric_timeouts": self._fabric_timeout_total,
            "num_dead_letters": len(self._dead_letters),
            # Overload control plane: bounded-admission rejections and
            # deadline expiries (llm_engine_shed_requests /
            # llm_engine_expired_requests counters carry the same totals).
            "shed_requests": self._shed_total,
            "expired_requests": self._expired_total,
            "max_queue_len": self.engine_config.max_queue_len,
            "max_queue_tokens": self.engine_config.max_queue_tokens,
            "speculation": (
                self._spec.name if self._spec is not None else "off"
            ),
            "spec_proposed_tokens": self._spec_proposed_total,
            "spec_accepted_tokens": self._spec_accepted_total,
            "spec_acceptance_rate": (
                self._spec_accepted_total
                / max(self._spec_proposed_total, 1)
            ),
            "spec_verify_steps": self._verify_steps,
            # Draft-mirror pool occupancy (0 without a stateful proposer):
            # must return to 0 when no requests are in flight — leaked
            # mirror blocks after aborts/disconnects show up here.
            "spec_draft_pool_allocated": (
                self._spec.allocator.num_allocated
                if self._spec is not None
                and getattr(self._spec, "allocator", None) is not None
                else 0
            ),
            "kv_pool_allocated": self.allocator.num_allocated,
            # > 1.0 means verification is amortizing decode steps: tokens
            # emitted per verify-program dispatch, correction included.
            "spec_tokens_per_verify_step": (
                self._spec_emitted_total / max(self._verify_steps, 1)
            ),
            "uptime_s": elapsed,
        }


class _RequestState:
    """One request's way out of the engine: where its tokens change thread
    (step thread -> the event loop on which `generate` or
    `generate_stream` was called) and, with `instrument` on, that
    hand-over's clock. The emitting side (the step thread, or whoever
    holds the server's lock) files nothing here itself: `offer` and
    `finish` put `(state, item, stamp)` on the server's outbox, which goes
    to the loop where a commit ends (`LLMServer._flush`), and there `_deliver`
    appends to `items` and wakes the request's coroutine. One writer a
    field: `offered`, `finished`, `seq` and `error` are the emitting
    side's; `items`, `waiter`, `ended`, `late` and the `handoff_*` the
    loop's, so neither takes a lock."""

    __slots__ = (
        "loop", "emit", "per_token", "items", "waiter", "ended", "late",
        "finished", "seq", "error",
        "stamped", "offered", "handoff_s", "handoff_max_s", "handoff_tokens",
    )

    def __init__(self, loop, emit: Callable, per_token: bool, stamped: bool):
        self.loop = loop
        self.emit = emit
        # Whether a token wakes the coroutine (a stream) or only the end
        # does (the blocking call gathers its tokens then).
        self.per_token = per_token
        self.items: Deque[tuple] = deque()
        self.waiter: Optional[asyncio.Future] = None
        self.ended = False
        self.late = False
        self.finished = False
        self.seq: Optional[Sequence] = None
        self.error: Optional[BaseException] = None
        # Whether an item's stamp is the perf_counter reading at commit
        # and not 0.0.
        self.stamped = stamped
        self.offered = 0
        self.handoff_s = 0.0
        self.handoff_max_s = 0.0
        self.handoff_tokens = 0

    # ---- the emitting side ----

    def offer(self, token: int) -> None:
        """`on_token`."""
        if self.stamped:
            self.offered += 1
            self.emit(self, token, time.perf_counter())
        else:
            self.emit(self, token, 0.0)

    def finish(self, seq: Optional[Sequence] = None) -> None:
        """`on_finish`, and the end of a request that ends without one
        (`error` set first): once, behind the request's last token."""
        if self.finished:
            return
        self.finished = True
        self.seq = seq
        self.emit(self, _STREAM_END, 0.0)

    # ---- the loop's side ----

    def take(self, entry: tuple) -> int:
        """The token of an entry of `items`, its wait charged."""
        token, committed = entry
        if self.stamped:
            waited = time.perf_counter() - committed
            self.handoff_s += waited
            if waited > self.handoff_max_s:
                self.handoff_max_s = waited
            self.handoff_tokens += 1
        return token

    def wake(self) -> None:
        waiter = self.waiter
        if waiter is not None and not waiter.done():
            waiter.set_result(None)

    def expire(self) -> None:
        """The deadline's timer, one a request."""
        self.late = True
        self.wake()

    async def wait(self, idle_s: Optional[float] = None) -> None:
        """Parks the request's coroutine until `_deliver` has something
        for it, its deadline has passed or `idle_s` seconds have: the
        caller tells which by what it finds. No timer a token unless the
        request asked for an idle time-out."""
        if self.late:
            return
        self.waiter = self.loop.create_future()
        idle = (
            None if idle_s is None else self.loop.call_later(idle_s, self.wake)
        )
        try:
            await self.waiter
        finally:
            self.waiter = None
            if idle is not None:
                idle.cancel()


_STREAM_END = object()


def _deliver(batch: List[tuple]) -> None:
    """One flush of the server's outbox, on the loop its requests were
    submitted on: each item filed with its request, whose coroutine is
    woken through a future, with no lock and no thread."""
    for state, item, stamp in batch:
        state.items.append((item, stamp))
        if item is _STREAM_END:
            state.ended = True
        elif not state.per_token:
            continue
        state.wake()


def _off_loop(method: Callable) -> Callable:
    """A call of `LLMServer`'s that takes the server's lock. A step holds
    that lock for its whole length and the actor's event loop serves every
    stream, so nothing may wait for the lock on the loop: the call is a
    coroutine that runs `method` on the server's pool."""

    @functools.wraps(method)
    async def call(self, *args, **kwargs):
        return await self._in_pool(method, self, *args, **kwargs)

    return call


class _HandoffLock:
    """LLMServer's lock: whoever is already waiting for it is served
    before a thread that releases it and asks again.

    The step thread releases the server's lock between steps and takes
    it again at once. A plain `threading.Lock` gives it back to that
    thread nearly every time (the woken waiter has yet to run), and at
    pipeline depth 1 the step thread no longer blocks on the device
    inside a step either: a submitting or aborting thread could wait out
    many steps with decode slots standing empty. Every acquire first
    passes `_gate`, which a waiter holds while it blocks on `_lock`, so
    the step thread queues behind it. No sleep, no polling; uncontended
    it is one more lock operation a step. `threading.Condition` drives
    it through acquire, release and _is_owned."""

    __slots__ = ("_gate", "_lock")

    def __init__(self):
        self._gate = threading.Lock()
        self._lock = threading.Lock()

    def acquire(self) -> bool:
        with self._gate:
            # ray-tpu: lint-ignore[RTL202] this IS the lock's acquire:
            # release() below is its pair, and callers hold it by `with`
            return self._lock.acquire()

    def release(self) -> None:
        self._lock.release()

    def _is_owned(self) -> bool:
        # threading.Condition asks before notify; its default probes
        # with a non-blocking acquire, which here would queue at the gate.
        return self._lock.locked()

    __enter__ = acquire

    def __exit__(self, *exc) -> None:
        self.release()


class LLMServer:
    """Engine actor: a background step thread, and `generate` /
    `generate_stream` as coroutines of the actor's event loop.

    An async actor (`ray_tpu.remote(LLMServer).options(max_concurrency=N)`,
    N a bound on coroutines): concurrent calls are continuous-batched
    inside the one engine and none holds a thread, live or waiting for a
    lane. `generate_stream` is an async generator: call it with
    `.options(num_returns="streaming")` on the actor handle. The step
    thread hands a decode's tokens to the loop in one
    `call_soon_threadsafe` where their commit ends (`_flush`); every call
    that takes the server's lock leaves the loop for it (`_off_loop`). In
    process, call them from a coroutine
    (`asyncio.run(server.generate(...))`): a request is served on the loop
    it was submitted on.
    """

    def __init__(
        self,
        model_config: Optional[GPTConfig] = None,
        engine_config: Optional[EngineConfig] = None,
        params=None,
        seed: int = 0,
        warmup: bool = True,
        draft_params=None,
    ):
        self._engine = LLMEngine(
            model_config, engine_config, params=params, seed=seed,
            draft_params=draft_params,
        )
        if warmup:
            # Compile every prefill bucket and the decode program now, while
            # the actor is still initializing — a Serve deployment only
            # reports healthy afterwards, so cold-start compile never runs
            # under live traffic (nor under the controller's health probes).
            # Warmup generations are NOT real requests: suppress per-request
            # instrumentation so multi-second XLA compiles don't land in the
            # TTFT/e2e SLO histograms or the trace buffer (the flight
            # recorder's compile events capture warmup cost instead).
            # Speculation is suppressed too: the generate-based warmup
            # rounds must deterministically exercise every prefill/decode
            # bucket (an all-zeros prompt is maximally repetitive, so the
            # n-gram proposer would reroute them through verify); the
            # verify buckets get their own dedicated compile pass below.
            # The KV fabric is suppressed during warmup too, hooks and
            # all: warmup's zero-prompt rounds must exercise the FULL
            # prefill program per bucket, but a fabric warmed by an
            # earlier replica's warmup would satisfy them as restores
            # (partial prefill), silently skipping the compile — and the
            # publish/spill side would flood the shared store with
            # zero-block entries every replica start.
            # The pipeline is suppressed during warmup as well (depth
            # 0): the generate-based rounds must compile each bucket
            # program in a deterministic order with deterministic step
            # counts, and a chained decode dispatches the SAME compiled
            # program anyway (identical avals — a device token array and
            # a host one trace alike), so depth 1 needs no warmup pass
            # of its own but for the one program it adds, which sets a
            # joining prompt's lane on the device (`_warmup_join`).
            # What the program store makes on the way is written once this
            # server is ready, by a thread of its own (`shutdown` joins it):
            # a boot that finds no store does not write one before it serves.
            self._engine._program_store.hold()
            try:
                instrumented = self._engine._instrument
                spec = self._engine._spec
                publish = self._engine._publish_on_fill
                on_evict = self._engine.allocator.on_evict
                probe = self._engine.scheduler.fabric_probe
                depth = self._engine._pipeline_depth
                defers_chunks = self._engine._defers_chunks
                self._engine._instrument = False
                # ray-tpu: lint-ignore[RTL403] deliberate temporary clear —
                # the finally below restores _spec on every path, so no
                # exception can skip the consumer of the saved value
                self._engine._spec = None
                self._engine._publish_on_fill = False
                self._engine.allocator.on_evict = None
                self._engine.scheduler.fabric_probe = None
                self._engine._pipeline_depth = 0
                t_warmup = time.perf_counter()
                try:
                    self._warmup()
                    if defers_chunks:
                        self._warmup_join()
                finally:
                    self._engine._warmup_s = time.perf_counter() - t_warmup
                    self._engine._instrument = instrumented
                    self._engine._spec = spec
                    self._engine._publish_on_fill = publish
                    self._engine.allocator.on_evict = on_evict
                    self._engine.scheduler.fabric_probe = probe
                    self._engine._pipeline_depth = depth
                if spec is not None:
                    self._warmup_verify(spec)
            finally:
                self._engine._program_store.release()
        self._lock = _HandoffLock()
        self._work = threading.Condition(self._lock)
        self._requests: Dict[str, _RequestState] = {}
        # What was emitted since the last flush, a list an event loop (the
        # actor has one; in-process callers may each bring their own), and
        # the flushes made: `call_soon_threadsafe` calls.
        self._outbox: Dict[asyncio.AbstractEventLoop, List[tuple]] = {}
        self._handoffs = 0
        self._engine.on_commit = self._flush
        # Where the calls that take the lock wait for it (`_in_pool`): a
        # pool of the server's own, since the actor runtime's loop takes
        # its next call through the loop's default executor and must not
        # queue behind admissions that wait out a step.
        self._pool = ThreadPoolExecutor(
            max_workers=16, thread_name_prefix="llm-engine-call"
        )
        # The hand-over clocks of the requests that have left `_requests`.
        self._handoff_retired_s = 0.0
        self._handoff_retired_tokens = 0
        self._shutdown = False
        self._wedged = False
        self._consecutive_step_failures = 0
        self._thread = threading.Thread(
            target=self._loop, name="llm-engine-loop", daemon=True
        )
        self._thread.start()

    def _round_start(self) -> tuple:
        engine = self._engine
        clock = engine._compile_clock
        return (
            time.monotonic(), clock and clock.totals(),
            engine._program_store.totals(),
        )

    def _record_round(self, program: str, bucket: int, start: tuple) -> None:
        """One warm-up round into the flight record: its wall seconds,
        with `instrument` on what JAX spent of them tracing and lowering
        and in the compile step, and `loaded`: whether the programs it
        warmed were read from the program store (False where one was
        traced; None where the round made none, the store being off or the
        process having them already)."""
        t0, before, programs = start
        engine = self._engine
        programs = engine._program_store.since(programs)
        split = engine._compile_clock.since(before) if before else {}
        split["loaded"] = (
            False if programs["programs_traced"]
            else True if programs["programs_loaded"] else None
        )
        engine.flight_recorder.record_compile(
            program, bucket, time.monotonic() - t0, split
        )

    def _warmup(self) -> None:
        ecfg = self._engine.engine_config
        buckets = ecfg.buckets()
        # With a chunked-prefill budget, prompts never feed more than
        # bucket_for(budget) tokens per dispatch, so larger bucket
        # programs are UNREACHABLE — warming them would waste init time
        # and charge compile blame for programs live traffic can't run.
        # chunk_widths() is exactly the reachable set (== buckets() when
        # chunking is off).
        widths = ecfg.chunk_widths()
        for bucket in widths:
            # Prompt length landing in this bucket, shaped so the whole
            # request passes admission (lifetime within the largest
            # bucket and max_model_len). 2 tokens when room allows: the
            # second forces a decode step, compiling that program too.
            n = bucket if bucket < buckets[-1] else bucket - 1
            n = min(n, ecfg.max_model_len - 1)
            budget = min(2, ecfg.max_model_len - n)
            if n < 1:
                continue
            # Each round must exercise the FULL prefill program: drop
            # the previous round's cached zero-blocks, or this prompt
            # would hit them and take the partial-prefill path, leaving
            # this bucket's full program uncompiled.
            self._engine.allocator.reset_prefix_cache()
            round_start = self._round_start()
            try:
                self._engine.generate([[0] * n], max_new_tokens=budget)
            except ValueError:
                # Bucket unwarmable under this config (e.g. the block
                # pool is smaller than the bucket); requests that large
                # are rejected at admission anyway.
                continue
            # Cold-compile blame: almost all of this round is XLA
            # compiling the bucket's full-prefill program (plus, on the
            # first round, the decode program).
            self._record_round("prefill", bucket, round_start)
        if self._engine.allocator.enable_prefix_caching:
            # Also compile every partial-prefill bucket and the
            # copy-on-write block copy, so cache hits never trigger a
            # cold compile under live traffic. Each round seeds exactly
            # one cached block of zeros, then prefills a zero-prompt
            # whose uncached suffix lands in the target bucket; the
            # duplicate-prompt round at the end exercises the
            # fully-cached path (CoW + smallest suffix bucket).
            alloc = self._engine.allocator
            bs = ecfg.block_size
            for bucket in widths + (0,):
                alloc.reset_prefix_cache()
                n = min(bs + bucket, ecfg.max_model_len - 1, buckets[-1])
                round_start = self._round_start()
                try:
                    self._engine.generate([[0] * bs], max_new_tokens=1)
                    if n > bs:
                        self._engine.generate([[0] * n], max_new_tokens=1)
                    else:  # CoW round: repeat the fully-cached prompt
                        self._engine.generate([[0] * bs], max_new_tokens=1)
                except ValueError:
                    continue
                self._record_round(
                    "cow" if n <= bs else "partial_prefill",
                    0 if n <= bs else bucket,
                    round_start,
                )
            alloc.reset_prefix_cache()
        if ecfg.prefill_token_budget is not None:
            # Chunked prefill dispatches BOTH prefill program families at
            # every reachable width: the full program for a cold first
            # chunk, the partial program for every continuation chunk
            # (and, with prefix caching off, the generate rounds above
            # never compiled the partial family at all). Compile each
            # (width × program) pair directly against the null block —
            # writes land in block 0, no allocator state is touched, and
            # already-compiled pairs are cache hits — so no chunk can
            # cold-compile under live traffic.
            runner = self._engine.runner
            null_table = [0] * ecfg.max_blocks_per_seq
            # A model with recurrent layers: into state slot 0, which no
            # sequence holds here and none reads before writing.
            slot = (0,) if self._engine._recurrent else ()
            # A model with sliding-window layers: the window class's null
            # block too.
            extra = (
                {"window_ids": [0]}
                if self._engine._window is not None
                else {}
            )
            for w in widths:
                round_start = self._round_start()
                runner.prefill([0] * w, [0], *slot, **extra)
                runner.prefill_suffix([0] * w, null_table, 0, *slot, **extra)
                self._record_round("chunk_prefill", w, round_start)

    def _warmup_join(self) -> None:
        """The program that sets a joining prompt's lane of a decode's
        token input on the device (`runner.join_token`; depth 1 alone),
        on the host's buffer and on a decode's output: against the null
        block and state slot 0, as the chunk programs above."""
        engine = self._engine
        ecfg, runner = engine.engine_config, engine.runner
        slot = (0,) if engine._recurrent else ()
        windowed = engine._window is not None
        round_start = self._round_start()
        out = runner.prefill(
            [0] * ecfg.chunk_widths()[0], [0], *slot,
            **({"window_ids": [0]} if windowed else {}),
        )
        lanes = np.zeros((ecfg.max_decode_slots,), np.int32)
        tables = np.zeros(
            (ecfg.max_decode_slots, ecfg.max_blocks_per_seq), np.int32
        )
        decoded = runner.decode(
            runner.join_token(lanes, 0, out), lanes, tables, lanes,
            **({"window_tables": tables} if windowed else {}),
        )
        runner.join_token(decoded, 0, out)
        self._record_round("join_token", 0, round_start)

    def _warmup_verify(self, spec) -> None:
        """Compile every k-token verify bucket program plus whatever the
        proposer owns (the draft model's prefill/decode programs), so the
        first speculative step under live traffic never cold-compiles.
        The synthetic verify calls run against all-null block tables:
        writes land in the null block (the masked-lane convention) and
        touch no allocator state."""
        ecfg = self._engine.engine_config
        runner = self._engine.runner
        slots = ecfg.max_decode_slots
        nb = ecfg.max_blocks_per_seq
        for s_bucket in ecfg.verify_buckets():
            round_start = self._round_start()
            runner.verify(
                np.zeros((slots, s_bucket), np.int32),
                np.zeros((slots, nb), np.int32),
                np.zeros((slots,), np.int32),
                np.full((slots,), s_bucket, np.int32),
            )
            self._record_round("verify", s_bucket, round_start)
        round_start = self._round_start()
        spec.warmup()
        self._record_round(f"proposer:{spec.name}", 0, round_start)

    # ---------------- engine loop ----------------

    def _loop(self) -> None:
        max_failures = self._engine.engine_config.max_consecutive_step_failures
        while True:
            with self._work:
                while not self._shutdown and not self._engine.has_work():
                    self._work.wait()
                if self._shutdown:
                    return
            # Step outside the condition wait but under the lock: the engine
            # is single-threaded and submissions mutate scheduler state.
            with self._lock:
                try:
                    self._engine.step()
                    self._consecutive_step_failures = 0
                    continue
                except BaseException as exc:
                    self._consecutive_step_failures += 1
                    # Attribution comes FIRST: an isolatable poison request
                    # must be dead-lettered even when the consecutive-
                    # failure counter is at the threshold (otherwise
                    # max_consecutive_step_failures=1 would disable
                    # isolation entirely).
                    culprit = self._engine.culprit_for(exc)
                    recorder = self._engine.flight_recorder
                    # A commit-time failure is attributed to the step
                    # that dispatched the program: failure_step() resolves
                    # to the in-flight record's DISPATCH index (one step
                    # back at pipeline depth 1, this step at depth 0).
                    step_idx = self._engine.failure_step()
                    if culprit is not None:
                        # Poison-request isolation: fail only the culpable
                        # request (dead-letter + KV release) and keep
                        # stepping for everyone else. The waiter's error is
                        # set BEFORE fail_request fires its finish callback
                        # so the caller never sees a clean finish.
                        state = self._requests.get(culprit)
                        if state is not None and not state.finished:
                            state.error = PoisonRequestError(
                                request_id=culprit, cause=exc
                            )
                        if self._engine.fail_request(culprit, exc):
                            # Contained: the culprit is out of the batch, so
                            # the engine is making progress — only steps
                            # that fail WITHOUT an isolatable culprit count
                            # toward the wedge threshold (a stream of poison
                            # requests must not take down the replica).
                            self._consecutive_step_failures = 0
                        recorder.record_failure(
                            step_idx, repr(exc), request_id=culprit,
                            action="dead_letter",
                        )
                        continue
                    if self._consecutive_step_failures < max_failures:
                        recorder.record_failure(
                            step_idx, repr(exc), action="retry"
                        )
                        # Unattributable failure (e.g. the batched decode
                        # program itself): per-sequence state only mutates
                        # after the risky calls return, so retrying the
                        # step is safe. A deterministic failure trips the
                        # consecutive-failures threshold and wedges below.
                        continue
                    # Wedged: broadcast to every waiter while still holding
                    # the lock so no submission can slip in between the
                    # error broadcast and the thread actually dying; the
                    # Serve controller's next health probe then replaces
                    # the replica.
                    recorder.record_failure(
                        step_idx, repr(exc), action="wedged"
                    )
                    self._wedged = True
                    self._shutdown = True
                    self._engine.close_traces(exc)
                    self._end_all(exc)
                    import traceback

                    traceback.print_exc()
                    return
                finally:
                    # A decode's tokens left where its commit ended
                    # (`on_commit`); what the step emitted since (a
                    # prompt's first token, the ends of streams, errors)
                    # leaves here.
                    self._flush()

    def _end_all(self, exc: BaseException) -> None:
        """The wedge's and the shutdown's broadcast: every live request
        ends with `exc`, behind the tokens it was already given. Caller
        holds the lock and flushes."""
        for state in self._requests.values():
            if not state.finished:
                state.error = exc
                state.finish()

    def _emit(self, state: _RequestState, item, stamp: float) -> None:
        """`_RequestState.emit`. Caller holds the lock."""
        batch = self._outbox.get(state.loop)
        if batch is None:
            batch = self._outbox[state.loop] = []
        batch.append((state, item, stamp))

    def _flush(self) -> None:
        """Everything emitted since the last flush goes to its event loop
        in one `call_soon_threadsafe` (one a loop: the actor has exactly
        one), so a decode's commit wakes one thread however many streams
        it fed. Caller holds the lock, and flushes before releasing it
        whatever it did that may have ended a request (a step, an abort,
        the broadcasts)."""
        if not self._outbox:
            return
        outbox, self._outbox = self._outbox, {}
        for loop, batch in outbox.items():
            try:
                loop.call_soon_threadsafe(_deliver, batch)
            except RuntimeError:
                # The loop is closed: the actor died under its step
                # thread, and nobody is left to serve. Drop the batch and
                # stop stepping.
                self._shutdown = True
                continue
            self._handoffs += 1

    def _in_pool(self, fn: Callable, *args, **kwargs):
        """Awaitable: `fn` on the server's pool, in the caller's context
        (the trace parent `add_request` captures is the calling
        coroutine's, not whatever the pool's thread ran last)."""
        call = functools.partial(
            contextvars.copy_context().run, fn, *args, **kwargs
        )
        return asyncio.get_running_loop().run_in_executor(self._pool, call)

    def _submit(
        self,
        state: _RequestState,
        prompt_ids: List[int],
        max_new_tokens: Optional[int],
        eos_id: Optional[int],
        request_id: Optional[str],
        deadline_s: Optional[float] = None,
    ) -> str:
        with self._work:
            if self._shutdown or not self._thread.is_alive():
                raise RuntimeError(
                    "LLM engine loop is not running (shut down or crashed); "
                    "restart the engine actor"
                )
            if request_id is not None and request_id in self._requests:
                raise ValueError(
                    f"request_id {request_id!r} already has an in-flight "
                    "generation on this server"
                )
            # Bounded admission fails fast HERE: add_request raises a
            # typed, retryable EngineOverloadedError before any state
            # lands in _requests — the caller (and through it the Serve
            # router) sees the shed in one lock acquisition, never after
            # queueing.
            rid = self._engine.add_request(
                prompt_ids,
                max_new_tokens=max_new_tokens,
                eos_id=eos_id,
                request_id=request_id,
                on_token=state.offer,
                on_finish=state.finish,
                deadline_s=deadline_s,
            )
            self._requests[rid] = state
            trace = self._engine._req_traces.get(rid)
            if trace is not None:
                trace.egress = state
            self._work.notify_all()
        return rid

    async def _admit(
        self,
        per_token: bool,
        prompt_ids: List[int],
        max_new_tokens: Optional[int],
        eos_id: Optional[int],
        request_id: Optional[str],
        timeout_s: Optional[float],
    ) -> tuple:
        """Submit a request from the running loop: its id, its state and
        its deadline's timer (None without a deadline), which `_release`
        takes back."""
        loop = asyncio.get_running_loop()
        # The commit's stamp rides `instrument`, as the step clock does.
        state = _RequestState(
            loop, self._emit, per_token, stamped=self._engine._instrument
        )
        deadline = (
            time.monotonic() + timeout_s if timeout_s is not None else None
        )
        rid = await self._in_pool(
            self._submit, state, prompt_ids, max_new_tokens, eos_id,
            request_id, deadline,
        )
        expiry = (
            None if timeout_s is None
            else loop.call_later(timeout_s, state.expire)
        )
        return rid, state, expiry

    async def _release(self, rid: str, expiry) -> None:
        """A request's coroutine is done with it, however it ended."""
        if expiry is not None:
            expiry.cancel()
        await self._in_pool(self._retire, rid)

    # ---------------- public API ----------------

    async def generate(
        self,
        prompt_ids: List[int],
        max_new_tokens: Optional[int] = None,
        eos_id: Optional[int] = None,
        request_id: Optional[str] = None,
        timeout_s: float = 120.0,
    ) -> dict:
        """The whole generation. `timeout_s` is the request's END-TO-END
        deadline: it bounds this call's wait AND rides into the engine as
        an absolute monotonic deadline, so a request that cannot finish in
        time is dropped from the queue before its prefill ever runs (or
        aborted mid-decode with its blocks reclaimed) instead of decoding
        for a caller that already gave up. Either side tripping first
        raises TimeoutError."""
        rid, state, expiry = await self._admit(
            False, prompt_ids, max_new_tokens, eos_id, request_id, timeout_s
        )
        try:
            if not state.ended:
                await state.wait()
            if not state.ended:
                # The request may have finished in the instant between the
                # timer firing and the abort landing; only a successful
                # abort (it was still queued/running) is a real timeout —
                # otherwise fall through and deliver the completed result.
                if await self.abort(rid) or not state.ended:
                    raise TimeoutError(
                        f"generation {rid} timed out after {timeout_s}s"
                    )
            if state.error is not None:
                raise state.error
            if (
                state.seq is not None
                and state.seq.finish_reason == FINISH_EXPIRED
            ):
                # The ENGINE enforced the deadline (queued expiry or
                # mid-decode abort) before this call's own timer fired:
                # same contract, same error.
                raise TimeoutError(
                    f"generation {rid} exceeded its {timeout_s}s deadline"
                )
            token_ids = [
                state.take(entry)
                for entry in state.items
                if entry[0] is not _STREAM_END
            ]
            return {
                "request_id": rid,
                "token_ids": token_ids,
                "finish_reason": state.seq.finish_reason if state.seq else None,
                "num_preemptions": state.seq.num_preemptions if state.seq else 0,
            }
        finally:
            await self._release(rid, expiry)

    async def generate_stream(
        self,
        prompt_ids: List[int],
        max_new_tokens: Optional[int] = None,
        eos_id: Optional[int] = None,
        request_id: Optional[str] = None,
        timeout_s: float = 120.0,
        stream_idle_timeout_s: Optional[float] = None,
    ):
        """Yields token ids as the engine produces them.

        `timeout_s` is the END-TO-END deadline — the same meaning as the
        blocking path (it previously meant the per-token gap here; that
        drift is exactly what `stream_idle_timeout_s` now carries). The
        deadline rides into the engine, so an expiring stream is aborted
        with its blocks reclaimed and this generator raises TimeoutError
        after yielding whatever was already emitted.
        `stream_idle_timeout_s` (optional) additionally bounds the gap
        between consecutive tokens — the old `timeout_s` semantics for
        callers that want a liveness check tighter than the deadline."""
        rid, state, expiry = await self._admit(
            True, prompt_ids, max_new_tokens, eos_id, request_id, timeout_s
        )
        items = state.items
        try:
            while True:
                if not items:
                    await state.wait(stream_idle_timeout_s)
                    if not items:
                        # A timer woke it: the deadline's or the gap's.
                        await self.abort(rid)
                        if state.late:
                            raise TimeoutError(
                                f"generation {rid} exceeded its {timeout_s}s "
                                "deadline"
                            )
                        raise TimeoutError(
                            f"generation {rid} produced no token for "
                            f"{stream_idle_timeout_s}s"
                        )
                entry = items.popleft()
                if entry[0] is _STREAM_END:
                    break
                yield state.take(entry)
            if state.error is not None:
                raise state.error
            if (
                state.seq is not None
                and state.seq.finish_reason == FINISH_EXPIRED
            ):
                raise TimeoutError(
                    f"generation {rid} exceeded its {timeout_s}s deadline"
                )
        finally:
            # Closed before exhaustion (consumer disconnected / stream
            # cancelled → GeneratorExit at the yield): the request is still
            # occupying KV blocks (and, with speculation=draft, mirror
            # blocks) to generate tokens nobody will read — `_retire`'s
            # abort returns the pool to steady state now. A finished
            # request is no longer active, so the abort is a no-op on the
            # normal path.
            await self._release(rid, expiry)

    def _retire(self, rid: str) -> None:
        """Drop a request's state, its hand-over clock kept, and abort
        what is left of it in the engine."""
        with self._lock:
            state = self._requests.pop(rid, None)
            if state is not None:
                self._handoff_retired_s += state.handoff_s
                self._handoff_retired_tokens += state.handoff_tokens
                # Its end has no reader any more.
                state.finished = True
            self._engine.abort(rid)

    @_off_loop
    def abort(self, request_id: str) -> bool:
        with self._lock:
            try:
                return self._engine.abort(request_id)
            finally:
                self._flush()

    @_off_loop
    def metrics(self) -> dict:
        delivery = self._stream_delivery()
        with self._lock:
            return self._server_stats(delivery)

    @staticmethod
    def _stream_delivery() -> dict:
        """`Runtime.stream_delivery()` of the hosting runtime; no group in
        a worker process, whose runtime proxy registers no stream. Read
        before the server's lock is taken: while a snapshot holds it the
        step thread stands still and the streams drain, so a backlog read
        under it is the backlog's floor."""
        from ray_tpu._private import runtime as runtime_mod

        return getattr(runtime_mod._RUNTIME, "stream_delivery", dict)()

    def _server_stats(self, groups: dict) -> dict:
        """The engine's stats and what only its server knows; `groups` is
        `_stream_delivery()`. Caller holds the lock. The two stream counts
        are the hosting runtime's, of every streaming generator in the
        process (`Runtime.report_stream_item`): how many items were
        reported, and how many travelled with their refs and not through
        the object store.

        The way out, hand-over by hand-over (totals, flat, for a window's
        difference): `egress_handoff_*`, commit -> the event loop on which
        `generate` or `generate_stream` runs, over live and retired
        requests (0 with `instrument` off), and `egress_handoffs`, the
        `call_soon_threadsafe` calls that carried them (tokens over
        hand-overs is the live lanes where a step feeds many streams);
        `engine_stream_*`, this class's
        `generate_stream` items from that loop to whoever iterates the
        stream (under Serve, the replica's thread), and `stream_*`, the
        same over every streaming generator of the process (under Serve:
        that hop and replica -> proxy), both from
        `Runtime.stream_delivery`; `egress_backlog_tokens`, a gauge: tokens
        committed and not yet taken by the last consumer in this process,
        wherever they wait (request queues and every live stream)."""
        from ray_tpu._private import runtime as runtime_mod

        stats = self._engine.stats()
        stats["wedged"] = self._wedged
        stats["consecutive_step_failures"] = self._consecutive_step_failures
        runtime = runtime_mod._RUNTIME
        for key in ("stream_items_reported", "stream_items_inline"):
            # 0 in a worker process or with no runtime: nothing is inline there.
            stats[key] = getattr(runtime, key, 0)
        handoff_s = self._handoff_retired_s
        handoff_tokens = self._handoff_retired_tokens
        backlog = 0
        for state in self._requests.values():
            handoff_s += state.handoff_s
            handoff_tokens += state.handoff_tokens
            backlog += state.offered - state.handoff_tokens
        stats["egress_handoff_s"] = handoff_s
        stats["egress_handoff_tokens"] = handoff_tokens
        stats["egress_handoffs"] = self._handoffs
        own = groups.get(f"{type(self).__name__}.generate_stream", {})
        stats["engine_stream_wait_s"] = own.get("wait_s", 0.0)
        stats["engine_stream_items_taken"] = own.get("items_taken", 0)
        stats["stream_wait_s"] = sum(g["wait_s"] for g in groups.values())
        stats["stream_items_taken"] = sum(
            g["items_taken"] for g in groups.values()
        )
        stats["egress_backlog_tokens"] = backlog + sum(
            g["items_offered"] - g["items_taken"] - g["items_dropped"]
            for g in groups.values()
        )
        return stats

    @_off_loop
    def autoscaling_snapshot(self) -> dict:
        """Compact SLO signal bundle for the serve controller's
        LLMAutoscalingPolicy: the engine's queue-time and TTFT histogram
        series (snapshotted engine-side so the numbers are correct even
        when the engine actor runs out-of-process from the controller)
        plus the prefill backlog and load counts. The controller diffs
        two snapshots to get a look-back window — scale-up triggers on
        RECENT p99, not the engine's lifetime percentile."""
        with self._lock:
            e = self._engine
            return {
                "engine_id": e._metric_tags["engine"],
                "queue_depth": len(e.scheduler.waiting),
                "num_running": len(e.scheduler.running),
                # Decode occupancy bound: num_running at max_decode_slots
                # means the engine is decode-SATURATED even when the
                # admission-time histograms are silent (long generations,
                # no new arrivals) — the policy must not read that
                # silence as idleness and scale the fleet down.
                "max_decode_slots": e.engine_config.max_decode_slots,
                "prefill_backlog_tokens": int(
                    e.scheduler.prefill_backlog_tokens()
                ),
                "queue_time": e._h_queue.snapshot(e._metric_tags),
                "ttft": e._h_ttft.snapshot(e._metric_tags),
            }

    @_off_loop
    def dead_letters(self) -> List[dict]:
        """Records of requests failed in isolation after poisoning an
        engine step (id, prompt hash, error, step), oldest first."""
        with self._lock:
            return self._engine.dead_letters()

    @_off_loop
    def shed_requests(self) -> List[dict]:
        """Records of submissions rejected by bounded admission or dead
        on arrival (id, reason, queue depth, retry-after hint), oldest
        first — the overload plane's dead_letters()."""
        with self._lock:
            return self._engine.shed_requests()

    @_off_loop
    def flight_record(self, steps_limit: Optional[int] = None) -> dict:
        """The engine flight recorder: bounded rings of per-step records
        (phase, batch size, tokens, buckets, cache hits, preemptions,
        duration), warmup compile events (cold-compile blame), and step
        failures with the action taken (dead_letter / retry / wedged)."""
        with self._lock:
            return self._engine.flight_recorder.snapshot(steps_limit)

    @_off_loop
    def observability_snapshot(
        self, steps_limit: Optional[int] = None
    ) -> dict:
        """metrics + dead letters + flight recorder in ONE actor round trip
        (the dashboard /api/llm panel polls this; three separate RPCs per
        engine per refresh would triple the scrape's exposure to a busy
        engine's lock)."""
        delivery = self._stream_delivery()
        with self._lock:
            e = self._engine
            return {
                "metrics": self._server_stats(delivery),
                "dead_letters": e.dead_letters(),
                "shed_requests": e.shed_requests(),
                "flight_record": e.flight_recorder.snapshot(steps_limit),
                # Engine-side histogram snapshots for cross-replica
                # aggregation (util.metrics.merge_snapshots): snapshotted
                # here so the numbers are correct even when the engine
                # actor runs out-of-process from the collector.
                "histograms": {
                    "llm_request_ttft_seconds": e._h_ttft.snapshot(
                        e._metric_tags
                    ),
                    "llm_request_time_per_output_token_seconds": (
                        e._h_tpot.snapshot(e._metric_tags)
                    ),
                    "llm_request_queue_time_seconds": e._h_queue.snapshot(
                        e._metric_tags
                    ),
                    "llm_request_e2e_seconds": e._h_e2e.snapshot(
                        e._metric_tags
                    ),
                },
            }

    @_off_loop
    def device_report(self) -> dict:
        """Bytes of weights and KV pools per device, and the compiled
        decode program's memory, kernels and collectives
        (GPTRunner.device_report). Holds the engine lock while the
        program compiles: a diagnostic, not a scrape."""
        with self._lock:
            return self._engine.runner.device_report()

    @_off_loop
    def reset_prefix_cache(self) -> None:
        """Drop all cached-but-unreferenced KV blocks (e.g. after swapping
        the served params, whose cached activations would be stale)."""
        with self._lock:
            self._engine.allocator.reset_prefix_cache()

    @_off_loop
    def flush_kv_fabric(self) -> int:
        """Demote the engine's cached-but-unreferenced KV blocks into the
        fabric (the drain path's cache preservation — called by the
        ingress replica's shutdown before the engine actor dies); returns
        blocks resident in the fabric afterwards, 0 without a fabric."""
        with self._lock:
            return self._engine.flush_kv_fabric()

    @_off_loop
    def num_pending(self) -> int:
        with self._lock:
            return len(self._engine.scheduler.waiting) + len(
                self._engine.scheduler.running
            )

    def check_health(self) -> bool:
        # ray-tpu: lint-ignore[RTL201] atomic bool read; taking the engine
        # lock here would park the health probe behind a full step (or a
        # bucket compile) and make the controller churn healthy replicas
        return self._thread.is_alive() and not self._wedged

    @_off_loop
    def shutdown(self) -> None:
        with self._work:
            # Preserve the prefix cache across the actor's death: flush the
            # evictable keyed blocks into the fabric (no-op without one)
            # before the step loop stops. Best effort — shutdown proceeds
            # regardless.
            try:
                self._engine.flush_kv_fabric()
            except Exception:
                pass
            self._shutdown = True
            # Fail in-flight requests promptly instead of leaving their
            # callers to run out their full wait timeout.
            exc = RuntimeError("LLM engine shut down with requests in flight")
            if self._requests:
                self._engine.close_traces(exc)
            self._end_all(exc)
            self._flush()
            self._work.notify_all()
        self._thread.join(timeout=10.0)
        self._engine._program_store.join(timeout=10.0)
