"""Jitted step programs for a model whose layers declare a kind and the
state each keeps: a recurrent kind (arrays a sequence, which the model
declares: `cache.RecurrentKind`), `attention` / `full_attention` (K and V
of every position, paged) and `sliding_attention` (K and V of the last
`horizon` positions, paged in a cache class of its own). The model is a
module of pure functions over a plain tree that its configuration names
(`llm_model`: `ray_tpu.models.granite_hybrid`, `ray_tpu.models.laguna`,
`ray_tpu.models.olmo_hybrid`, `ray_tpu.models.falcon_h1`); what this
runner reads of it is `init_params`, `embed`, `head`, `run_layers`,
`attention_qkv` / `attention_out`, `ATTENTION_SCOPES`, and where it has
them `recurrent_kinds` / `recurrent_shape`, `expert_shape` and
`layer_mixers`, and of its configuration `layer_types`, `cache_classes` /
`cache_class_of`, `heads_of`, `attention_scale`, `num_key_value_heads`,
`head_dim`.

A layer kind holds one mixer, itself, unless the model says otherwise
(`layer_mixers(cfg) -> {kind: (mixer, ...)}`: a Falcon-H1 layer runs a
recurrent mixer and full attention on the same normed input and adds
them). What keeps memory is the mixer: a recurrent mixer has a state pool
for every layer that holds it, a cached one a layer of its cache class's
pools, both counted over the layers that hold the mixer, and `run_layers`
is handed one function a mixer, `mixers[mixer](i, p, u)` for the i-th
layer that holds it. A layer with two mixers writes its state pool and its
K/V blocks in the one program, at the same layer index.

The same three program shapes `model_runner` compiles, under the same
names: one decode program over all decode lanes, and for every prefill
bucket a program that starts a sequence (`_prefill_step`: empty state, no
cached context) and one that continues it (`_prefill_suffix_step`: the
chunk starts from the slot's state and attends the cached context through
the block tables). A cache class has one K and one V pool over its
attention layers ([layers of the class, N of the class, bs, kv heads * head
size]) and a block table a sequence; every recurrent layer has a pool
[slots, *shape] for each array its kind declares (granite's Mamba-2: a
float32 state and a convolution tail), one array a layer. All are donated
through every step. A layer
of a class with a horizon attends through `paged_attention_impl(...,
window=horizon)`: the entries of its table below the window are null and
never read.

On a model with recurrent layers a sequence owns one state slot for as
long as it runs (the scheduler hands them out), and its slot is its lane
in the decode batch: the decode program updates the state pools in place,
lane for lane, and the lanes that do not decode this step (context length
0: idle, or a sequence still prefilling) stay as they were: a kind's
`decode` is handed which lanes are live and returns the pools' arrays, and
the runner selects nothing over them. Nothing gathers or scatters a state.
A slot is never cleared: the program that starts a sequence does not read
it.

The decode program of a model with routed experts (one that publishes
`expert_shape`) returns the sampled tokens and the step's routing counts in
one int32 vector, so the engine reads both in the one fetch it makes
anyway, one step behind at pipeline depth 1; its token input has the same
length so that a step can be chained on the last one's output. A dense
model's returns the tokens alone and has no routing counters.
"""

from __future__ import annotations

import functools
import importlib
import threading
from typing import Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu._private.jax_setup import ensure_compile_cache
from ray_tpu.llm import program_store
from ray_tpu.llm.cache import kv_pool_bytes_sharded
from ray_tpu.llm.config import EngineConfig
from ray_tpu.llm.model_runner import (
    bytes_by_device,
    decode_tiling,
    join_token,
    prefill_tiling,
    start_host_copy,
)
from ray_tpu.ops.paged_flash import paged_attention_impl, resolve_paged_impl
from ray_tpu.util.device_report import scopes_of  # noqa: F401  (also the name tests know it by)

# The routing counts a decode step appends to its tokens, in this order.
DECODE_COUNTS = ("held", "absent", "touched", "load_max")


def model_of(cfg):
    """The module of pure functions `cfg` names (`llm_model`)."""
    return importlib.import_module(type(cfg).llm_model)


def recurrent_kinds(cfg) -> dict:
    """kind -> `cache.RecurrentKind` of the recurrent kinds the model of
    `cfg` declares ({}: every layer keeps its memory in the paged cache)."""
    declare = getattr(model_of(cfg), "recurrent_kinds", None)
    return {} if declare is None else declare(cfg)


def layer_mixers(cfg) -> dict:
    """layer kind -> the mixers a layer of that kind holds, for the kinds
    in `cfg.layer_types`: what the model declares (`layer_mixers`), else
    the kind itself."""
    declare = getattr(model_of(cfg), "layer_mixers", None)
    declared = {} if declare is None else declare(cfg)
    return {
        kind: tuple(declared.get(kind, (kind,)))
        for kind in dict.fromkeys(cfg.layer_types)
    }


def layers_holding(cfg) -> dict:
    """mixer -> how many of the model's layers hold it, every mixer of
    every kind, in the order the kinds first appear."""
    held = layer_mixers(cfg)
    count: dict = {}
    for kind in cfg.layer_types:
        for mixer in held[kind]:
            count[mixer] = count.get(mixer, 0) + 1
    return count


def state_layout(cfg) -> list:
    """The state pools of `cfg`'s model, in the order the programs take
    them: (mixer, layers that hold it, (name, shape, dtype)) an array a
    recurrent kind declares."""
    holding = layers_holding(cfg)
    return [
        (mixer, holding[mixer], array)
        for mixer, spec in recurrent_kinds(cfg).items() for array in spec.arrays
    ]


def visible_pairs(offset: int, tokens: int, horizon: int) -> int:
    """(query, key) pairs a chunk of `tokens` queries at positions offset ..
    sees in one layer of a class with `horizon`: the query at position p
    sees min(p + 1, horizon) keys."""
    return sum(min(p + 1, horizon) for p in range(offset, offset + tokens))


class _HybridPrograms:
    """The jitted programs of one (model, block size, attention impl);
    `jit` as `model_runner._StepPrograms`'."""

    def __init__(self, cfg, block_size: int, attn_impl: str, jit=jax.jit):
        self.cfg = cfg
        self.model = model_of(cfg)
        self.block_size = block_size
        self.attn_impl = attn_impl
        self.routed = hasattr(self.model, "expert_shape")
        self.recurrent = recurrent_kinds(cfg)
        # Where in the state pools a recurrent mixer's arrays are
        # (`state_layout`).
        kinds = [kind for kind, _, _ in state_layout(cfg)]
        self.state_at = {
            kind: [j for j, k in enumerate(kinds) if k == kind]
            for kind in self.recurrent
        }
        donated = (1, 2, 3)
        self.decode_fn = jit(self._decode_step, donate_argnums=donated)
        self.prefill_fn = jit(self._prefill_step, donate_argnums=donated)
        self.prefill_suffix_fn = jit(
            self._prefill_suffix_step, donate_argnums=donated
        )
        self.join_token_fn = jit(join_token)

    def _sample(self, logits):
        """Greedy, over the last axis. The one place a program's logits
        become tokens: tests observe them here."""
        return jnp.argmax(logits, axis=-1)

    def _attend(self, kind, p, u, positions, k_cache, v_cache, tables, lens,
                layer, new):
        """q of u (tokens at `positions`) against the cached context of the
        kind's cache class, as far as the class's horizon lets it see, and
        the new tokens' own K/V (kept in `new` for the scatter). u
        [B, S, D], positions [B, S]; `kind` is the mixer and `layer` counts
        the layers that hold it, which are the class's."""
        cfg, model = self.cfg, self.model
        cls = cfg.cache_class_of(kind)
        horizon = cfg.cache_classes[cls].horizon
        projections, attention = model.ATTENTION_SCOPES[kind]
        with jax.named_scope(projections):
            q, k, v = model.attention_qkv(cfg, kind, p, u, positions)
            new[cls, layer] = (k, v)
        with jax.named_scope(attention):
            out = paged_attention_impl(
                q, k_cache[cls], v_cache[cls], tables[cls], lens, new_k=k,
                new_v=v, layer=layer, sm_scale=cfg.attention_scale,
                impl=self.attn_impl,
                **({} if horizon is None else {"window": horizon}),
            )
        with jax.named_scope(projections):
            return model.attention_out(cfg, kind, p, u, out)

    def _mixers(self, recur, attend) -> dict:
        """`run_layers`' mixers: one function for every mixer a layer of
        the model holds, the recurrent path or the cached one."""
        return {
            mixer: functools.partial(recur if mixer in self.recurrent else attend, mixer)
            for mixer in layers_holding(self.cfg)
        }

    def _decode_step(
        self, params, k_cache, v_cache, state, tokens, positions,
        block_tables, context_lens,
    ):
        """One token for every lane that decodes. tokens [B + counts] (the
        last step's output or the host's; the first B are read), the rest
        [B] / a [B, nb] table a cache class -> (pools, [B tokens, counts]).
        k_cache and v_cache are a pool a cache class, state a pool a layer
        for each array of `state_layout`."""
        cfg, model = self.cfg, self.model
        k_cache, v_cache = list(k_cache), list(v_cache)
        b = positions.shape[0]
        live = context_lens > 0
        state = [list(pools) for pools in state]
        new: dict = {}

        def recur(kind, i, p, u):
            spec, at = self.recurrent[kind], self.state_at[kind]
            out, *after = spec.decode(cfg, p, u, *(state[j][i] for j in at), live)
            for j, array in zip(at, after):
                state[j][i] = array
            return out

        def attend(kind, i, p, u):
            return self._attend(
                kind, p, u[:, None], positions[:, None], k_cache, v_cache,
                block_tables, context_lens, i, new,
            )[:, 0]

        h, counts = model.run_layers(
            cfg, params, model.embed(cfg, params, tokens[:b]),
            self._mixers(recur, attend), grouped=False, valid=live,
        )
        # Each lane's new K/V at its own position; an idle lane's table is
        # all null, so it lands in block 0.
        block_ids = [
            table[jnp.arange(b), positions // self.block_size]
            for table in block_tables
        ]
        offsets = positions % self.block_size
        for (cls, layer), (k, v) in new.items():
            at = (layer, block_ids[cls], offsets)
            k_cache[cls] = k_cache[cls].at[at].set(k.reshape(b, -1))
            v_cache[cls] = v_cache[cls].at[at].set(v.reshape(b, -1))
        out = self._sample(model.head(cfg, params, h)).astype(jnp.int32)
        if self.routed:
            out = jnp.concatenate([
                out, jnp.stack([counts[k] for k in DECODE_COUNTS]).astype(jnp.int32),
            ])
        return (tuple(k_cache), tuple(v_cache), tuple(map(tuple, state))), out

    def _chunk(
        self, params, k_cache, v_cache, state, tokens, block_table,
        offset, true_len, slot, fresh: bool,
    ):
        """tokens [1, S_bucket] (0-padded past true_len) of the sequence in
        state slot `slot`, at positions offset.. -> (pools, [next token,
        and of a model with routed experts the held assignments and the
        sorted rows the grouped experts walked for them]). block_table
        holds a [nb] table a cache class."""
        cfg, model = self.cfg, self.model
        k_cache, v_cache = list(k_cache), list(v_cache)
        sb = tokens.shape[1]
        lane = jnp.arange(sb)
        valid = lane < true_len
        positions = jnp.where(valid, offset + lane, 0)
        state = [list(pools) for pools in state]
        new: dict = {}

        def recur(kind, i, p, u):
            spec, at = self.recurrent[kind], self.state_at[kind]
            if fresh:
                before = [jnp.zeros_like(state[j][i][0]) for j in at]
            else:
                before = [state[j][i][slot] for j in at]
            out, *after = spec.prefill(cfg, p, u, *before, true_len)
            with jax.named_scope(spec.scan_scope):
                for j, array in zip(at, after):
                    pool = state[j][i]
                    state[j][i] = pool.at[slot].set(array.astype(pool.dtype))
            return out

        def attend(kind, i, p, u):
            return self._attend(
                kind, p, u[None], positions[None], k_cache, v_cache,
                [table[None, :] for table in block_table],
                jnp.reshape(offset, (1,)), i, new,
            )[0]

        h, counts = model.run_layers(
            cfg, params, model.embed(cfg, params, tokens[0]),
            self._mixers(recur, attend), grouped=True, valid=valid,
        )
        bs = self.block_size
        block_ids = [
            jnp.where(valid, table[positions // bs], 0) for table in block_table
        ]
        offsets = jnp.where(valid, positions % bs, 0)
        for (cls, layer), (k, v) in new.items():
            at = (layer, block_ids[cls], offsets)
            k_cache[cls] = k_cache[cls].at[at].set(k[0].reshape(sb, -1))
            v_cache[cls] = v_cache[cls].at[at].set(v[0].reshape(sb, -1))
        logits = model.head(cfg, params, h[true_len - 1])
        out = [self._sample(logits)]
        if self.routed:
            out += [counts["held"], counts["walked"]]
        out = jnp.stack(out).astype(jnp.int32)
        return (tuple(k_cache), tuple(v_cache), tuple(map(tuple, state))), out

    def _prefill_step(
        self, params, k_cache, v_cache, state, tokens, block_table,
        true_len, slot,
    ):
        return self._chunk(
            params, k_cache, v_cache, state, tokens, block_table,
            jnp.int32(0), true_len, slot, fresh=True,
        )

    def _prefill_suffix_step(
        self, params, k_cache, v_cache, state, tokens, block_table,
        offset, true_len, slot,
    ):
        return self._chunk(
            params, k_cache, v_cache, state, tokens, block_table,
            offset, true_len, slot, fresh=False,
        )


_PROGRAM_CACHE: dict = {}
_PROGRAM_CACHE_LOCK = threading.Lock()


def _hybrid_programs(
    cfg, block_size: int, attn_impl: str, engine_config: EngineConfig
) -> _HybridPrograms:
    """One `_HybridPrograms` a configuration and process, as
    `model_runner._step_programs`: jax's cache keys on the callable, and
    where the programs' modules are stored a table is of one engine config."""
    store = program_store.default()
    table = (cfg, block_size, attn_impl)
    key = table + ((store.directory, engine_config) if store.directory else ())
    with _PROGRAM_CACHE_LOCK:
        programs = _PROGRAM_CACHE.get(key)
        if programs is None:
            programs = _PROGRAM_CACHE[key] = _HybridPrograms(
                *table,
                jit=functools.partial(
                    program_store.stored_jit, store=store,
                    table=(table, engine_config),
                ),
            )
    return programs


class HybridRunner:
    """Owns the params, the K/V pools of every cache class, the state pools
    and the compiled steps. The engine's side of `GPTRunner`, with a state
    slot or a second block table beside the blocks where the model's layers
    keep such."""

    def __init__(
        self,
        model_config,
        engine_config: EngineConfig,
        params=None,
        seed: int = 0,
    ):
        if engine_config.max_model_len > model_config.max_seq_len:
            raise ValueError(
                f"cache capacity {engine_config.max_model_len} tokens/seq "
                f"exceeds model max_seq_len {model_config.max_seq_len}"
            )
        # int8 pools and a tensor-parallel mesh are refused by the engine,
        # from what the model's configuration declares.
        self.model_config = cfg = model_config
        self.engine_config = ecfg = engine_config
        self.model = model = model_of(cfg)
        self.on_dispatched: Optional[Callable[[], None]] = None
        self.tensor_parallel_size = 1
        self.mesh = None
        ensure_compile_cache()
        self.attn_impl = resolve_paged_impl(ecfg.attn_impl)
        self.kv_cache_dtype = cfg.dtype
        self.kv_cache_dtype_str = {jnp.bfloat16: "bf16"}.get(
            cfg.dtype, jnp.dtype(cfg.dtype).name
        )
        self._programs = _hybrid_programs(
            cfg, ecfg.block_size, self.attn_impl, ecfg
        )
        self.params = (
            model.init_params(cfg, seed) if params is None else params
        )
        self.num_params = model.num_params(self.params)
        self.weight_bytes = int(
            sum(x.nbytes for x in jax.tree_util.tree_leaves(self.params))
        )
        self.host_bytes_in = 0
        self.host_bytes_out = 0

        # One K and one V pool a cache class. The full class has the
        # engine's `num_blocks`; a class with a horizon is sized from the
        # lanes, the horizon and the chunk in flight.
        self.classes = cfg.cache_classes
        held = [0] * len(self.classes)
        for mixer, layers in layers_holding(cfg).items():
            if mixer not in self._programs.recurrent:
                held[cfg.cache_class_of(mixer)] += layers
        if held != [cls.layers for cls in self.classes]:
            raise ValueError(
                f"cache classes {self.classes} against {held} layers that hold "
                "a cached mixer"
            )
        self.class_blocks = tuple(
            ecfg.num_blocks if cls.horizon is None
            else ecfg.window_class_blocks(cls.horizon)
            for cls in self.classes
        )
        minor = cfg.num_key_value_heads * cfg.head_dim
        self.k_cache, self.v_cache = (
            tuple(
                jnp.zeros((cls.layers, blocks, ecfg.block_size, minor), cfg.dtype)
                for cls, blocks in zip(self.classes, self.class_blocks)
            )
            for _ in range(2)
        )
        # One state slot a decode lane: a running sequence holds a lane
        # from admission on, prefilling or decoding. The pools are what the
        # model's recurrent kinds declare, an array a layer.
        self.recurrent = bool(cfg.recurrent_state)
        self.routed = self._programs.routed
        self.state_slots = slots = ecfg.max_decode_slots
        self.state = tuple(
            tuple(jnp.zeros((slots, *shape), dtype) for _ in range(layers))
            for _, layers, (_, shape, dtype) in state_layout(cfg)
        )
        self.state_slot_bytes = sum(
            int(pool.nbytes) for pool in jax.tree_util.tree_leaves(self.state)
        ) // slots
        # Routing and state traffic, cumulative (stats()).
        names = [
            "decode_expert_assignments", "decode_expert_assignments_absent",
            "decode_experts_touched", "decode_expert_load_max",
            "prefill_expert_assignments", "prefill_expert_rows_walked",
        ] if self.routed else []
        if self.recurrent:
            names = ["decode_state_bytes", *names, "prefill_scan_tokens"]
        # The horizon of the window class, where the model has one: what a
        # chunk's sliding layers see is counted in pairs.
        self.horizon = next(
            (cls.horizon for cls in self.classes if cls.horizon is not None), None
        )
        if self.horizon is not None:
            names.append("prefill_window_pairs")
        self.counters = dict.fromkeys(names, 0)

    # ---------------- pools ----------------

    @property
    def _tail(self) -> int:
        """The routing counts behind a decode result's tokens."""
        return len(DECODE_COUNTS) if self.routed else 0

    @property
    def _pools(self):
        return (self.k_cache, self.v_cache, self.state)

    def _set_pools(self, pools) -> None:
        self.k_cache, self.v_cache, self.state = pools

    def _dispatched(self) -> None:
        if self.on_dispatched is not None:
            self.on_dispatched()

    def _count_transfer(self, arrays_in, out) -> None:
        self.host_bytes_in += sum(int(a.nbytes) for a in arrays_in)
        self.host_bytes_out += int(out.nbytes)

    def host_transfer_bytes(self) -> int:
        return self.host_bytes_in + self.host_bytes_out

    def pool_sharding_spec(self) -> Optional[str]:
        return None

    def kv_token_bytes(self) -> int:
        """K and V of one token in one attention layer."""
        cfg = self.model_config
        return (
            2 * cfg.num_key_value_heads * cfg.head_dim
            * np.dtype(self.kv_cache_dtype).itemsize
        )

    def kv_pool_bytes(self) -> dict:
        """Both pools of every cache class, together."""
        cfg, ecfg = self.model_config, self.engine_config
        per_class = [
            kv_pool_bytes_sharded(
                cls.layers, blocks, ecfg.block_size, cfg.num_key_value_heads,
                cfg.head_dim, np.dtype(self.kv_cache_dtype).itemsize,
            )
            for cls, blocks in zip(self.classes, self.class_blocks)
        ]
        return {
            "aggregate": sum(c["aggregate"] for c in per_class),
            "per_shard": sum(c["per_shard"] for c in per_class),
            "tensor_parallel_size": 1,
        }

    def attention_shape(self) -> dict:
        """The K/V pools as the paged kernel reads them and how it tiles a
        chunk: of the one cache class, or by class name where the model
        has several or asks for it (`ATTENTION_SHAPE_BY_CLASS`)."""
        cfg = self.model_config
        recurrent = recurrent_kinds(cfg)
        kinds = {
            cfg.cache_class_of(mixer): mixer
            for mixer in layers_holding(cfg) if mixer not in recurrent
        }
        shapes = {}
        for i, cls in enumerate(self.classes):
            if i not in kinds:
                continue
            query_heads = max(cfg.heads_of(kinds[i]), default=0)
            shapes[cls.name] = {
                "num_layers": cls.layers,
                "num_heads": cfg.num_key_value_heads,
                "head_dim": cfg.head_dim,
                "kv_itemsize": np.dtype(self.kv_cache_dtype).itemsize,
                "num_query_heads": query_heads,
                **({} if cls.horizon is None else {"horizon": cls.horizon}),
                **prefill_tiling(
                    self.engine_config, query_heads, cfg.num_key_value_heads,
                    cfg.head_dim, cfg.dtype, self.kv_cache_dtype,
                ),
                **decode_tiling(
                    self.engine_config, cfg.num_key_value_heads, cfg.head_dim,
                    self.kv_cache_dtype,
                ),
            }
        by_class = len(self.classes) > 1 or getattr(
            model_of(cfg), "ATTENTION_SHAPE_BY_CLASS", False
        )
        return shapes if by_class else next(iter(shapes.values()))

    def stats(self) -> dict:
        """The counters and shapes the engine's `stats()` carries for a
        model with routed experts, with recurrent layers, or both: each
        where the model has them."""
        cfg, model = self.model_config, self.model
        recurrent = {
            "state_slots": self.state_slots,
            "state_slot_bytes": self.state_slot_bytes,
            "state_pool_bytes": self.state_slot_bytes * self.state_slots,
            "recurrent_shape": model.recurrent_shape(cfg),
        } if self.recurrent else {}
        routed = {
            "expert_shape": {
                **model.expert_shape(cfg),
                "weight_itemsize": np.dtype(cfg.param_dtype).itemsize,
            },
        } if self.routed else {}
        return {
            **self.counters, **recurrent, **routed,
            "layer_mixers": {k: list(v) for k, v in layer_mixers(cfg).items()},
            "head_shape": {
                "vocab_size": cfg.vocab_size,
                "hidden_size": cfg.hidden_size,
                "weight_itemsize": np.dtype(cfg.param_dtype).itemsize,
            },
        }

    # ---------------- programs ----------------

    def _i32(self, *shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    def _lowered(self):
        """(program name, bucket or None, lowered) for the decode program
        and every prefill program the chunked path can reach."""
        ecfg = self.engine_config
        slots, nb = ecfg.max_decode_slots, ecfg.max_blocks_per_seq
        i32 = self._i32
        per_class = len(self.classes)
        yield "jit__decode_step", None, self._programs.decode_fn.lower(
            self.params, *self._pools, i32(slots + self._tail),
            i32(slots), (i32(slots, nb),) * per_class, i32(slots),
        )
        tables = (i32(nb),) * per_class
        for width in ecfg.chunk_widths():
            yield "jit__prefill_step", width, self._programs.prefill_fn.lower(
                self.params, *self._pools, i32(1, width), tables, i32(), i32(),
            )
            yield "jit__prefill_suffix_step", width, (
                self._programs.prefill_suffix_fn.lower(
                    self.params, *self._pools, i32(1, width), tables, i32(),
                    i32(), i32(),
                )
            )

    def device_report(self) -> dict:
        """As `GPTRunner.device_report`, plus `op_scopes`: for the decode
        program and the prefill programs, HLO instruction name -> the part
        of a layer it belongs to (the model's SCOPES), which is what lets a
        reader split a trace's time by part whatever implements the part.
        The prefill programs of every bucket share a name in a trace; where
        their instruction names disagree on a part, the widest bucket's
        says (`op_scope_conflicts` counts them)."""
        op_scopes: Dict[str, Dict[str, str]] = {}
        conflicts = 0
        report: dict = {}
        for name, width, lowered in self._lowered():
            compiled = lowered.compile()
            text = compiled.as_text()
            if width is None:
                memory = compiled.memory_analysis()
                report.update(
                    decode_argument_bytes=int(memory.argument_size_in_bytes),
                    decode_temp_bytes=int(memory.temp_size_in_bytes),
                    decode_kernels=text.count('custom_call_target="tpu_custom_call"'),
                )
            merged = op_scopes.setdefault(name, {})
            for op, scope in scopes_of(text).items():
                conflicts += op in merged and merged[op] != scope
                merged[op] = scope  # widths ascend: the widest says
        leaves = jax.tree_util.tree_leaves
        return {
            "param_bytes_by_device": bytes_by_device(leaves(self.params)),
            "pool_bytes_by_device": bytes_by_device(leaves(self._pools)),
            **report,
            "decode_collectives": {},
            "op_scopes": op_scopes,
            "op_scope_conflicts": int(conflicts),
        }

    def _table(self, block_ids: Sequence[int]) -> np.ndarray:
        table = np.zeros((self.engine_config.max_blocks_per_seq,), np.int32)
        table[: len(block_ids)] = block_ids
        return table

    def _tables(self, block_ids, window_ids) -> tuple:
        """A sequence's table in every cache class, the full class first."""
        if len(self.classes) == 1:
            return (self._table(block_ids),)
        return (self._table(block_ids), self._table(window_ids))

    def _padded(self, token_ids: Sequence[int]) -> np.ndarray:
        tokens = np.zeros((1, self.engine_config.bucket_for(len(token_ids))), np.int32)
        tokens[0, : len(token_ids)] = token_ids
        return tokens

    def _chunk_done(
        self, pools, out, arrays_in, n: int, offset: int = 0
    ) -> jax.Array:
        """A chunk program is dispatched: what its shape says is counted
        here, what its output says where `read_chunk` reads it."""
        self._set_pools(pools)
        self._dispatched()
        self._count_transfer(arrays_in, out)
        start_host_copy(out)
        if self.recurrent:
            self.counters["prefill_scan_tokens"] += n
        if self.horizon is not None:
            self.counters["prefill_window_pairs"] += visible_pairs(
                offset, n, self.horizon
            )
        return out

    def read_chunk(self, out: jax.Array) -> int:
        """The token a chunk program sampled, on the host, its routing
        counts (a model with routed experts) into the counters: waits for
        the program, and raises here if it failed. Once an output."""
        token, *routing = (int(v) for v in np.asarray(out))
        if self.routed:
            self.counters["prefill_expert_assignments"] += routing[0]
            self.counters["prefill_expert_rows_walked"] += routing[1]
        return token

    def join_token(self, tokens, lane: int, out: jax.Array) -> jax.Array:
        """As `GPTRunner.join_token`; the host's buffer gets the tail a
        decode result carries behind its tokens."""
        if not isinstance(tokens, jax.Array):
            tokens = jnp.asarray(
                np.concatenate([tokens, np.zeros(self._tail, np.int32)])
            )
            self.host_bytes_in += int(tokens.nbytes)
        return self._programs.join_token_fn(tokens, np.int32(lane), out)

    @staticmethod
    def _on_device(tables) -> tuple:
        """The int32 tables of a call, transferred. Through a list: a
        transfer releases the interpreter, and a tuple that `tuple(<a
        generator>)` is still building must be referred to by nobody else
        when it is resized, which `gc.get_objects()` in another thread (the
        benchmark's harness calls it as its window closes) breaks with a
        SystemError in this one (chip run, PR 47)."""
        return tuple([jnp.asarray(t, jnp.int32) for t in tables])

    def prefill(
        self, token_ids: Sequence[int], block_ids: Sequence[int],
        state_slot: int = 0, window_ids: Sequence[int] = (),
    ) -> jax.Array:
        """Start a sequence: its first chunk, from an empty state (left in
        `state_slot` on a model with recurrent layers), its blocks those of
        `block_ids` and, in a window class, `window_ids`. Dispatches and
        returns the program's output on the device (the greedily sampled
        next token first), for `read_chunk` or `join_token`."""
        tokens, tables = self._padded(token_ids), self._tables(block_ids, window_ids)
        pools, out = self._programs.prefill_fn(
            self.params, *self._pools, jnp.asarray(tokens),
            self._on_device(tables),
            jnp.int32(len(token_ids)), jnp.int32(state_slot),
        )
        return self._chunk_done(pools, out, (tokens, *tables), len(token_ids))

    def prefill_suffix(
        self, token_ids: Sequence[int], block_ids: Sequence[int], offset: int,
        state_slot: int = 0, window_ids: Sequence[int] = (),
    ) -> jax.Array:
        """The next chunk of a sequence whose first `offset` tokens are in
        the cache (and in `state_slot`'s state)."""
        tokens, tables = self._padded(token_ids), self._tables(block_ids, window_ids)
        pools, out = self._programs.prefill_suffix_fn(
            self.params, *self._pools, jnp.asarray(tokens),
            self._on_device(tables),
            jnp.int32(offset), jnp.int32(len(token_ids)), jnp.int32(state_slot),
        )
        return self._chunk_done(
            pools, out, (tokens, *tables), len(token_ids), offset
        )

    def decode(
        self,
        tokens,
        positions: np.ndarray,
        block_tables: np.ndarray,
        context_lens: np.ndarray,
        window_tables: Optional[np.ndarray] = None,
    ) -> jax.Array:
        """As `GPTRunner.decode`: dispatch one decode over the lanes without
        waiting. On a model with recurrent layers lane i is state slot i.
        `window_tables` are the lanes' tables in the window class, where
        the model has one. The result (and a chained `tokens`) is the
        sampled tokens [lanes] and then, of a model with routed experts, the
        step's routing counts (DECODE_COUNTS), which `count_routing` takes
        once fetched."""
        chained = isinstance(tokens, jax.Array)
        if not chained:
            tokens = jnp.asarray(
                np.concatenate([tokens, np.zeros(self._tail, np.int32)])
            )
        tables = (block_tables,) if window_tables is None else (
            block_tables, window_tables
        )
        pools, out = self._programs.decode_fn(
            self.params, *self._pools, tokens,
            jnp.asarray(positions.copy(), jnp.int32),
            self._on_device([t.copy() for t in tables]),
            jnp.asarray(context_lens.copy(), jnp.int32),
        )
        self._set_pools(pools)
        self._dispatched()
        start_host_copy(out)
        host_in = (positions, *tables, context_lens)
        self._count_transfer(host_in if chained else (tokens,) + host_in, out)
        if self.recurrent:
            # Every decoding lane's state is read and written once.
            self.counters["decode_state_bytes"] += (
                2 * int(np.count_nonzero(context_lens)) * self.state_slot_bytes
            )
        return out

    def count_routing(self, fetched: np.ndarray) -> None:
        """Add a fetched decode result's routing counts (its tail; a dense
        model's result has none)."""
        if not self.routed:
            return
        held, absent, touched, load_max = (
            int(v) for v in fetched[-len(DECODE_COUNTS):]
        )
        counters = self.counters
        counters["decode_expert_assignments"] += held
        counters["decode_expert_assignments_absent"] += absent
        counters["decode_experts_touched"] += touched
        counters["decode_expert_load_max"] += load_max
