"""Jitted step programs for a model whose layers carry a recurrent state
beside the paged cache (`ray_tpu.models.granite_hybrid`).

The same three program shapes `model_runner` compiles, under the same
names: one decode program over all decode lanes, and for every prefill
bucket a program that starts a sequence (`_prefill_step`: empty state, no
cached context) and one that continues it (`_prefill_suffix_step`: the
chunk starts from the slot's state and attends the cached context through
the block table). Beside the K/V pools, which cover the attention layers
only ([attention layers, N, bs, kv heads * head size]), every Mamba layer
has a state pool [slots, H, P, N] float32 and a convolution-tail pool
[slots, d_conv - 1, conv_dim], one array a layer, all donated through
every step.

A sequence owns one state slot for as long as it runs (the scheduler hands
them out), and its slot is its lane in the decode batch: the decode
program updates the state pools in place, lane for lane, and leaves the
lanes that do not decode this step (context length 0: idle, or a sequence
still prefilling) as they were. Nothing gathers or scatters a state. A
slot is never cleared: the program that starts a sequence does not read
it.

The decode program returns the sampled tokens and the step's routing
counts in one int32 vector, so the engine reads both in the one fetch it
makes anyway, one step behind at pipeline depth 1; its token input has the
same length so that a step can be chained on the last one's output.
"""

from __future__ import annotations

import re
import threading
from typing import Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu._private.jax_setup import ensure_compile_cache
from ray_tpu.llm.cache import kv_pool_bytes_sharded
from ray_tpu.llm.config import EngineConfig
from ray_tpu.llm.model_runner import bytes_by_device
from ray_tpu.models import granite_hybrid as model
from ray_tpu.ops.paged_flash import paged_attention_impl, resolve_paged_impl

# The routing counts a decode step appends to its tokens, in this order.
DECODE_COUNTS = ("held", "absent", "touched", "load_max")
SCOPES = (
    "llm.mixer.mamba.proj", "llm.mixer.mamba.scan", "llm.mixer.mamba.update",
    "llm.mixer.attention", "llm.moe.router", "llm.moe.routed",
    "llm.moe.shared", "llm.head",
)
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?(?P<name>[^\s=]+) = ")
_OP_NAME = re.compile(r'op_name="(?P<path>[^"]*)"')


def scopes_of(hlo_text: str) -> Dict[str, str]:
    """HLO instruction name -> the innermost of SCOPES its `op_name`
    metadata passes through, for the instructions that have one. A fusion
    carries its root's metadata, so an operation fused across two parts
    counts to its root's."""
    out: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        found = _INSTRUCTION.match(line)
        path = _OP_NAME.search(line)
        if not found or not path:
            continue
        inside = [part for part in path["path"].split("/") if part in SCOPES]
        if inside:
            out[found["name"]] = inside[-1]
        elif path["path"].startswith("ragged-dot"):
            # XLA:TPU expands a ragged dot into a kernel and its metadata
            # call under a name of their own, without the scope; these
            # programs have no ragged dot but the routed experts'.
            out[found["name"]] = "llm.moe.routed"
    return out


class _HybridPrograms:
    """The jitted programs of one (model, block size, attention impl)."""

    def __init__(self, cfg: model.GraniteHybridConfig, block_size: int, attn_impl: str):
        self.cfg = cfg
        self.block_size = block_size
        self.attn_impl = attn_impl
        donated = (1, 2, 3, 4)
        self.decode_fn = jax.jit(self._decode_step, donate_argnums=donated)
        self.prefill_fn = jax.jit(self._prefill_step, donate_argnums=donated)
        self.prefill_suffix_fn = jax.jit(
            self._prefill_suffix_step, donate_argnums=donated
        )

    def _sample(self, logits):
        """Greedy, over the last axis. The one place a program's logits
        become tokens: tests observe them here."""
        return jnp.argmax(logits, axis=-1)

    def _attend(self, p, u, k_cache, v_cache, tables, lens, layer, new):
        """q of u against the cached context and the new tokens' own K/V
        (kept in `new` for the scatter). u [B, S, D]."""
        cfg = self.cfg
        with jax.named_scope("llm.mixer.attention"):
            q, k, v = model.attention_qkv(cfg, p, u)
            new[layer] = (k, v)
            out = paged_attention_impl(
                q, k_cache, v_cache, tables, lens, new_k=k, new_v=v,
                layer=layer, sm_scale=cfg.attention_multiplier,
                impl=self.attn_impl,
            )
            return model._matmul(
                out.reshape(u.shape[:-1] + (-1,)), p["o"], cfg.dtype
            )

    def _decode_step(
        self, params, k_cache, v_cache, conv, ssm, tokens, positions,
        block_tables, context_lens,
    ):
        """One token for every lane that decodes. tokens [B + counts] (the
        last step's output or the host's; the first B are read), the rest
        [B] / [B, nb] -> (pools, [B tokens, counts])."""
        cfg = self.cfg
        b = positions.shape[0]
        live = context_lens > 0
        conv, ssm = list(conv), list(ssm)
        new: dict = {}

        def mamba(i, p, u):
            out, tail, state = model.mamba_decode(cfg, p, u, conv[i], ssm[i])
            with jax.named_scope("llm.mixer.mamba.update"):
                conv[i] = jnp.where(live[:, None, None], tail, conv[i])
                ssm[i] = jnp.where(live[:, None, None, None], state, ssm[i])
            return out

        def attend(i, p, u):
            return self._attend(
                p, u[:, None], k_cache, v_cache, block_tables, context_lens,
                i, new,
            )[:, 0]

        h, counts = model.run_layers(
            cfg, params, model.embed(cfg, params, tokens[:b]), mamba, attend,
            grouped=False, valid=live,
        )
        # Each lane's new K/V at its own position; an idle lane's table is
        # all null, so it lands in block 0.
        block_ids = block_tables[jnp.arange(b), positions // self.block_size]
        offsets = positions % self.block_size
        for layer, (k, v) in new.items():
            k_cache = k_cache.at[layer, block_ids, offsets].set(k.reshape(b, -1))
            v_cache = v_cache.at[layer, block_ids, offsets].set(v.reshape(b, -1))
        next_tokens = self._sample(model.head(cfg, params, h))
        out = jnp.concatenate([
            next_tokens.astype(jnp.int32),
            jnp.stack([counts[k] for k in DECODE_COUNTS]).astype(jnp.int32),
        ])
        return (k_cache, v_cache, tuple(conv), tuple(ssm)), out

    def _chunk(
        self, params, k_cache, v_cache, conv, ssm, tokens, block_table,
        offset, true_len, slot, fresh: bool,
    ):
        """tokens [1, S_bucket] (0-padded past true_len) of the sequence in
        state slot `slot`, at positions offset.. -> (pools, [next token,
        held assignments])."""
        cfg = self.cfg
        sb = tokens.shape[1]
        lane = jnp.arange(sb)
        valid = lane < true_len
        positions = jnp.where(valid, offset + lane, 0)
        conv, ssm = list(conv), list(ssm)
        new: dict = {}

        def mamba(i, p, u):
            if fresh:
                tail, state = jnp.zeros_like(conv[i][0]), jnp.zeros_like(ssm[i][0])
            else:
                tail, state = conv[i][slot], ssm[i][slot]
            out, tail, state = model.mamba_prefill(cfg, p, u, tail, state, true_len)
            with jax.named_scope("llm.mixer.mamba.scan"):
                conv[i] = conv[i].at[slot].set(tail.astype(conv[i].dtype))
                ssm[i] = ssm[i].at[slot].set(state)
            return out

        def attend(i, p, u):
            return self._attend(
                p, u[None], k_cache, v_cache, block_table[None, :],
                jnp.reshape(offset, (1,)), i, new,
            )[0]

        h, counts = model.run_layers(
            cfg, params, model.embed(cfg, params, tokens[0]), mamba, attend,
            grouped=True, valid=valid,
        )
        bs = self.block_size
        block_ids = jnp.where(valid, block_table[positions // bs], 0)
        offsets = jnp.where(valid, positions % bs, 0)
        for layer, (k, v) in new.items():
            k_cache = k_cache.at[layer, block_ids, offsets].set(k[0].reshape(sb, -1))
            v_cache = v_cache.at[layer, block_ids, offsets].set(v[0].reshape(sb, -1))
        logits = model.head(cfg, params, h[true_len - 1])
        out = jnp.stack([self._sample(logits), counts["held"]]).astype(jnp.int32)
        return (k_cache, v_cache, tuple(conv), tuple(ssm)), out

    def _prefill_step(
        self, params, k_cache, v_cache, conv, ssm, tokens, block_table,
        true_len, slot,
    ):
        return self._chunk(
            params, k_cache, v_cache, conv, ssm, tokens, block_table,
            jnp.int32(0), true_len, slot, fresh=True,
        )

    def _prefill_suffix_step(
        self, params, k_cache, v_cache, conv, ssm, tokens, block_table,
        offset, true_len, slot,
    ):
        return self._chunk(
            params, k_cache, v_cache, conv, ssm, tokens, block_table,
            offset, true_len, slot, fresh=False,
        )


_PROGRAM_CACHE: dict = {}
_PROGRAM_CACHE_LOCK = threading.Lock()


def _hybrid_programs(cfg, block_size: int, attn_impl: str) -> _HybridPrograms:
    """One `_HybridPrograms` a configuration and process, as
    `model_runner._step_programs`: jax's cache keys on the callable."""
    key = (cfg, block_size, attn_impl)
    with _PROGRAM_CACHE_LOCK:
        programs = _PROGRAM_CACHE.get(key)
        if programs is None:
            programs = _PROGRAM_CACHE[key] = _HybridPrograms(*key)
    return programs


class HybridRunner:
    """Owns the params, the K/V and state pools, and the compiled steps.
    The engine's side of `GPTRunner`, with a state slot beside the blocks."""

    def __init__(
        self,
        model_config: model.GraniteHybridConfig,
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
        self.on_dispatched: Optional[Callable[[], None]] = None
        self.tensor_parallel_size = 1
        self.mesh = None
        ensure_compile_cache()
        self.attn_impl = resolve_paged_impl(ecfg.attn_impl)
        self.kv_cache_dtype = cfg.dtype
        self.kv_cache_dtype_str = {jnp.bfloat16: "bf16"}.get(
            cfg.dtype, jnp.dtype(cfg.dtype).name
        )
        self._programs = _hybrid_programs(cfg, ecfg.block_size, self.attn_impl)
        self.params = (
            model.init_params(cfg, seed) if params is None else params
        )
        self.num_params = model.num_params(self.params)
        self.weight_bytes = int(
            sum(x.nbytes for x in jax.tree_util.tree_leaves(self.params))
        )
        self.host_bytes_in = 0
        self.host_bytes_out = 0

        kv_shape = (
            cfg.attention_layers, ecfg.num_blocks, ecfg.block_size,
            cfg.num_key_value_heads * cfg.head_dim,
        )
        self.k_cache = jnp.zeros(kv_shape, cfg.dtype)
        self.v_cache = jnp.zeros(kv_shape, cfg.dtype)
        # One state slot a decode lane: a running sequence holds a lane
        # from admission on, prefilling or decoding.
        self.state_slots = slots = ecfg.max_decode_slots
        self.conv = tuple(
            jnp.zeros((slots, cfg.mamba_d_conv - 1, cfg.conv_dim), cfg.dtype)
            for _ in range(cfg.mamba_layers)
        )
        self.ssm = tuple(
            jnp.zeros(
                (slots, cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state),
                jnp.float32,
            )
            for _ in range(cfg.mamba_layers)
        )
        self.state_slot_bytes = sum(
            int(pool.nbytes) for pool in self.conv + self.ssm
        ) // slots
        # Routing and state traffic, cumulative (stats()).
        self.counters = dict.fromkeys(
            (
                "decode_state_bytes", "decode_expert_assignments",
                "decode_expert_assignments_absent", "decode_experts_touched",
                "decode_expert_load_max", "prefill_expert_assignments",
                "prefill_scan_tokens",
            ),
            0,
        )

    # ---------------- pools ----------------

    @property
    def _pools(self):
        return (self.k_cache, self.v_cache, self.conv, self.ssm)

    def _set_pools(self, pools) -> None:
        self.k_cache, self.v_cache, self.conv, self.ssm = pools

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

    def kv_pool_bytes(self) -> dict:
        cfg, ecfg = self.model_config, self.engine_config
        return kv_pool_bytes_sharded(
            cfg.attention_layers, ecfg.num_blocks, ecfg.block_size,
            cfg.num_key_value_heads, cfg.head_dim,
            np.dtype(self.kv_cache_dtype).itemsize,
        )

    def attention_shape(self) -> dict:
        """The K/V pools as the paged kernel reads them."""
        cfg = self.model_config
        return {
            "num_layers": cfg.attention_layers,
            "num_heads": cfg.num_key_value_heads,
            "head_dim": cfg.head_dim,
            "kv_itemsize": np.dtype(self.kv_cache_dtype).itemsize,
            "num_query_heads": cfg.num_attention_heads,
        }

    def stats(self) -> dict:
        """The counters and shapes the engine's `stats()` carries for a
        model with recurrent layers and routed experts."""
        cfg = self.model_config
        return {
            **self.counters,
            "state_slots": self.state_slots,
            "state_slot_bytes": self.state_slot_bytes,
            "state_pool_bytes": self.state_slot_bytes * self.state_slots,
            "recurrent_shape": {
                "num_layers": cfg.mamba_layers,
                "num_heads": cfg.mamba_n_heads,
                "head_dim": cfg.mamba_d_head,
                "state_size": cfg.mamba_d_state,
                "conv_width": cfg.mamba_d_conv,
                "conv_dim": cfg.conv_dim,
                "chunk_size": cfg.mamba_chunk_size,
                "state_itemsize": 4,
                "conv_itemsize": np.dtype(cfg.dtype).itemsize,
            },
            "expert_shape": {
                "num_layers": cfg.num_layers,
                "num_experts": cfg.num_local_experts,
                "experts_held": len(cfg.experts_held),
                "experts_per_token": cfg.num_experts_per_tok,
                "hidden_size": cfg.hidden_size,
                "expert_width": cfg.intermediate_size,
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
        yield "jit__decode_step", None, self._programs.decode_fn.lower(
            self.params, *self._pools, i32(slots + len(DECODE_COUNTS)),
            i32(slots), i32(slots, nb), i32(slots),
        )
        for width in ecfg.chunk_widths():
            yield "jit__prefill_step", width, self._programs.prefill_fn.lower(
                self.params, *self._pools, i32(1, width), i32(nb), i32(), i32(),
            )
            yield "jit__prefill_suffix_step", width, (
                self._programs.prefill_suffix_fn.lower(
                    self.params, *self._pools, i32(1, width), i32(nb), i32(),
                    i32(), i32(),
                )
            )

    def device_report(self) -> dict:
        """As `GPTRunner.device_report`, plus `op_scopes`: for the decode
        program and the prefill programs, HLO instruction name -> the part
        of a layer it belongs to (SCOPES), which is what lets a reader
        split a trace's time by part whatever implements the part. The
        prefill programs of every bucket share a name in a trace; where
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

    def _padded(self, token_ids: Sequence[int]) -> np.ndarray:
        tokens = np.zeros((1, self.engine_config.bucket_for(len(token_ids))), np.int32)
        tokens[0, : len(token_ids)] = token_ids
        return tokens

    def _chunk_done(self, pools, out, arrays_in, n: int) -> int:
        self._set_pools(pools)
        self._dispatched()
        self._count_transfer(arrays_in, out)
        token, held = (int(v) for v in np.asarray(out))
        self.counters["prefill_expert_assignments"] += held
        self.counters["prefill_scan_tokens"] += n
        return token

    def prefill(
        self, token_ids: Sequence[int], block_ids: Sequence[int], state_slot: int
    ) -> int:
        """Start a sequence in `state_slot`: its first chunk, from an empty
        state. Returns the greedily sampled next token."""
        tokens, table = self._padded(token_ids), self._table(block_ids)
        pools, out = self._programs.prefill_fn(
            self.params, *self._pools, jnp.asarray(tokens), jnp.asarray(table),
            jnp.int32(len(token_ids)), jnp.int32(state_slot),
        )
        return self._chunk_done(pools, out, (tokens, table), len(token_ids))

    def prefill_suffix(
        self, token_ids: Sequence[int], block_ids: Sequence[int], offset: int,
        state_slot: int,
    ) -> int:
        """The next chunk of the sequence in `state_slot`, whose first
        `offset` tokens are in the cache and in the slot's state."""
        tokens, table = self._padded(token_ids), self._table(block_ids)
        pools, out = self._programs.prefill_suffix_fn(
            self.params, *self._pools, jnp.asarray(tokens), jnp.asarray(table),
            jnp.int32(offset), jnp.int32(len(token_ids)), jnp.int32(state_slot),
        )
        return self._chunk_done(pools, out, (tokens, table), len(token_ids))

    def decode(
        self,
        tokens,
        positions: np.ndarray,
        block_tables: np.ndarray,
        context_lens: np.ndarray,
    ) -> jax.Array:
        """As `GPTRunner.decode`: dispatch one decode over the lanes without
        waiting. Lane i is state slot i. The result (and a chained `tokens`)
        is [lanes + len(DECODE_COUNTS)]: the sampled tokens, then the
        step's routing counts, which `count_routing` takes once fetched."""
        chained = isinstance(tokens, jax.Array)
        if not chained:
            tokens = jnp.asarray(
                np.concatenate([tokens, np.zeros(len(DECODE_COUNTS), np.int32)])
            )
        pools, out = self._programs.decode_fn(
            self.params, *self._pools, tokens,
            jnp.asarray(positions.copy(), jnp.int32),
            jnp.asarray(block_tables.copy(), jnp.int32),
            jnp.asarray(context_lens.copy(), jnp.int32),
        )
        self._set_pools(pools)
        self._dispatched()
        try:
            out.copy_to_host_async()
        except (AttributeError, NotImplementedError):  # pragma: no cover
            pass
        host_in = (positions, block_tables, context_lens)
        self._count_transfer(host_in if chained else (tokens,) + host_in, out)
        # Every decoding lane's state is read and written once.
        self.counters["decode_state_bytes"] += (
            2 * int(np.count_nonzero(context_lens)) * self.state_slot_bytes
        )
        return out

    def count_routing(self, fetched: np.ndarray) -> None:
        """Add a fetched decode result's routing counts (its tail)."""
        held, absent, touched, load_max = (
            int(v) for v in fetched[-len(DECODE_COUNTS):]
        )
        counters = self.counters
        counters["decode_expert_assignments"] += held
        counters["decode_expert_assignments_absent"] += absent
        counters["decode_experts_touched"] += touched
        counters["decode_expert_load_max"] += load_max
