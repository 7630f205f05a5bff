"""Fused Pallas paged-attention kernel for the serving hot path.

The XLA-assembled decode path (`ops.paged_attention`) gathers whole pages —
`k_cache[block_tables]` materializes [B, nb*bs, H, D] in HBM every step —
and runs a full-matrix softmax over [B, H, Q, K] logits. This kernel walks
each sequence's block table itself. Its unit of work is a compute block:
as many table entries as hold 128 cached tokens (8 blocks of 16, 16 of 8),
gathered into one [128, H*D] tile of VMEM so that a score row fills whole
lanes. A decode call's compute block is sized in bytes instead, as many
128-token blocks as keep about 2 MiB of K and V in flight (`decode_tile`):
a narrow row's 128 tokens leave the HBM pipe empty while a turn's copies
are started and waited for. The grid is (batch, q tiles, q tiles),
sequential; a slot's context is a loop inside one grid step, bounded by the
scalar-prefetched context_lens[b], so what a table holds past the context
costs neither a copy nor a step, and an idle slot costs its new-token step
alone. The pools stay in HBM: the kernel's own async copies bring in the
blocks table[b, ...] of the context, two tiles deep, the next compute block
(or the next slot's first) in flight while this one is computed. Block gather,
QK^T, validity masking, streaming (online) softmax, and the weighted-V
accumulation all happen in one pass; neither the gathered pages nor the
logits ever touch HBM. After the walk the not-yet-scattered new tokens' K/V
are folded in under a causal mask and the last grid step normalizes —
fully-masked rows (a padded slot with context_len 0 and no new tokens) come
out as exact zeros, matching `finalize_partial`'s l == 0 hygiene.

The pools arrive as the runner stores them, [L, N, bs, H*D]: heads and head
size merged into one lane-dense minor axis, all layers in one array. That
is the form the device keeps row-major, which is what a Mosaic call takes
its operands in — a pool whose minor dimensions are [H, D] = [20, 64] is
kept with the block count minor-most, and every program that handed it to
the kernel converted the whole pool on the way in and back out. The layer
is picked in the copy's address (pool[layer, table[b, j]]), never sliced
out in XLA, and head h is lanes h*D:(h+1)*D of the tile. Mosaic slices an
HBM operand only in whole lanes: where H*D is not a multiple of 128 (a
chip's 5 heads of 64 under tp = 4) XLA gathers the table's blocks and the
kernel is handed each slot's tiles, the same body over another source.

Covers both program shapes ray_tpu.llm compiles, with the contraction
chosen by shape. Decode (S == 1) lays q block-diagonally, [H, H*D], so one
MXU product against the tile scores every head and one more weights V.
Prefix-aware partial prefill and verify (S > 1: the fed tokens attend the
cached prefix through the table and themselves causally) take one product a
cached head: the q tiles of the query heads it serves stacked along the
rows, [heads / kv_heads * tq, D] x [D, 128] (one head's [tq, D] where every
query head has its own cached head), cached heads walked a lane tile at a
time in a loop the program traces once. `ops.paged_attention` is the
correctness oracle; interpret mode on CPU runs the same code path in tests.

int8 KV cache rides on top: the cache pools store int8 with per-token,
per-head scales (written by `quantize_kv` at scatter time — per-token
scales are the only granularity a one-token decode scatter can maintain
without requantizing the rest of the block). Dequantization is fused into
the block loop, folded into the score/weight matrices: K's scale multiplies
the score columns after QK^T and V's scale folds into the softmax weights
before PV, so the kernel never materializes a dequantized block. The
[L, N, bs, H] scale pools are narrower than a lane tile: XLA gathers a
slot's scales, tokens on the lanes as the scores have them. Scales are
stored bfloat16 (math in f32): at block_size=8, head_dim=64 the pool +
scale bytes per token come to ~52% of bf16, so the same HBM holds ~1.9x the
sequences.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import (
    NEG_INF,
    dequantize_kv,  # noqa: F401 — canonical home; re-exported via ops
    head_sharded_call,
    paged_attention,
    validate_kv_pools,
    validate_tp_heads,
)
from ray_tpu.ops.flash_attention import _on_cpu

_LANES = 128  # TPU lane width: min trailing dim for scratch tiles
# VMEM one q tile's blocks and the cached K and V tiles may take together.
# The compiler's default scoped limit on v5e is 16 MiB; the rest is left to
# the kernel's own temporaries. With the heads walked in a loop those are
# one product's score and weight blocks, under 0.5 MiB: the call at 72
# query heads over 8 cached heads of 128, whose blocks count 12.7 MiB here,
# compiles for a v5e under a scoped limit of 13.5 MiB and not under 13
# (chip-less, PR 38). At 20 heads of 64 the two-deep bf16 tiles are
# 1.25 MiB and a 128-token q tile 8.75.
_Q_TILE_VMEM_BYTES = 13 * 1024 * 1024

# Storage dtype for the KV-cache scale tensors. bf16 keeps the scale
# overhead at 2 bytes per (token, head) — f32 scales at block_size=8 would
# eat the capacity win the int8 pool exists for. All scale MATH is f32;
# quantization divides by the bf16-rounded scale so the round trip is
# consistent with what the kernel will dequantize with.
KV_SCALE_DTYPE = jnp.bfloat16


def quantize_kv(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-token, per-head int8 quantization of K or V.

    x: [..., H, D] (any leading shape) → (values int8 [..., H, D],
    scales KV_SCALE_DTYPE [..., H]). Scales are amax/127 per (token, head)
    so a single decode token's scatter writes its own scale slot and never
    touches neighbors — the property that makes quantization compatible
    with the paged cache's per-token writes (per-block scales would need
    the whole block requantized on every append).
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-8).astype(KV_SCALE_DTYPE)
    # Quantize with the *stored* (bf16-rounded) scale; clip because the
    # rounding can shrink the scale by ~0.4%, pushing x/scale past 127.
    q = jnp.clip(
        jnp.round(xf / scale.astype(jnp.float32)[..., None]), -127.0, 127.0
    ).astype(jnp.int8)
    return q, scale


def _online_update(s, stats, weigh):
    """One streaming-softmax step: fold the score block `s` ([rows, cols])
    into the running (max, sum, accumulator) scratch `stats`. `weigh(p)`
    returns the softmax weights' product with the block's value rows.
    The scratch holds the same rows, [rows, ...] or split by query head
    [heads, rows // heads, ...]: whole sublane tiles either way."""
    m_scr, l_scr, acc_scr = stats
    rows = s.shape[0]
    m_prev = m_scr[..., 0:1].reshape(rows, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    # Masked lanes hold NEG_INF: exp underflows to exactly 0.
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_scr[..., 0:1].reshape(rows, 1) * alpha + jnp.sum(
        p, axis=1, keepdims=True
    )
    acc = acc_scr[...].reshape(rows, -1) * alpha + weigh(p)
    acc_scr[...] = acc.reshape(acc_scr.shape)
    for scr, new in ((m_scr, m_new), (l_scr, l_new)):
        scr[...] = jnp.broadcast_to(new, (rows, scr.shape[-1])).reshape(scr.shape)


def _dot(a, b, contract):
    return jax.lax.dot_general(
        a, b, ((contract, ((), ()))), preferred_element_type=jnp.float32
    )


def _each(n, body, looped: bool = True, first=0) -> None:
    """body(i) for `first` <= i < n: a loop whose body the program traces
    and lowers once with i traced, or (`looped` false, n static) n times
    with i a Python number. Rolled costs a 64-token chunk's call 56 us where it took
    47 unrolled (on the chip), and a step program a fifth of the lowering:
    36 layers of 20 unrolled heads were 50 s of every replica's start."""
    if looped:
        jax.lax.fori_loop(first, n, lambda i, carry: body(i) or carry, 0)
    else:
        for i in range(first, n):
            body(i)


def _head_mask(heads: int, head_dim: int) -> jax.Array:
    """[H, H*D] bool: lane belongs to the row's head."""
    shape = (heads, heads * head_dim)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    first = jax.lax.broadcasted_iota(jnp.int32, shape, 0) * head_dim
    return (lane >= first) & (lane < first + head_dim)


def _paged_kernel(
    # scalar prefetch
    tables_ref, lens_ref,
    # inputs
    q_ref, nk_ref, nv_ref, k_src, v_src, *rest,
    heads: int, head_dim: int, bs: int, nb: int, entries: int, tq: int,
    layer: int, quantized: bool, batched_heads: bool, kernel_copies: bool,
    kv_heads: int, window: Optional[int] = None,
):
    """Grid (B, nq, nq), every dimension sequential: one q tile of `tq` fed
    tokens per (b, qi). Step j == 0 walks the slot's cached context in
    compute blocks of `entries` table entries (`entries * bs` tokens): a
    loop bounded by context_lens[b], so a block past the context costs
    neither a copy nor a grid step. Every step j <= qi then folds new-token
    tile j in causally, and the last step normalizes. Running max / sum /
    accumulator live in VMEM scratch.

    `kernel_copies`: the pools stay in HBM and the kernel copies the blocks
    table[b, c*entries ...] of `layer` that the context reaches into one
    [entries * bs, H*D] tile of VMEM, two tiles deep; the copy of the next
    compute block (the next step's first one, when this is the last) is in
    flight while this one is computed. Otherwise `k_src` / `v_src` are the
    slot's context already gathered, [compute blocks, entries * bs, H*D].

    Two contractions over the same tile, chosen by shape. Decode
    (`batched_heads`, tq == 1): q, new K/V and out are lane-dense [1, H*D]
    rows; q is laid block-diagonally [H, H*D], so one product against the
    tile scores every head, one more weights V, and the other heads' lanes
    of the accumulator are dropped at the end. Otherwise q / new-token K/V
    / out blocks are heads-leading [1, H, tq, D] (Mosaic tiles the last two
    dims, so a per-head [tq, D] view must not have the head dim between
    them) and cached head h is a lane slice of the tile: one
    [rows, D] x [D, tokens] product, one streaming-softmax step and one
    weighted-V product a cached head. int8 scales come gathered,
    [compute blocks, H, tokens]: the layout the scores have.

    Grouped-query attention (`kv_heads` < `heads`): the tile, the new K/V
    and the lanes hold `kv_heads` heads, each read by `heads // kv_heads`
    consecutive query heads. Decode's q then arrives already laid
    block-diagonally, [H, kv_heads * D] with row h in the lanes of cached
    head h // group, and its out leaves in that form (the caller keeps each
    row's own lanes). Elsewhere the q tiles of a cached head's query heads
    are consecutive in the heads-leading blocks and scratch, so they are
    read, folded and written as one [heads // kv_heads * tq, ...] slab: the
    rows of one product (a reshape of whole sublane tiles; tq is a multiple
    of the dtype's sublane tile for every chunk width a grouped model
    warms), each row masked by its own token's position. With
    kv_heads == heads the slab is one head's [tq, ...] and every branch
    below is the one it was.

    `window`: the query at position p sees the keys at p - window < j <= p
    (a fed token's position is context_lens[b] + its index). The walk of a
    q tile then starts at the compute block that holds its first row's
    lowest visible key, copies of it only the table entries from that key's
    block on (what lies below may be the null block: a window cache frees
    those blocks), and masks row by row; a new-token tile no row of the q
    tile can see is skipped. None: every branch below is the one it was."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr, *copy_scratch = rest
    else:
        o_ref, m_scr, l_scr, acc_scr, *copy_scratch = rest
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    qi = pl.program_id(1)
    j = pl.program_id(2)
    compute_dtype = q_ref.dtype
    d = head_dim
    tile = entries * bs  # cached tokens a compute block covers
    ctx = lens_ref[b]
    live_blocks = pl.cdiv(ctx, tile)

    def horizon(slot_b, q_tile):
        """The lowest cached position the first row of q tile `q_tile` of
        slot `slot_b` sees: no row of the tile sees below it."""
        return jnp.maximum(lens_ref[slot_b] + q_tile * tq - (window - 1), 0)

    def first_block(slot_b, q_tile):
        """The compute block a walk starts at."""
        if window is None:
            return 0
        return jnp.minimum(
            horizon(slot_b, q_tile) // tile, pl.cdiv(lens_ref[slot_b], tile)
        )

    # Heads are walked in a loop, not unrolled, so a program traces and
    # lowers one head's body a section whatever the model's. A head's lanes
    # of the tile can be sliced at a traced offset only in whole lane
    # tiles: heads narrower than one go a lane tile's worth a turn, and a
    # shape that does not divide so is unrolled.
    group = _LANES // d if _LANES % d == 0 else 1
    looped = kv_heads % group == 0 and (group * d) % _LANES == 0
    if not looped:
        group = 1
    shared = heads // kv_heads  # query heads a cached head serves

    # A fed chunk's unit of work is a cached head: its `shared` query heads'
    # q tiles stacked along the rows of one product, [shared * tq, D].
    def of_head(h):
        """Where cached head h's query heads lie on a heads-leading axis."""
        return h if shared == 1 else pl.ds(h * shared, shared)

    def stacked(x):  # [shared, tq, n] -> [shared * tq, n]; [tq, n] as it is
        return x.reshape(-1, x.shape[-1])

    def under_each_head(x):  # [tq, n] -> [shared * tq, n]: x a query head
        if shared == 1:
            return x
        return stacked(jnp.broadcast_to(x[None], (shared, *x.shape)))

    def head_stats(h):
        if batched_heads:
            return m_scr, l_scr, acc_scr
        return tuple(scr.at[of_head(h)] for scr in (m_scr, l_scr, acc_scr))

    def block_diagonal_q():  # [H, H*D] float32: row h holds head h's q
        if shared > 1:
            return q_ref[...].astype(jnp.float32)
        return jnp.where(
            _head_mask(heads, d), q_ref[...].astype(jnp.float32), 0.0
        )

    def cached_tile(c, k_ref, v_ref, at):
        """Fold compute block `c`, whose K and V rows are `k_ref[at]` and
        `v_ref[at]`: [tile, H*D], or that in `entries` blocks."""

        def rows(ref, lo, lanes):
            return ref[at, ..., pl.ds(lo, lanes)].reshape(tile, lanes)

        t_ids = c * tile + jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
        live = t_ids < ctx
        if window is not None:
            # Row r of a query head is the query at ctx + qi * tq + r
            # (decode's rows are the heads of the one query at ctx).
            q_pos = ctx + qi * tq + jax.lax.broadcasted_iota(
                jnp.int32, (1 if batched_heads else tq, 1), 0
            )
            if not batched_heads:
                q_pos = under_each_head(q_pos)
            live = live & (t_ids + window > q_pos)

        def fold(h, s, heads_here, v):
            # int8 dequant is folded into the score / weight matrices: K's
            # per-token scale multiplies score columns, V's rescales the
            # softmax weights — never a dequantized [tile, D] block.
            if quantized:
                s = s * ks_ref[c, pl.ds(h, heads_here)]

            def weigh(p):
                if quantized:
                    p = p * vs_ref[c, pl.ds(h, heads_here)]
                return _dot(p.astype(compute_dtype), v, ((1,), (0,)))

            _online_update(jnp.where(live, s, NEG_INF), head_stats(h), weigh)

        if batched_heads:
            s = _dot(
                block_diagonal_q().astype(compute_dtype),
                rows(k_ref, 0, kv_heads * d).astype(compute_dtype),
                ((1,), (1,)),
            )  # [H, tile]
            fold(0, s, heads, rows(v_ref, 0, kv_heads * d).astype(compute_dtype))
            return

        def head_group(g):
            lo = g * group * d
            if looped:
                lo = pl.multiple_of(lo, _LANES)
            k = rows(k_ref, lo, group * d).astype(compute_dtype)
            v = rows(v_ref, lo, group * d).astype(compute_dtype)
            for i in range(group):
                h, lanes = g * group + i, slice(i * d, (i + 1) * d)
                s = _dot(
                    stacked(q_ref[0, of_head(h)]), k[:, lanes], ((1,), (1,))
                )  # [shared * tq, tile]
                fold(h, s, 1, v[:, lanes])

        _each(kv_heads // group, head_group, looped)

    def walk_copying():
        base_ref, sems, k_buf, v_buf = copy_scratch
        nq = pl.num_programs(1)

        def tile_copies(slot_b, q_tile, c, slot, act):
            """Start or wait for the copies of compute block `c` of slot
            `slot_b` into tile `slot`: the table entries its context
            reaches (from the one that holds q tile `q_tile`'s horizon on,
            under a window), so one (slot_b, q_tile, c) names the same
            copies both times."""
            reached = pl.cdiv(lens_ref[slot_b] - c * tile, bs)
            below = 0
            if window is not None:
                below = jnp.clip(
                    (horizon(slot_b, q_tile) - c * tile) // bs, 0, entries
                )

            def entry(i):
                page = tables_ref[slot_b, jnp.minimum(c * entries + i, nb - 1)]
                for n, (hbm, buf) in enumerate(
                    ((k_src, k_buf), (v_src, v_buf))
                ):
                    copy = pltpu.make_async_copy(
                        hbm.at[layer, page], buf.at[slot, i], sems.at[n, slot]
                    )
                    getattr(copy, act)()  # "start" | "wait"

            _each(jnp.clip(reached, 0, entries), entry, first=below)

        @pl.when((b == 0) & (qi == 0))
        def _first():
            # A tile row no copy has written is weighted by an exact 0,
            # which a stale NaN would survive.
            k_buf[...] = jnp.zeros_like(k_buf)
            v_buf[...] = jnp.zeros_like(v_buf)
            base_ref[0] = 0
            tile_copies(b, qi, first_block(b, qi), 0, "start")

        base = base_ref[0]  # the tile this walk's first block is in
        # The step that walks next: its first block is this step's to
        # start, so that only the call's first block is waited for cold.
        b_next = jnp.where(qi == nq - 1, b + 1, b)
        has_next = b_next < pl.num_programs(0)
        b_next = jnp.minimum(b_next, pl.num_programs(0) - 1)
        # The q tile that walks next and, under a window, where each walk
        # starts; with none every walk starts at block 0 and a q tile does
        # not enter a copy's name.
        qi_next = first = first_next = 0
        if window is not None:
            qi_next = jnp.where(qi == nq - 1, 0, qi + 1)
            first = first_block(b, qi)
            first_next = first_block(b_next, qi_next)

        def walked(c):  # blocks of this walk before block c
            return c if window is None else c - first

        def body(c, carry):
            slot = (base + walked(c)) % 2

            last = c + 1 == live_blocks

            @pl.when(~last | has_next)
            def _():
                tile_copies(
                    jnp.where(last, b_next, b),
                    0 if window is None else jnp.where(last, qi_next, qi),
                    jnp.where(last, first_next, c + 1), 1 - slot, "start",
                )

            tile_copies(b, qi, c, slot, "wait")
            cached_tile(c, k_buf, v_buf, slot)
            return carry

        jax.lax.fori_loop(first, live_blocks, body, 0)

        @pl.when((live_blocks == first) & has_next)
        def _idle():
            tile_copies(b_next, qi_next, first_next, base, "start")

        base_ref[0] = (base + walked(live_blocks)) % 2

    @pl.when(j == 0)
    def _walk():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        if kernel_copies:
            walk_copying()
            return

        def body(c, carry):
            cached_tile(c, k_src, v_src, c)
            return carry

        jax.lax.fori_loop(first_block(b, qi), live_blocks, body, 0)

    # New-token tile j is folded in where some row of q tile qi sees some
    # column of it: at or below the diagonal and, under a window, with the
    # tile's nearest pair (its first row, j's last column) inside it.
    seen = j <= qi
    if window is not None:
        seen = seen & ((qi - j) * tq - (tq - 1) < window)

    @pl.when(seen)
    def _new_tokens():
        if batched_heads:
            # One fed token: it attends itself, no mask. Scores are the
            # block-diagonal q's lane sums against the new K row.
            s = jnp.sum(
                block_diagonal_q() * nk_ref[...].astype(jnp.float32),
                axis=1, keepdims=True,
            )  # [H, 1]
            nv = nv_ref[...].astype(jnp.float32)
            _online_update(
                s, head_stats(0),
                lambda p: p.astype(compute_dtype).astype(jnp.float32) * nv,
            )
            return

        def head(h):  # a cached head and the query heads it serves
            s = _dot(
                stacked(q_ref[0, of_head(h)]), nk_ref[0, h], ((1,), (1,))
            )  # [shared * tq, tq]
            rows = under_each_head(
                qi * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, tq), 0)
            )
            cols = j * tq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            visible = rows >= cols
            if window is not None:
                visible = visible & (rows - cols < window)
            _online_update(
                jnp.where(visible, s, NEG_INF), head_stats(h),
                lambda p: _dot(  # new tokens are never quantized
                    p.astype(compute_dtype), nv_ref[0, h], ((1,), (0,))
                ),
            )

        _each(kv_heads, head)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        # Fully-masked rows (context_len 0 and no valid new token)
        # normalize to exact zeros, not garbage — finalize_partial's
        # l == 0 hygiene.
        def normalized(stats):
            _, l_scr, acc_scr = stats
            l = l_scr[..., 0:1]
            return jnp.where(
                l == 0.0, 0.0, acc_scr[...] / jnp.where(l == 0.0, 1.0, l)
            )

        if batched_heads and shared > 1:
            o_ref[...] = normalized(head_stats(0)).astype(o_ref.dtype)
            return
        if batched_heads:
            out = jnp.where(_head_mask(heads, d), normalized(head_stats(0)), 0)
            o_ref[...] = jnp.sum(out, axis=0, keepdims=True).astype(o_ref.dtype)
            return

        def head(h):
            o_ref[0, of_head(h)] = normalized(head_stats(h)).astype(o_ref.dtype)

        _each(kv_heads, head)


_TILE_TOKENS = 128  # cached tokens a compute block covers: whole lanes


def _whole_lanes(n: int) -> int:
    return -(-n // _LANES) * _LANES


def _compute_blocks(block_size: int, table_blocks: int) -> Tuple[int, int, int]:
    """A table's compute blocks: as many table entries as make _TILE_TOKENS
    cached tokens (the whole table, where that is shorter) -> (entries a
    compute block, its tokens, compute blocks a table)."""
    entries = min(max(1, _TILE_TOKENS // block_size), table_blocks)
    return entries, entries * block_size, -(-table_blocks // entries)


# K and V bytes a decode walk keeps in flight ahead of the compute block it
# folds: in whole MiB, what one 128-token block is of the one row that fills
# the HBM pipe at that width, 30 cached heads of 128 in bf16 (1.875 MiB;
# 88.7% of the roofline: ledger, PR 48). A narrower row gets as many
# 128-token blocks a compute block as come to that, and no more than
# _DECODE_TILE_TOKENS: past them a long walk gains nothing and a short or
# windowed one pays for rows no copy wrote (a call at 4 cached heads of 128
# and contexts of 350: 299 us at 128 tokens, 214 at 512, 240 at 1,024; at 8
# of 128 and 3,000: 1,191, 835, 830; under a window of 512: 278, 242, 287;
# on the chip, PR 51).
_DECODE_BYTES_IN_FLIGHT = 2 * 1024 * 1024
_DECODE_TILE_TOKENS = 512


def decode_tile(
    block_size: int, table_blocks: int, kv_heads: int, head_dim: int,
    kv_itemsize: int,
) -> Tuple[int, int, int]:
    """`_compute_blocks` of a decode walk (one fed token a slot): where the
    kernel copies the blocks itself, as many whole 128-token blocks as keep
    _DECODE_BYTES_IN_FLIGHT of K and V in flight, the whole table where
    that is shorter. The two-deep ring is then at most twice those bytes,
    4 MiB, whatever the row (at a wider row than the constant's, the two
    128-token tiles it always was). int8 pools (their scales are gathered a
    128-token block) and the gathered transport keep 128 tokens too."""
    entries, tile, n_tiles = _compute_blocks(block_size, table_blocks)
    lanes = kv_heads * head_dim
    if lanes % _LANES or kv_itemsize == 1:
        return entries, tile, n_tiles
    wide = min(
        _DECODE_BYTES_IN_FLIGHT // (2 * tile * lanes * kv_itemsize),
        _DECODE_TILE_TOKENS // tile,
    )
    entries = min(entries * max(1, wide), table_blocks)
    return entries, entries * block_size, -(-table_blocks // entries)


def q_tile(
    s_len: int, heads: int, kv_heads: int, head_dim: int, itemsize: int,
    block_size: int, table_blocks: int, kv_itemsize: int,
) -> int:
    """Fed tokens per q tile of an `s_len`-token chunk, as
    `paged_flash_attention` tiles it: the largest tile whose blocks fit
    _Q_TILE_VMEM_BYTES beside the cached K and V (the two-deep tiles the
    kernel copies, or a slot's gathered context and, for int8 pools of
    `kv_itemsize` 1, its scales) — q and out at `heads`, new K and new V at
    `kv_heads`, double-buffered in the compute dtype, plus the float32
    running max, sum and accumulator at `heads`, every [tile, D] slab
    padded to whole lanes. One product of the fed-chunk branch has
    `heads // kv_heads` times as many rows."""
    _, tile, n_tiles = _compute_blocks(block_size, table_blocks)
    if (kv_heads * head_dim) % _LANES == 0:
        kv_vmem_bytes = 2 * 2 * tile * kv_heads * head_dim * kv_itemsize
    else:
        kv_vmem_bytes = (
            2 * 2 * n_tiles * tile * _whole_lanes(kv_heads * head_dim) * kv_itemsize
        )
    if kv_itemsize == 1:
        kv_vmem_bytes += 2 * 2 * n_tiles * heads * _whole_lanes(tile) * 4
    row_bytes = _whole_lanes(head_dim) * (
        heads * (2 * 2 * itemsize + 3 * 4) + kv_heads * 2 * 2 * itemsize
    )
    room = _Q_TILE_VMEM_BYTES - kv_vmem_bytes
    tile = next((t for t in (128, 64, 32) if t * row_bytes <= room), 16)
    return min(s_len, tile)


def resolve_paged_impl(impl: str) -> str:
    """Resolve the `impl` knob to a concrete implementation: 'auto' picks
    the fused kernel only on an actual TPU backend and the XLA reference
    everywhere else (CPU, GPU — the kernel's PrefetchScalarGridSpec and
    compiler params lower for TPU only; CPU gets it via interpret mode
    when forced). The single owner of that policy — the engine (tagging
    metrics/flight records) and the dispatcher below both call this, so
    they can never disagree."""
    if impl not in ("auto", "pallas", "reference"):
        raise ValueError(f"Unknown paged attention impl {impl!r}")
    if impl == "auto":
        return (
            "pallas" if jax.devices()[0].platform == "tpu" else "reference"
        )
    return impl


def paged_flash_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    block_tables: jax.Array,
    context_lens: jax.Array,
    *,
    new_k: jax.Array,
    new_v: jax.Array,
    layer: int = 0,
    sm_scale: Optional[float] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    interpret: Optional[bool] = None,
    num_kv_heads: Optional[int] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Fused paged attention over the block-table KV cache (Pallas TPU).

    Same contract as :func:`ray_tpu.ops.paged_attention` — q [B, S, H, D],
    k/v_cache [L, N, bs, H*D] pools read at `layer`, block_tables [B, nb]
    (0-padded), context_lens [B] — except `new_k`/`new_v` are REQUIRED
    (every generation step of ray_tpu.llm carries the new tokens' K/V; a
    cache-only query should use the reference op). S == 1 is decode,
    S > 1 is prefix-aware partial prefill. When the cache pools are int8,
    `k_scale`/`v_scale` [L, N, bs, H] carry the per-token dequant scales
    (see `quantize_kv`). `num_kv_heads` (default: new_k's) is the number of
    cached heads under grouped-query attention: the pools are
    [L, N, bs, num_kv_heads * D], new_k / new_v [B, S, num_kv_heads, D], and
    query head h reads cached head h // (H // num_kv_heads). With
    num_kv_heads == H the call lowers to the program it always did.
    `window`: a fed token at position p (context_lens[b] + its index) sees
    the keys at p - window < j <= p only, and the table entries of blocks
    wholly below a slot's lowest such key are never read (they may be the
    null block). None lowers to the program it always did.

    Runs in interpret mode on CPU by default so tests exercise the same
    kernel the TPU compiles.
    """
    if new_k is None or new_v is None:
        raise ValueError(
            "paged_flash_attention requires new_k/new_v (the engine always "
            "carries the new tokens' K/V); use ops.paged_attention for "
            "cache-only queries"
        )
    hkv = new_k.shape[2] if num_kv_heads is None else num_kv_heads
    if new_k.shape[2] != hkv or new_v.shape[2] != hkv:
        raise ValueError(
            f"new_k/new_v hold {new_k.shape[2]}/{new_v.shape[2]} heads, "
            f"num_kv_heads is {hkv}"
        )
    validate_kv_pools(q, k_cache, v_cache, k_scale, v_scale, hkv)
    quantized = k_cache.dtype == jnp.int8
    if window is not None and (window < 1 or quantized):
        raise ValueError(
            f"window {window}: at least 1, and not over int8 pools (their "
            "gathered scales are not cut to a window)"
        )
    b, s_len, h, d = q.shape
    grouped = hkv != h
    nb = block_tables.shape[1]
    bs = k_cache.shape[2]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = _on_cpu()
    # Prescale q once outside the kernel (fused into the producing matmul's
    # epilogue by XLA): no per-score-element scale pass inside.
    q = (q.astype(jnp.float32) * sm_scale).astype(q.dtype)

    # One fed token a slot (decode) takes every head in one product: q, new
    # K/V and out as lane-dense [1, H*D] rows, which q's own reshape gives.
    # Its compute block is sized in bytes; a chunk's is 128 tokens.
    batched_heads = s_len == 1
    if batched_heads:
        entries, tile, n_tiles = decode_tile(
            bs, nb, hkv, d, k_cache.dtype.itemsize
        )
    else:
        entries, tile, n_tiles = _compute_blocks(bs, nb)
    # The kernel copies blocks out of a pool itself only in whole lanes.
    # Mosaic refuses to slice a narrower or ragged minor axis in HBM (a
    # chip's 5 heads of 64 under tp = 4; every scale pool): XLA gathers the
    # table's blocks of those, and the kernel is handed a slot's tiles.
    kernel_copies = (hkv * d) % _LANES == 0

    def gathered(pool):  # [L, N, bs, X] -> [B, n_tiles, tile, X]
        x = pool[layer][block_tables]
        x = jnp.pad(x, ((0, 0), (0, n_tiles * entries - nb), (0, 0), (0, 0)))
        return x.reshape(b, n_tiles, tile, pool.shape[3])

    def slot_tiles(rows, lanes):  # a slot's gathered tiles, resident
        return pl.BlockSpec(
            (None, n_tiles, rows, lanes),
            lambda bi, qi, j, tables_ref, lens_ref: (bi, 0, 0, 0),
        )

    if kernel_copies:
        # The pools go in whole, as stored: the copies' addresses choose
        # the layer and the block, so XLA neither slices a layer out of a
        # pool nor converts its layout.
        kv_specs = [pl.BlockSpec(memory_space=pl.ANY)] * 2
        kv_operands = [k_cache, v_cache]
        tile_buf = pltpu.VMEM((2, entries, bs, hkv * d), k_cache.dtype)
        copy_scratch = [
            pltpu.SMEM((1,), jnp.int32),       # the tile the next walk starts in
            pltpu.SemaphoreType.DMA((2, 2)),   # (K | V, tile)
            tile_buf, tile_buf,
        ]
    else:
        kv_specs = [slot_tiles(tile, hkv * d)] * 2
        kv_operands = [gathered(k_cache), gathered(v_cache)]
        copy_scratch = []
    if quantized:
        # Tokens on the lanes, as the scores have them.
        kv_specs += [slot_tiles(h, tile)] * 2
        kv_operands += [
            gathered(scale).transpose(0, 1, 3, 2).astype(jnp.float32)
            for scale in (k_scale, v_scale)
        ]

    # The fed tokens are tiled over S so VMEM holds one [H, tq, D] tile of
    # q / new K / new V / out and its statistics, whatever the bucket. A
    # length that is not a whole number of tiles is zero-padded: padded key
    # columns sit above every real row's diagonal, padded q rows are cut.
    tq = q_tile(
        s_len, h, hkv, d, q.dtype.itemsize, bs, nb, k_cache.dtype.itemsize
    )
    nq = -(-s_len // tq)
    pad = nq * tq - s_len

    if batched_heads:
        fed_block, stat_rows, acc_shape = (None, 1, h * d), (h,), (h, hkv * d)
        new_block = (None, 1, hkv * d)

        def fed(x):  # [B, 1, H, D] -> [B, 1, H*D]
            return x.reshape(b, 1, x.shape[2] * d)

        def q_map(bi, qi, j, tables_ref, lens_ref):
            return (bi, 0, 0)

        new_map = q_map
        if grouped:
            # q goes in laid block-diagonally, [B, H, Hkv*D]: row h holds
            # query head h in the lanes of cached head h // group, nought
            # elsewhere. Out comes back in the same form.
            fed_block = (None, h, hkv * d)
            own = (
                jnp.arange(h)[:, None] // (h // hkv) == jnp.arange(hkv)[None, :]
            )  # [H, Hkv]
    else:
        fed_block, stat_rows, acc_shape = (1, h, tq, d), (h, tq), (h, tq, d)
        new_block = (1, hkv, tq, d)

        def fed(x):  # [B, S, H, D] -> [B, H, nq * tq, D]
            x = x.transpose(0, 2, 1, 3)
            return (
                jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else x
            )

        def q_map(bi, qi, j, tables_ref, lens_ref):
            return (bi, 0, qi, 0)

        def new_map(bi, qi, j, tables_ref, lens_ref):
            # New-token tile j, held at qi above the diagonal: a skipped
            # step re-names a tile that is already resident and copies
            # nothing.
            return (bi, 0, jnp.minimum(j, qi), 0)

    if batched_heads and grouped:
        q = jnp.where(own[None, :, :, None], q[:, 0, :, None, :], 0).reshape(
            b, h, hkv * d
        )
    else:
        q = fed(q)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nq, nq),
        in_specs=[
            pl.BlockSpec(fed_block, q_map),
            pl.BlockSpec(new_block, new_map), pl.BlockSpec(new_block, new_map),
            *kv_specs,
        ],
        out_specs=pl.BlockSpec(fed_block, q_map),
        scratch_shapes=[
            pltpu.VMEM(stat_rows + (_LANES,), jnp.float32),
            pltpu.VMEM(stat_rows + (_LANES,), jnp.float32),
            pltpu.VMEM(acc_shape, jnp.float32),
            *copy_scratch,
        ],
    )
    kernel = functools.partial(
        _paged_kernel, heads=h, head_dim=d, bs=bs, nb=nb, entries=entries,
        tq=tq, layer=layer, quantized=quantized, batched_heads=batched_heads,
        kernel_copies=kernel_copies, kv_heads=hkv,
        **({} if window is None else {"window": window}),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        # Sequential throughout: a step starts the copies the next one
        # waits for, and the online softmax state lives in scratch.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(block_tables, context_lens, q, fed(new_k), fed(new_v), *kv_operands)
    if batched_heads and grouped:
        # Row h's own lanes: those of cached head h // group.
        out = out.reshape(b, h, hkv, d)
        return jnp.sum(jnp.where(own[None, :, :, None], out, 0), axis=2)[:, None]
    if batched_heads:
        return out.reshape(b, 1, h, d)
    return out[:, :, :s_len].transpose(0, 2, 1, 3)


def paged_attention_impl(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    block_tables: jax.Array,
    context_lens: jax.Array,
    *,
    new_k: Optional[jax.Array] = None,
    new_v: Optional[jax.Array] = None,
    layer: int = 0,
    sm_scale: Optional[float] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    impl: str = "auto",
    mesh=None,
    window: Optional[int] = None,
) -> jax.Array:
    """Dispatcher: the fused Pallas kernel on TPU, the XLA reference
    elsewhere (impl='auto'); 'pallas' forces the kernel (interpret mode on
    CPU), 'reference' forces the gather+softmax reference. Both take the
    pools as stored ([L, N, bs, H*D], read at `layer`). A cache-only
    query (new_k=None) is outside the kernel's contract: 'auto' falls back
    to the reference, 'pallas' raises (inside paged_flash_attention).

    `mesh` (a Mesh whose `tp` axis is > 1) runs the chosen implementation
    head-sliced over the tensor-parallel axis via shard_map: each chip's
    instance receives only its local heads' q / new-token K/V / cache and
    scale pool slices (heads are contiguous lane groups of the pools'
    minor axis), so the kernel's per-block DMA touches local-head bytes
    only and the attention output comes back head-sharded with no
    collective (heads never mix inside attention — the psum this layering
    implies happens later, in the attn output projection).

    `window` (both implementations, not under a mesh): a fed token at
    position p sees the keys at p - window < j <= p."""
    resolved = resolve_paged_impl(impl)
    use_reference = resolved == "reference" or (
        impl == "auto" and new_k is None
    )
    op = paged_attention if use_reference else paged_flash_attention
    if window is not None:
        if mesh is not None and mesh.shape.get("tp", 1) > 1:
            raise ValueError("a window is not implemented under a tp mesh")
        op = functools.partial(op, window=window)
    if mesh is not None and mesh.shape.get("tp", 1) > 1:
        from jax.sharding import PartitionSpec as P

        from ray_tpu.parallel.sharding import LLM_HEAD_SPEC, LLM_POOL_SPEC

        validate_tp_heads(q.shape[2], mesh.shape["tp"])
        if sm_scale is None:
            sm_scale = 1.0 / math.sqrt(q.shape[-1])
        args = [q, k_cache, v_cache, block_tables, context_lens]
        specs = [LLM_HEAD_SPEC, LLM_POOL_SPEC, LLM_POOL_SPEC, P(), P()]
        if new_k is not None:
            args += [new_k, new_v]
            specs += [LLM_HEAD_SPEC, LLM_HEAD_SPEC]
        if k_scale is not None:
            args += [k_scale, v_scale]
            specs += [LLM_POOL_SPEC, LLM_POOL_SPEC]

        def sharded(q, k_cache, v_cache, block_tables, context_lens,
                    *rest):
            nk = nv = ks = vs = None
            if new_k is not None:
                nk, nv, *rest = rest
            if k_scale is not None:
                ks, vs = rest
            return op(
                q, k_cache, v_cache, block_tables, context_lens,
                new_k=nk, new_v=nv, layer=layer, sm_scale=sm_scale,
                k_scale=ks, v_scale=vs,
            )

        return head_sharded_call(mesh, sharded, args, specs)
    return op(
        q, k_cache, v_cache, block_tables, context_lens,
        new_k=new_k, new_v=new_v, layer=layer, sm_scale=sm_scale,
        k_scale=k_scale, v_scale=v_scale,
    )


def kv_pool_bytes(
    num_blocks: int, block_size: int, heads: int, head_dim: int,
    kv_dtype, with_scales: bool,
) -> int:
    """Total bytes of one K or V pool (+ its scale tensor when int8):
    the honest denominator for capacity-ratio claims."""
    values = (
        num_blocks * block_size * heads * head_dim * np.dtype(kv_dtype).itemsize
    )
    if with_scales:
        values += (
            num_blocks * block_size * heads * np.dtype(KV_SCALE_DTYPE).itemsize
        )
    return values
