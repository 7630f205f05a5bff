"""Flash attention as a Pallas TPU kernel.

Online-softmax attention tiled for the MXU: grid (batch*heads, q_blocks,
kv_blocks) with the kv dimension sequential ("arbitrary") so running max/sum/
accumulator live in VMEM scratch across kv steps. bf16 inputs hit the MXU; all
softmax statistics are f32.

Causal masking skips the compute of fully-masked (q, kv) blocks via pl.when.
(Clamping the index maps to also elide those blocks' copies was measured
SLOWER on v5e — the data-dependent block index defeats the pipeline's
prefetch — so the copies run and only the matmuls are skipped; the inner
loop is per-step-overhead-bound at d=64 anyway.)

Layout: kernels run on [B*H, S, D] (Mosaic tiles the last two dims, so the
head dim cannot stay minor-adjacent to D). The fold/unfold transposes are
paid ONCE in the forward; residuals are saved in kernel layout so the
backward re-reads them directly instead of re-transposing ~125 MB per layer
(the original scheme's hidden cost at GPT-2 bench shapes).

Backward: when the whole sequence fits one block (num_q == num_k == 1, the
GPT-2 bench case), a SINGLE fused kernel computes dQ, dK, and dV in one
program — one s/p recompute and 5 matmuls instead of the 7 (plus two
softmax recomputes) of the two-kernel scheme, with delta (rowsum dO·O)
folded in. Longer sequences use two kernels (dQ accumulating over k-blocks;
dK/dV over q-blocks) fed by the forward's per-row logsumexp; neither
direction ever materializes S×S logits, so long-context training stays
compute-bound (measured on v5e: fwd+bwd at S=8192 is ~10x the full-logits
recompute). Q arrives at every kernel prescaled by sm_scale (folded into
surrounding XLA ops), removing the per-element scale passes; dQ is
rescaled once on its [block, d] output tile.

Grouped queries and a sliding window (`flash_attention`'s `window`; the
group is q.shape[2] // k.shape[2]): K and V stay at their own head count
in HBM. The forward and the dQ kernel run one program a QUERY head and
read the key head `row // group`; the dK/dV kernel runs one program a KEY
head and its sequential axis walks the group's query heads one after the
other ([B*Hq, S, D] folded is [B*Hkv, group*S, D] without a copy, so
"the group's rows stacked along the q axis" and "accumulate over the
group's heads" are the same addressing). Under a window the query at i
sees i - window < j <= i, and the grids shrink to the band: the
sequential axis has only as many steps as blocks can meet one block's band
(2 of 8 at S=8192, window 1024, blocks of 1024), counted from the
diagonal's block, so blocks outside the band cost neither a product, nor a
copy, nor a grid step. That index is a function of the program ids alone.
A step before the sequence's start clamps to block 0 and is skipped. At
window None and group 1 every kernel and index map traces as it did
before either existed (tests/test_flash_window_gqa.py pins the jaxprs).

Net-new vs the reference (no attention kernels exist in Ray); design follows
the standard flash-attention blockwise algorithm (PAPERS.md) and the Pallas TPU
guide's scratch/when/dimension-semantics idioms.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import NEG_INF, mha_reference

_LANES = 128  # TPU lane width: min trailing dim for scratch tiles
# What `flash_attention`'s backward reads of its forward kernel: the output in
# kernel layout and the rows' log-sum-exp, under these names. A caller that
# recomputes a layer asks for them (`models/mellum.py`:
# `jax.checkpoint(..., policy=save_only_these_names(*RESIDUAL_NAMES))`) and
# the forward kernel then runs once; where no policy asks, a name is an
# identity and lowers to nothing.
RESIDUAL_NAMES = ("flash_out", "flash_lse")


def _seen(q_ids, k_ids, window: Optional[int]):
    """The causal mask, and the window's lower bound where there is one."""
    if window is None:
        return q_ids >= k_ids
    return (q_ids >= k_ids) & (k_ids > q_ids - window)


def _band_k_block(qi, step, block_q: int, block_k: int, steps: int):
    """Under a window: the key block that `step` of q block `qi` reads, the
    last of `steps` being the diagonal's. Negative before the sequence's
    start (the index maps clamp it, the kernels skip it)."""
    return (qi * block_q + block_q - 1) // block_k - (steps - 1) + step


def _band_q_block(ki, step, block_q: int, block_k: int):
    """Under a window: the q block that `step` of key block `ki` reads, the
    first being the diagonal's. Past the sequence's end where the band
    leaves it (clamped and skipped alike)."""
    return (ki * block_k) // block_q + step


def _in_band(qi, ki, block_q: int, block_k: int, window: int):
    """Whether q block `qi` sees anything of key block `ki`, given that the
    block is not above the diagonal: its last key is inside the first
    query's window, and the block exists."""
    return (ki >= 0) & (ki * block_k + block_k - 1 > qi * block_q - window)


def _band_steps(window: int, block_q: int, block_k: int, num_q: int, num_k: int):
    """(key blocks that can meet one q block's band, q blocks that can meet
    one key block's): the extents of the kernels' sequential axes under a
    window."""
    k_steps = max(
        (i * block_q + block_q - 1) // block_k
        - max((i * block_q - window + 1) // block_k, 0) + 1
        for i in range(num_q)
    )
    q_steps = max(
        min((j * block_k + block_k + window - 2) // block_q, num_q - 1)
        - (j * block_k) // block_q + 1
        for j in range(num_k)
    )
    return k_steps, q_steps


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scratch, l_scratch, acc_scratch,
    *, causal: bool, block_q: int, block_k: int, num_k: int,
    window: Optional[int] = None,
):
    ki = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    step = ki
    if window is None:
        # Blocks entirely above the causal diagonal contribute nothing: skip
        # their compute (their copies still run — see module docstring).
        needed = (not causal) or (ki * block_k <= qi * block_q + block_q - 1)
    else:
        # `num_k` steps ending at the diagonal's block (module docstring).
        ki = _band_k_block(qi, step, block_q, block_k, num_k)
        needed = _in_band(qi, ki, block_q, block_k, window)

    @pl.when(needed)
    def _body():
        q = q_ref[0]  # [block_q, d], prescaled by sm_scale
        k = k_ref[0]  # [block_k, d]
        v = v_ref[0]  # [block_k, d]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [block_q, block_k]

        if causal:
            q_ids = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_ids = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(_seen(q_ids, k_ids, window), s, NEG_INF)

        # Under a window a row may see nothing of an early block: its
        # maximum stays NEG_INF and p reads 1 there, and the diagonal's
        # block, which comes last and holds the row's own key, wipes that
        # with alpha == 0.
        m_prev = m_scratch[:, 0:1]  # [block_q, 1] broadcast column
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # Masked lanes hold NEG_INF: exp underflows to exactly 0, no second
        # select needed.
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)  # [block_q, 1]
        l_new = l_scratch[:, 0:1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scratch[:] = acc_scratch[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scratch[:] = jnp.broadcast_to(m_new, m_scratch.shape)
        l_scratch[:] = jnp.broadcast_to(l_new, l_scratch.shape)

    @pl.when(step == num_k - 1)
    def _finalize():
        l = l_scratch[:, 0:1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scratch[:] / l).astype(o_ref.dtype)
        # Per-row logsumexp, consumed by the backward kernels. Stored with 8
        # redundant sublane rows: TPU blocks need the last two dims to tile
        # (8, 128), and a (1, block_q) block does not.
        lse = m_scratch[:, 0] + jnp.log(l[:, 0])
        lse_ref[0] = jnp.broadcast_to(lse[None, :], lse_ref.shape[1:])


def _flash_fwd_pallas(
    q: jax.Array, k: jax.Array, v: jax.Array,
    causal: bool, block_q: int, block_k: int, interpret: bool,
    window: Optional[int] = None,
):
    """q [B*Hq, S, D] prescaled by sm_scale, k and v [B*Hkv, S, D]. Returns
    (out, lse)."""
    bh, s_q, d = q.shape
    s_k = k.shape[1]
    kv_row = _kv_row(bh // k.shape[0])
    block_q = min(block_q, s_q)
    block_k = min(block_k, s_k)
    if s_q % block_q or s_k % block_k:
        raise ValueError(
            f"seq lengths ({s_q},{s_k}) must be divisible by blocks "
            f"({block_q},{block_k})"
        )
    num_q = s_q // block_q
    num_k = s_k // block_k
    if window is None:
        kv_map = lambda b, i, j: (kv_row(b), j, 0)
    else:
        num_k, _ = _band_steps(window, block_q, block_k, num_q, num_k)
        kv_map = lambda b, i, j: (
            kv_row(b), jnp.maximum(_band_k_block(i, j, block_q, block_k, num_k), 0), 0
        )
    kernel = functools.partial(
        _fwd_kernel,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        num_k=num_k,
        window=window,
    )
    return pl.pallas_call(
        kernel,
        grid=(bh, num_q, num_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_q, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 8, s_q), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)


def _kv_row(group: int):
    """Folded query row [B*Hq] -> the folded K/V row [B*Hkv] it reads."""
    if group == 1:
        return lambda b: b
    return lambda b: b // group


def _on_cpu() -> bool:
    return jax.devices()[0].platform == "cpu"


def _pick_block(s: int) -> int:
    """Largest power-of-two block <= 1024 that divides the sequence length
    (falls back to s itself for short/odd lengths, handled by the min()
    clamp in the pallas wrappers)."""
    for block in (1024, 512, 256, 128):
        if s % block == 0:
            return block
    return s


# ---------------------------------------------------------------- backward


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_scratch,
    *, sm_scale: float, causal: bool, block_q: int, block_k: int, num_k: int,
    window: Optional[int] = None,
):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    qi = pl.program_id(1)
    step = ki
    if window is None:
        # Causal: k blocks entirely above the diagonal contribute nothing.
        needed = (not causal) or (ki * block_k <= qi * block_q + block_q - 1)
    else:
        ki = _band_k_block(qi, step, block_q, block_k, num_k)
        needed = _in_band(qi, ki, block_q, block_k, window)

    @pl.when(needed)
    def _body():
        q = q_ref[0]  # prescaled by sm_scale
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if causal:
            q_ids = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_ids = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(_seen(q_ids, k_ids, window), s, NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0][:, None])  # [bq, bk] f32
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta_ref[0, 0][:, None])
        acc_scratch[:] = acc_scratch[:] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(step == num_k - 1)
    def _finalize():
        # sm_scale applied once on the [block_q, d] tile rather than per
        # S×S element.
        dq_ref[0] = (acc_scratch[:] * sm_scale).astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_scratch, dv_scratch,
    *, sm_scale: float, causal: bool, block_q: int, block_k: int, num_q: int,
    window: Optional[int] = None, group: int = 1, q_blocks: int = 0,
):
    """One program a key head and key block; the sequential axis walks the
    `group` query heads of the key head, `num_q` steps each (`q_blocks`, of
    the sequence's, under a window: `num_q` is then the band's steps)."""
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scratch[:] = jnp.zeros_like(dk_scratch)
        dv_scratch[:] = jnp.zeros_like(dv_scratch)

    ki = pl.program_id(1)
    step = qi
    if group > 1:
        qi = step % num_q
    if window is None:
        needed = (not causal) or (qi * block_q + block_q - 1 >= ki * block_k)
    else:
        qi = _band_q_block(ki, qi, block_q, block_k)
        needed = (qi < q_blocks) & _in_band(qi, ki, block_q, block_k, window)

    @pl.when(needed)
    def _body():
        q = q_ref[0]  # prescaled by sm_scale: dS^T @ q_scaled == sm_scale·dS^T @ q
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if causal:
            q_ids = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_ids = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(_seen(q_ids, k_ids, window), s, NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0][:, None])  # [bq, bk]
        # dV += P^T @ dO
        dv_scratch[:] = dv_scratch[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta_ref[0, 0][:, None])
        # dK += dS^T @ Q_scaled (carries the sm_scale factor)
        dk_scratch[:] = dk_scratch[:] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(step == group * num_q - 1)
    def _finalize():
        dk_ref[0] = dk_scratch[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scratch[:].astype(dv_ref.dtype)


def _bwd_fused_kernel(
    q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref, dk_ref, dv_ref,
    *, sm_scale: float, causal: bool
):
    """Whole-sequence backward in ONE program (num_q == num_k == 1): a
    single s/p recompute feeds dV, dK, and dQ — 5 matmuls vs the two-kernel
    scheme's 7 — and delta (rowsum dO·O) is computed in-kernel on the
    [S, d] tiles instead of as a separate XLA op."""
    q = q_ref[0]  # [s, d], prescaled by sm_scale
    k = k_ref[0]
    v = v_ref[0]
    do = do_ref[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    if causal:
        q_ids = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_ids = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(q_ids >= k_ids, s, NEG_INF)
    p = jnp.exp(s - lse_ref[0, 0][:, None])  # masked lanes underflow to 0
    dv_ref[0] = jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(dv_ref.dtype)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    delta = jnp.sum(
        do.astype(jnp.float32) * o_ref[0].astype(jnp.float32),
        axis=1, keepdims=True,
    )
    ds = p * (dp - delta)
    ds_lp = ds.astype(q.dtype)
    dk_ref[0] = jax.lax.dot_general(
        ds_lp, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ).astype(dk_ref.dtype)  # q prescaled: carries sm_scale
    dq = jax.lax.dot_general(
        ds_lp, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    dq_ref[0] = (dq * sm_scale).astype(dq_ref.dtype)


def _flash_bwd_fused_pallas(q, k, v, o, do, lse, sm_scale, causal, interpret):
    """Single-block backward: q,k,v,o,do [BH, S, D]; lse [BH, 8, S]."""
    bh, s_len, d = q.shape
    full = lambda b: (b, 0, 0)
    return pl.pallas_call(
        functools.partial(_bwd_fused_kernel, sm_scale=sm_scale, causal=causal),
        grid=(bh,),
        in_specs=[
            pl.BlockSpec((1, s_len, d), full),
            pl.BlockSpec((1, s_len, d), full),
            pl.BlockSpec((1, s_len, d), full),
            pl.BlockSpec((1, s_len, d), full),
            pl.BlockSpec((1, s_len, d), full),
            pl.BlockSpec((1, 8, s_len), full),
        ],
        out_specs=[
            pl.BlockSpec((1, s_len, d), full),
            pl.BlockSpec((1, s_len, d), full),
            pl.BlockSpec((1, s_len, d), full),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_len, d), q.dtype),
            jax.ShapeDtypeStruct((bh, s_len, d), k.dtype),
            jax.ShapeDtypeStruct((bh, s_len, d), v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(q, k, v, o, do, lse)


def _flash_bwd_pallas(
    q, k, v, do, lse, delta, sm_scale, causal, block_q, block_k, interpret,
    window=None,
):
    """q, do [B*Hq, S, D], k, v [B*Hkv, S, D], lse, delta [B*Hq, 8, S];
    returns (dq, dk, dv), dk and dv summed over a key head's query heads."""
    bh, s_q, d = q.shape
    s_k = k.shape[1]
    group = bh // k.shape[0]
    kv_row = _kv_row(group)
    block_q = min(block_q, s_q)
    block_k = min(block_k, s_k)
    num_q = s_q // block_q
    num_k = s_k // block_k
    if window is None:
        k_steps, q_steps = num_k, num_q
        kv_map = lambda b, i, j: (kv_row(b), j, 0)
        q_block = lambda j, i: i
    else:
        k_steps, q_steps = _band_steps(window, block_q, block_k, num_q, num_k)
        kv_map = lambda b, i, j: (
            kv_row(b), jnp.maximum(_band_k_block(i, j, block_q, block_k, k_steps), 0), 0
        )
        q_block = lambda j, i: jnp.minimum(
            _band_q_block(j, i, block_q, block_k), num_q - 1
        )
    if group == 1:
        q_of = lambda b, j, i: (b, q_block(j, i))
    else:
        # Step i of a key head's program: query head i // q_steps of its
        # group, that head's step i % q_steps.
        q_of = lambda b, j, i: (b * group + i // q_steps, q_block(j, i % q_steps))
    q_map = lambda b, j, i: (*q_of(b, j, i), 0)
    qrow_map = lambda b, j, i: (q_of(b, j, i)[0], 0, q_of(b, j, i)[1])
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, num_k=k_steps, window=window,
        ),
        grid=(bh, num_q, k_steps),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, num_q=q_steps, window=window,
            group=group, q_blocks=num_q,
        ),
        grid=(k.shape[0], num_k, group * q_steps),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, 8, block_q), qrow_map),
            pl.BlockSpec((1, 8, block_q), qrow_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ------------------------------------------------- packed-QKV fast path
#
# GPT-style blocks produce one [B, S, 3E] projection; the packed kernels
# consume it directly — heads are lane-slices inside the kernel, so the
# split / [B,S,H,D] reshape / fold-unfold transposes vanish from the graph
# (~600 MB/layer of pure layout traffic at GPT-2 bench shapes), and the
# backward emits dqkv [B, S, 3E] ready for the projection's grad matmul.
# One program per batch row; causal work is subtiled in halves so the
# strictly-above-diagonal quarter of every matmul is skipped with no grid
# overhead (everything stays VMEM-resident).


def _packed_fwd_kernel(qkv_ref, o_ref, lse_ref, *, heads: int, dim: int,
                       sm_scale: float, causal: bool, n_sub: int):
    s_len = o_ref.shape[1]
    embed = heads * dim
    C = s_len // n_sub
    for h in range(heads):
        k = qkv_ref[0, :, embed + h * dim:embed + (h + 1) * dim]
        v = qkv_ref[0, :, 2 * embed + h * dim:2 * embed + (h + 1) * dim]
        for t in range(n_sub):
            lim = (t + 1) * C if causal else s_len
            rows = slice(t * C, (t + 1) * C)
            q = qkv_ref[0, rows, h * dim:(h + 1) * dim]
            q = (q.astype(jnp.float32) * sm_scale).astype(q.dtype)
            s = jax.lax.dot_general(
                q, k[:lim, :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [C, lim]
            if causal:
                qi = t * C + jax.lax.broadcasted_iota(jnp.int32, (C, lim), 0)
                ki = jax.lax.broadcasted_iota(jnp.int32, (C, lim), 1)
                s = jnp.where(qi >= ki, s, NEG_INF)
            m = jnp.max(s, axis=1, keepdims=True)
            p = jnp.exp(s - m)
            l = jnp.sum(p, axis=1, keepdims=True)
            o = jax.lax.dot_general(
                p.astype(v.dtype), v[:lim, :], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            o_ref[0, rows, h * dim:(h + 1) * dim] = (o / l).astype(o_ref.dtype)
            lse_ref[0, h, t * C:(t + 1) * C] = (m + jnp.log(l))[:, 0]


def _packed_bwd_kernel(qkv_ref, o_ref, do_ref, lse_ref, dqkv_ref,
                       *, heads: int, dim: int, sm_scale: float,
                       causal: bool, n_sub: int):
    s_len = o_ref.shape[1]
    embed = heads * dim
    C = s_len // n_sub
    for h in range(heads):
        k = qkv_ref[0, :, embed + h * dim:embed + (h + 1) * dim]
        v = qkv_ref[0, :, 2 * embed + h * dim:2 * embed + (h + 1) * dim]
        do_h = do_ref[0, :, h * dim:(h + 1) * dim]
        dk_parts = []
        dv_parts = []
        for t in range(n_sub):
            lim = (t + 1) * C if causal else s_len
            rows = slice(t * C, (t + 1) * C)
            q = qkv_ref[0, rows, h * dim:(h + 1) * dim]
            q = (q.astype(jnp.float32) * sm_scale).astype(q.dtype)
            do_r = do_h[rows, :]
            s = jax.lax.dot_general(
                q, k[:lim, :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if causal:
                qi = t * C + jax.lax.broadcasted_iota(jnp.int32, (C, lim), 0)
                ki = jax.lax.broadcasted_iota(jnp.int32, (C, lim), 1)
                s = jnp.where(qi >= ki, s, NEG_INF)
            lse_r = lse_ref[0, h, t * C:(t + 1) * C]
            p = jnp.exp(s - lse_r[:, None])  # masked lanes underflow to 0
            dp = jax.lax.dot_general(
                do_r, v[:lim, :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            delta = jnp.sum(
                do_r.astype(jnp.float32)
                * o_ref[0, rows, h * dim:(h + 1) * dim].astype(jnp.float32),
                axis=1, keepdims=True)
            ds = p * (dp - delta)
            p_lp = p.astype(do_r.dtype)
            ds_lp = ds.astype(q.dtype)
            dq = jax.lax.dot_general(
                ds_lp, k[:lim, :], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dqkv_ref[0, rows, h * dim:(h + 1) * dim] = (
                dq * sm_scale).astype(dqkv_ref.dtype)
            # dV[:lim] += P^T dO_r ; dK[:lim] += dS^T Q_scaled (carries scale)
            dv_parts.append(jax.lax.dot_general(
                p_lp, do_r, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
            dk_parts.append(jax.lax.dot_general(
                ds_lp, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))

        def _accumulate(parts):
            # parts[t] covers k rows [0, lim_t); sum overlapping prefixes
            # (n_sub is 1 or 2, so this is one concat at most).
            total = parts[-1]
            for part in parts[:-1]:
                r = part.shape[0]
                total = jnp.concatenate(
                    [total[:r, :] + part, total[r:, :]], axis=0)
            return total

        dqkv_ref[0, :, embed + h * dim:embed + (h + 1) * dim] = (
            _accumulate(dk_parts).astype(dqkv_ref.dtype))
        dqkv_ref[0, :, 2 * embed + h * dim:2 * embed + (h + 1) * dim] = (
            _accumulate(dv_parts).astype(dqkv_ref.dtype))


def _packed_n_sub(s_len: int, causal: bool) -> int:
    # Halves measured fastest on v5e at S=1024: 25% of matmul work skipped
    # with only one extra subtile loop iteration (quarters save 37.5% of
    # the FLOPs but lose more to loop overhead).
    return 2 if (causal and s_len % 2 == 0 and s_len >= 512) else 1


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _packed_flash(qkv, heads, sm_scale, causal):
    return _packed_fwd(qkv, heads, sm_scale, causal)[0]


def _packed_fwd(qkv, heads, sm_scale, causal):
    b, s_len, three_e = qkv.shape
    embed = three_e // 3
    dim = embed // heads
    n_sub = _packed_n_sub(s_len, causal)
    kernel = functools.partial(
        _packed_fwd_kernel, heads=heads, dim=dim, sm_scale=sm_scale,
        causal=causal, n_sub=n_sub)
    full = lambda i: (i, 0, 0)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, s_len, three_e), full)],
        out_specs=[pl.BlockSpec((1, s_len, embed), full),
                   pl.BlockSpec((1, heads, s_len), full)],
        out_shape=[jax.ShapeDtypeStruct((b, s_len, embed), qkv.dtype),
                   jax.ShapeDtypeStruct((b, heads, s_len), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=_on_cpu(),
    )(qkv)
    return out, (qkv, out, lse)


def _packed_bwd(heads, sm_scale, causal, residuals, do):
    qkv, out, lse = residuals
    b, s_len, three_e = qkv.shape
    embed = three_e // 3
    dim = embed // heads
    n_sub = _packed_n_sub(s_len, causal)
    kernel = functools.partial(
        _packed_bwd_kernel, heads=heads, dim=dim, sm_scale=sm_scale,
        causal=causal, n_sub=n_sub)
    full = lambda i: (i, 0, 0)
    dqkv = pl.pallas_call(
        kernel,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, s_len, three_e), full),
                  pl.BlockSpec((1, s_len, embed), full),
                  pl.BlockSpec((1, s_len, embed), full),
                  pl.BlockSpec((1, heads, s_len), full)],
        out_specs=pl.BlockSpec((1, s_len, three_e), full),
        out_shape=jax.ShapeDtypeStruct((b, s_len, three_e), qkv.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=_on_cpu(),
    )(qkv, out, do, lse)
    return (dqkv,)


_packed_flash.defvjp(_packed_fwd, _packed_bwd)


def flash_attention_packed(
    qkv: jax.Array,
    num_heads: int,
    *,
    causal: bool = False,
    sm_scale: Optional[float] = None,
) -> jax.Array:
    """Flash attention on a packed [B, S, 3*E] qkv projection → [B, S, E].

    The fastest path for standard transformer blocks: heads are sliced
    inside the kernel (no split/reshape/transpose ops in the graph) and the
    backward returns dqkv in the same packed layout. Sequences longer than
    ~2048 should use `flash_attention` (blockwise-pipelined) or ring
    attention instead — the packed kernels hold a full [S, S/2] score tile
    in VMEM."""
    b, s_len, three_e = qkv.shape
    if three_e % (3 * num_heads):
        raise ValueError(f"qkv last dim {three_e} not divisible by 3*heads")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(three_e // (3 * num_heads))
    return _packed_flash(qkv, num_heads, sm_scale, causal)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7)
)
def _flash_attention(q, k, v, sm_scale, causal, block_q, block_k, window=None):
    return _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, window)[0]


def _fold_heads(x):
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _unfold_heads(x, b, h):
    bh, s, d = x.shape
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, window=None):
    b, s, h, d = q.shape
    # Prescale q once on the [B,S,H,D] tensor (XLA fuses this into the
    # producing matmul's epilogue in real models): every kernel then skips
    # its per-S×S-element scale pass.
    q = (q.astype(jnp.float32) * sm_scale).astype(q.dtype)
    q_f, k_f, v_f = _fold_heads(q), _fold_heads(k), _fold_heads(v)
    out_f, lse = _flash_fwd_pallas(
        q_f, k_f, v_f, causal, block_q, block_k, interpret=_on_cpu(), window=window
    )
    out_f = checkpoint_name(out_f, RESIDUAL_NAMES[0])
    lse = checkpoint_name(lse[:, 0, :], RESIDUAL_NAMES[1])
    out = _unfold_heads(out_f, b, h)
    # Residuals stay in kernel layout (q_f prescaled): the backward reads
    # them directly instead of paying the fold transposes a second time.
    return out, (q_f, k_f, v_f, out_f, lse)


def _flash_bwd(sm_scale, causal, block_q, block_k, window, residuals, do):
    """Flash backward using the forward's per-row logsumexp — no S×S logits
    are ever materialized. Single-block sequences take the fused one-kernel
    path (as many key heads as query heads, no window); longer ones the
    two-kernel (dQ over k-blocks; dK/dV over q-blocks) scheme."""
    q_f, k_f, v_f, out_f, lse = residuals
    b, _, h, _ = do.shape
    do_f = _fold_heads(do)
    pad8 = lambda x: jnp.broadcast_to(x[:, None, :], (x.shape[0], 8, x.shape[1]))
    s_len = q_f.shape[1]
    plain = window is None and k_f.shape[0] == q_f.shape[0]
    if plain and min(block_q, s_len) == s_len == k_f.shape[1] == min(block_k, s_len):
        dq, dk, dv = _flash_bwd_fused_pallas(
            q_f, k_f, v_f, out_f, do_f, pad8(lse),
            sm_scale, causal, interpret=_on_cpu(),
        )
    else:
        # delta_i = sum_d dO_i · O_i (rowwise), f32.
        delta = jnp.sum(
            do_f.astype(jnp.float32) * out_f.astype(jnp.float32), axis=-1
        )
        dq, dk, dv = _flash_bwd_pallas(
            q_f, k_f, v_f, do_f, pad8(lse), pad8(delta),
            sm_scale, causal, block_q, block_k, interpret=_on_cpu(), window=window,
        )
    kv_heads = k_f.shape[0] // b
    return (
        _unfold_heads(dq, b, h),
        _unfold_heads(dk, b, kv_heads),
        _unfold_heads(dv, b, kv_heads),
    )


_flash_attention.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Flash attention. q [B, S, Hq, D], k and v [B, S, Hkv, D] with Hq a
    multiple of Hkv (query head j reads key head j // (Hq // Hkv)) →
    [B, S, Hq, D]. With `window` (causal only) the query at i sees the
    keys i - window < j <= i.

    Runs the Pallas kernels (interpret mode on CPU so tests exercise the
    same code path). Differentiable via dedicated Pallas backward kernels.

    Default block size: the largest power-of-two divisor of S up to 1024 —
    1024-token blocks measured fastest on v5e at d=64 (smaller blocks are
    per-step-overhead-bound; the [1024,1024] f32 score block sits within
    VMEM next to the pipeline buffers), while odd lengths like S=1536 fall
    back to a block that divides them. Explicit block sizes must divide S.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if block_q is None:
        block_q = _pick_block(q.shape[1])
    if block_k is None:
        block_k = _pick_block(k.shape[1])
    if q.shape[2] % k.shape[2] or k.shape != v.shape:
        raise ValueError(f"query heads {q.shape[2]} over key heads {k.shape[2]}")
    if window is not None and (not causal or window < 1 or q.shape[1] != k.shape[1]):
        raise ValueError("a window needs causal self-attention and at least one key")
    return _flash_attention(q, k, v, sm_scale, causal, block_q, block_k, window)


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    impl: str = "auto",
) -> jax.Array:
    """Dispatcher: pallas flash on TPU, reference elsewhere (impl='auto')."""
    if impl == "reference" or (impl == "auto" and _on_cpu() and q.shape[1] <= 1024):
        return mha_reference(q, k, v, causal=causal, sm_scale=sm_scale)
    if impl in ("auto", "flash"):
        return flash_attention(q, k, v, causal=causal, sm_scale=sm_scale)
    raise ValueError(f"Unknown attention impl {impl!r}")
