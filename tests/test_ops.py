"""Attention kernel correctness vs the pure-JAX reference, on CPU (pallas
interpret mode) and the 8-device virtual mesh for ring attention."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import (
    dequantize_kv,
    flash_attention,
    mha_reference,
    paged_attention,
    paged_attention_impl,
    paged_flash_attention,
    quantize_kv,
    ring_self_attention,
)
from ray_tpu.parallel import MeshSpec


def _rand_qkv(key, b=2, s=256, h=4, d=64, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, d), dtype)
    k = jax.random.normal(kk, (b, s, h, d), dtype)
    v = jax.random.normal(kv, (b, s, h, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    q, k, v = _rand_qkv(jax.random.PRNGKey(0))
    expected = mha_reference(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-5)


def test_flash_grad_matches_reference():
    q, k, v = _rand_qkv(jax.random.PRNGKey(1), s=128)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block_q=64, block_k=64) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr), atol=5e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_reference(causal):
    """Sequence sharded 8 ways over sp; result must equal full attention."""
    mesh = MeshSpec(sp=8).build()
    q, k, v = _rand_qkv(jax.random.PRNGKey(2), b=1, s=256, h=2, d=32)
    expected = mha_reference(q, k, v, causal=causal)
    got = ring_self_attention(mesh, q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-5)


def test_ring_attention_with_dp_and_sp():
    mesh = MeshSpec(dp=2, sp=4).build()
    q, k, v = _rand_qkv(jax.random.PRNGKey(3), b=4, s=128, h=2, d=32)
    expected = mha_reference(q, k, v, causal=True)
    got = ring_self_attention(mesh, q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_packed_flash_matches_reference(causal):
    """Packed-QKV kernel ([B,S,3E] in, heads sliced in-kernel) vs reference,
    forward and backward."""
    from ray_tpu.ops.flash_attention import flash_attention_packed

    B, S, H, D = 2, 256, 4, 32
    E = H * D
    qkv = jax.random.normal(jax.random.PRNGKey(7), (B, S, 3 * E))

    def ref(qkv):
        q, k, v = jnp.split(qkv, 3, axis=-1)
        return mha_reference(
            q.reshape(B, S, H, D), k.reshape(B, S, H, D),
            v.reshape(B, S, H, D), causal=causal,
        ).reshape(B, S, E)

    out = flash_attention_packed(qkv, H, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref(qkv)), atol=2e-5
    )
    g = jax.grad(lambda x: jnp.sum(flash_attention_packed(x, H, causal=causal) ** 2))(qkv)
    g_ref = jax.grad(lambda x: jnp.sum(ref(x) ** 2))(qkv)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=5e-3, atol=5e-3)


def test_packed_flash_single_subtile_odd_seq():
    """Sequence lengths that defeat the half-split subtiling (odd multiples
    of the tile) still go through the n_sub=1 path correctly."""
    from ray_tpu.ops.flash_attention import flash_attention_packed

    B, S, H, D = 1, 384, 2, 32
    E = H * D
    qkv = jax.random.normal(jax.random.PRNGKey(8), (B, S, 3 * E))

    def ref(qkv):
        q, k, v = jnp.split(qkv, 3, axis=-1)
        return mha_reference(
            q.reshape(B, S, H, D), k.reshape(B, S, H, D),
            v.reshape(B, S, H, D), causal=True,
        ).reshape(B, S, E)

    out = flash_attention_packed(qkv, H, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(qkv)), atol=2e-5)


# ---------------- fused paged attention (serving hot path) ----------------

# The tests below state the mathematics in per-layer pools [N, bs, H, D]
# (scales [N, bs, H]); the ops take the pools as the runner stores them,
# [L, N, bs, H*D] (scales [L, N, bs, H]) read at `layer`. `_stored` puts a
# per-layer pool at layer LAYER of three, with loud content in the other
# two, so an op that read the wrong layer (or layer 0 always) fails every
# comparison here.
LAYER = 1


def _stored(pool):
    if pool is None:
        return None
    flat = pool.reshape(pool.shape[0], pool.shape[1], -1)
    loud = jnp.full_like(flat, 77)
    return jnp.stack([loud, flat, -loud])


def _stored_call(op, q, k_cache, v_cache, *args, k_scale=None, v_scale=None,
                 **kwargs):
    return op(
        q, _stored(k_cache), _stored(v_cache), *args, layer=LAYER,
        k_scale=_stored(k_scale), v_scale=_stored(v_scale), **kwargs,
    )


def _paged_ref(*args, **kwargs):
    return _stored_call(paged_attention, *args, **kwargs)


def _paged_kernel(*args, **kwargs):
    return _stored_call(paged_flash_attention, *args, **kwargs)


def _paged_dispatch(*args, **kwargs):
    return _stored_call(paged_attention_impl, *args, **kwargs)



def _paged_case(seed, b, s, h=4, d=16, num_blocks=None, bs=4, nb=4):
    """Random paged-attention inputs: pools, 0-padded tables, new K/V."""
    if num_blocks is None:
        num_blocks = b * nb + 1  # enough distinct non-null blocks per row
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k_cache = jnp.asarray(rng.randn(num_blocks, bs, h, d), jnp.float32)
    v_cache = jnp.asarray(rng.randn(num_blocks, bs, h, d), jnp.float32)
    new_k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    new_v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    # Distinct non-null blocks per row, 0-padded past each row's blocks.
    tables = np.zeros((b, nb), np.int32)
    perm = rng.permutation(np.arange(1, num_blocks))
    for i in range(b):
        tables[i] = perm[i * nb : (i + 1) * nb]
    return q, k_cache, v_cache, jnp.asarray(tables), new_k, new_v


# Geometries for the kernel's compute blocks (128 cached tokens: 8 table
# entries of 16, 16 of 8). H*D = 128 is whole lanes, so the kernel copies the
# blocks out of the pools itself; 48 lanes are not, and XLA gathers them.
_COPIED = dict(h=4, d=32)
_GATHERED = dict(h=3, d=16)
_BLOCK_EDGES = (0, 1, 127, 128, 129, 256)


@pytest.mark.parametrize(
    "ctx_lens, geometry",
    [
        ((9, 2, 16, 0), {}),    # partial block / tiny / max / empty padded slot
        ((8, 4, 12, 16), {}),   # block boundaries and full table
        # A compute block's edges, a full 1,024-token table, idle slots
        # between live ones.
        ((0, 1, 127, 128, 129, 1024, 0, 300), dict(bs=16, nb=64, **_COPIED)),
        # 16 entries a compute block; 5 tokens reach one of them.
        ((129, 0, 256, 5, 0, 128), dict(bs=8, nb=32, **_COPIED)),
        (_BLOCK_EDGES, dict(bs=16, nb=16, **_GATHERED)),
        (_BLOCK_EDGES, dict(bs=8, nb=32, **_GATHERED)),
        # A table shorter than a compute block, through the kernel's copies.
        ((9, 0, 16, 3), _COPIED),
    ],
)
def test_paged_flash_decode_matches_reference(ctx_lens, geometry):
    """Decode shape (S == 1): the fused kernel walking the block table must
    equal the XLA gather+softmax reference at every context length —
    including 0 (an idle padded slot attending only its own new token),
    exact block boundaries, and the full table."""
    q, kc, vc, tables, nk, nv = _paged_case(
        0, b=len(ctx_lens), s=1, **geometry
    )
    lens = jnp.asarray(ctx_lens, jnp.int32)
    want = _paged_ref(q, kc, vc, tables, lens, new_k=nk, new_v=nv)
    got = _paged_kernel(q, kc, vc, tables, lens, new_k=nk, new_v=nv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_paged_flash_partial_prefill_matches_reference():
    """Partial prefill (S > 1): paged over the cached prefix, causal among
    the suffix tokens riding along as new_k/new_v."""
    q, kc, vc, tables, nk, nv = _paged_case(1, b=3, s=5)
    lens = jnp.asarray([9, 0, 16], jnp.int32)
    want = _paged_ref(q, kc, vc, tables, lens, new_k=nk, new_v=nv)
    got = _paged_kernel(q, kc, vc, tables, lens, new_k=nk, new_v=nv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    # And against per-position dense attention (the oracle's own oracle).
    bsz = kc.shape[1]
    nb = tables.shape[1]
    for i, ctx in enumerate(ctx for ctx in (9, 0, 16)):
        k_seq = kc[tables[i]].reshape(1, nb * bsz, *kc.shape[2:])[:, :ctx]
        v_seq = vc[tables[i]].reshape(1, nb * bsz, *vc.shape[2:])[:, :ctx]
        for j in range(q.shape[1]):
            k_full = jnp.concatenate([k_seq, nk[i : i + 1, : j + 1]], axis=1)
            v_full = jnp.concatenate([v_seq, nv[i : i + 1, : j + 1]], axis=1)
            dense = mha_reference(q[i : i + 1, j : j + 1], k_full, v_full)
            np.testing.assert_allclose(
                np.asarray(got[i : i + 1, j : j + 1]),
                np.asarray(dense),
                atol=1e-5,
            )


@pytest.mark.parametrize(
    "fed, ctx_lens, geometry",
    [
        (40, (9, 0, 16), {}),
        # Two q tiles over contexts that end inside a compute block: each
        # tile walks the cache again, behind the other's last block.
        (20, (130, 0, 255), dict(bs=16, nb=16, **_COPIED)),
        (20, (130, 0, 255), dict(bs=8, nb=32, **_GATHERED)),
    ],
)
@pytest.mark.parametrize("quantized", [False, True])
def test_paged_flash_q_tiles_match_reference(
    monkeypatch, quantized, fed, ctx_lens, geometry
):
    """More fed tokens than one q tile holds (the kernel tiles S so any
    bucket fits VMEM): with no VMEM to spare the tile is 16 tokens, so 40
    tokens are three tiles, the last zero-padded. Every row still attends
    its cached prefix and the new tokens below its diagonal — across tile
    boundaries."""
    from ray_tpu.ops import paged_flash

    monkeypatch.setattr(paged_flash, "_Q_TILE_VMEM_BYTES", 0)
    q, kc, vc, tables, nk, nv = _paged_case(11, b=3, s=fed, **geometry)
    lens = jnp.asarray(ctx_lens, jnp.int32)
    scales = {}
    if quantized:
        kc, ks = quantize_kv(kc)
        vc, vs = quantize_kv(vc)
        scales = {"k_scale": ks, "v_scale": vs}
    want = _paged_ref(
        q, kc, vc, tables, lens, new_k=nk, new_v=nv, **scales
    )
    got = _paged_kernel(
        q, kc, vc, tables, lens, new_k=nk, new_v=nv, **scales
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_paged_flash_null_padded_table_ignored():
    """Rows whose table is padded with the null block past their real
    blocks must not read it: mutating block 0 cannot change the output."""
    q, kc, vc, tables, nk, nv = _paged_case(2, b=2, s=1)
    lens = jnp.asarray([6, 10], jnp.int32)
    out1 = _paged_kernel(q, kc, vc, tables, lens, new_k=nk, new_v=nv)
    kc2 = kc.at[0].set(1e6)
    vc2 = vc.at[0].set(-1e6)
    out2 = _paged_kernel(q, kc2, vc2, tables, lens, new_k=nk, new_v=nv)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


def test_paged_attention_empty_context_returns_zeros():
    """Regression: context_lens == 0 with no new tokens used to softmax
    over all-NEG_INF logits — uniform weights over garbage gathered from
    the null block. Masked/empty slots must return exact zeros."""
    rng = np.random.RandomState(3)
    kc = jnp.asarray(rng.randn(6, 4, 2, 8), jnp.float32)
    vc = jnp.asarray(1e3 * rng.randn(6, 4, 2, 8), jnp.float32)  # loud garbage
    q = jnp.asarray(rng.randn(2, 1, 2, 8), jnp.float32)
    tables = jnp.asarray([[1, 2], [3, 0]], jnp.int32)
    lens = jnp.asarray([0, 5], jnp.int32)
    out = _paged_ref(q, kc, vc, tables, lens)
    assert np.all(np.asarray(out[0]) == 0.0)  # exact zeros, not garbage
    assert np.any(np.asarray(out[1]) != 0.0)  # live rows unaffected


@pytest.mark.parametrize(
    "fed, ctx_lens, geometry",
    [
        (2, (9, 16, 0), {}),
        (1, _BLOCK_EDGES, dict(bs=16, nb=16, **_COPIED)),
        (3, _BLOCK_EDGES, dict(bs=8, nb=32, **_COPIED)),
    ],
)
def test_paged_flash_int8_matches_int8_reference(fed, ctx_lens, geometry):
    """int8 KV: the kernel's fused dequant (scales folded into the score /
    weight matrices) must match the reference dequantizing gathered pages
    — same quantized inputs, near-identical outputs."""
    q, kc, vc, tables, nk, nv = _paged_case(
        4, b=len(ctx_lens), s=fed, **geometry
    )
    lens = jnp.asarray(ctx_lens, jnp.int32)
    kq, ks = quantize_kv(kc)
    vq, vs = quantize_kv(vc)
    assert kq.dtype == jnp.int8 and ks.shape == kc.shape[:-1]
    want = _paged_ref(
        q, kq, vq, tables, lens, new_k=nk, new_v=nv, k_scale=ks, v_scale=vs
    )
    got = _paged_kernel(
        q, kq, vq, tables, lens, new_k=nk, new_v=nv, k_scale=ks, v_scale=vs
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    # And the quantized result stays within quantization tolerance of the
    # exact f32 computation.
    exact = _paged_ref(q, kc, vc, tables, lens, new_k=nk, new_v=nv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exact), atol=0.05)


@pytest.mark.parametrize("variant", ["f32", "bf16", "int8"])
def test_paged_flash_verify_shape_matches_reference(variant):
    """Speculative-decoding verify shape: [B slots, S = k+1 fed tokens]
    multi-query paged attention — paged over each slot's committed prefix,
    causal among the fed (last + proposed) tokens — must agree between the
    fused kernel and the XLA reference at the same context boundaries the
    decode parity suite covers: 0 (no committed prefix), a block edge, the
    full table, and a mid-block length, in bf16 and int8 as well as f32.
    This is the program the engine's verify phase compiles, so it gets the
    same oracle coverage as decode."""
    s = 5  # num_speculative_tokens=4 -> 1 + 4 fed tokens
    q, kc, vc, tables, nk, nv = _paged_case(8, b=4, s=s)
    lens = jnp.asarray([0, 8, 16, 9], jnp.int32)
    kwargs = {}
    atol = 1e-5
    if variant == "bf16":
        q, kc, vc, nk, nv = (
            x.astype(jnp.bfloat16) for x in (q, kc, vc, nk, nv)
        )
        atol = 5e-2  # bf16 storage/accumulation rounding
    elif variant == "int8":
        kc, ks = quantize_kv(kc)
        vc, vs = quantize_kv(vc)
        kwargs = dict(k_scale=ks, v_scale=vs)
        atol = 2e-5
    want = _paged_ref(
        q, kc, vc, tables, lens, new_k=nk, new_v=nv, **kwargs
    )
    got = _paged_kernel(
        q, kc, vc, tables, lens, new_k=nk, new_v=nv, **kwargs
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=atol
    )
    # Causality across the fed tokens: mutating the LAST fed token's K/V
    # must not change any earlier fed position's output (the engine
    # depends on this to accept a prefix while rejecting the tail).
    nk2 = nk.at[:, -1].set(jnp.asarray(7.0, nk.dtype))
    nv2 = nv.at[:, -1].set(jnp.asarray(-7.0, nv.dtype))
    got2 = _paged_kernel(
        q, kc, vc, tables, lens, new_k=nk2, new_v=nv2, **kwargs
    )
    np.testing.assert_array_equal(
        np.asarray(got[:, : s - 1]), np.asarray(got2[:, : s - 1])
    )


def test_quantize_kv_round_trip():
    """Per-token int8 quantization: sub-1% round-trip error, exact-zero
    preservation, and int8 range discipline."""
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(7, 3, 4, 32) * 3.0, jnp.float32)
    qv, sc = quantize_kv(x)
    assert qv.dtype == jnp.int8 and sc.shape == (7, 3, 4)
    assert int(jnp.max(jnp.abs(qv.astype(jnp.int32)))) <= 127
    back = dequantize_kv(qv, sc)
    err = np.abs(np.asarray(back) - np.asarray(x))
    amax = np.abs(np.asarray(x)).max(axis=-1, keepdims=True)
    assert np.all(err <= amax / 127.0 + 1e-6)  # half-step + scale rounding
    z, zs = quantize_kv(jnp.zeros((2, 1, 4, 8)))
    assert np.all(np.asarray(z) == 0)
    assert np.all(np.asarray(dequantize_kv(z, zs)) == 0.0)


def test_paged_flash_requires_new_kv():
    q, kc, vc, tables, nk, nv = _paged_case(6, b=1, s=1)
    lens = jnp.asarray([4], jnp.int32)
    with pytest.raises(ValueError, match="new_k/new_v"):
        _paged_kernel(q, kc, vc, tables, lens, new_k=None, new_v=None)
    # Scales with non-int8 pools must raise in BOTH implementations —
    # silently dropping (kernel) or applying (reference) them would make
    # impl='auto' platform-dependent.
    _, ks = quantize_kv(kc)
    _, vs = quantize_kv(vc)
    kq, _ = quantize_kv(kc)
    vq, _ = quantize_kv(vc)
    for op in (_paged_kernel, _paged_ref):
        with pytest.raises(ValueError, match="non-int8"):
            op(
                q, kc, vc, tables, lens, new_k=nk, new_v=nv,
                k_scale=ks, v_scale=vs,
            )
        # ...and the mirror: int8 pools without scales.
        with pytest.raises(ValueError, match="require k_scale/v_scale"):
            op(q, kq, vq, tables, lens, new_k=nk, new_v=nv)


@pytest.mark.parametrize("variant", ["bf16", "int8"])
@pytest.mark.parametrize("fed", [1, 5])
@pytest.mark.parametrize("head_dim", [16, 64, 128])
@pytest.mark.parametrize("heads", [3, 4])
def test_paged_flash_lane_sliced_heads_match_reference(
    heads, head_dim, fed, variant
):
    """The kernel reads head h as lanes h*D:(h+1)*D of a [bs, H*D] block
    of the stored pool, at a layer other than 0: heads that share a lane
    tile (16, and 64 at odd heads) and heads that are a whole tile (128),
    decode (S == 1) and suffix prefill (S > 1), bf16 and int8. Four heads
    of 64 are two whole lane tiles, which S > 1 walks in a loop."""
    q, kc, vc, tables, nk, nv = _paged_case(
        21, b=3, s=fed, h=heads, d=head_dim
    )
    lens = jnp.asarray([9, 0, 16], jnp.int32)
    q, nk, nv = (x.astype(jnp.bfloat16) for x in (q, nk, nv))
    if variant == "int8":
        kc, ks = quantize_kv(kc)
        vc, vs = quantize_kv(vc)
        scales = dict(k_scale=ks, v_scale=vs)
    else:
        kc, vc = kc.astype(jnp.bfloat16), vc.astype(jnp.bfloat16)
        scales = {}
    want = _paged_ref(q, kc, vc, tables, lens, new_k=nk, new_v=nv, **scales)
    got = _paged_kernel(q, kc, vc, tables, lens, new_k=nk, new_v=nv, **scales)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=5e-2
    )


@pytest.mark.parametrize("op", ["reference", "kernel"])
def test_paged_ops_reject_pools_not_in_the_stored_form(op):
    """A per-layer [N, bs, H, D] pool (the form before the pools were
    stored lane-dense) is refused by name, not read as four layers."""
    q, kc, vc, tables, nk, nv = _paged_case(22, b=1, s=1)
    fn = paged_attention if op == "reference" else paged_flash_attention
    with pytest.raises(ValueError, match="stored form"):
        fn(q, kc, vc, tables, jnp.asarray([4], jnp.int32), new_k=nk, new_v=nv)


def test_paged_attention_impl_dispatcher():
    """impl='auto' takes the reference on CPU; 'pallas' forces the kernel
    (interpret mode here); both agree, unknown impls are rejected."""
    q, kc, vc, tables, nk, nv = _paged_case(7, b=2, s=1)
    lens = jnp.asarray([6, 3], jnp.int32)
    auto = _paged_dispatch(
        q, kc, vc, tables, lens, new_k=nk, new_v=nv, impl="auto"
    )
    forced = _paged_dispatch(
        q, kc, vc, tables, lens, new_k=nk, new_v=nv, impl="pallas"
    )
    np.testing.assert_allclose(np.asarray(forced), np.asarray(auto), atol=1e-5)
    with pytest.raises(ValueError, match="impl"):
        _paged_dispatch(
            q, kc, vc, tables, lens, new_k=nk, new_v=nv, impl="cuda"
        )


def test_flash_attention_backward_matches_reference():
    """Pallas bwd kernels vs autodiff through the reference (both causal and
    bidirectional)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops.attention import mha_reference
    from ray_tpu.ops.flash_attention import flash_attention

    B, S, H, D = 2, 256, 2, 64
    mk = lambda s: jax.random.normal(jax.random.PRNGKey(s), (B, S, H, D))
    q, k, v = mk(0), mk(1), mk(2)
    for causal in (False, True):
        g_ref = jax.grad(
            lambda q, k, v: jnp.sum(mha_reference(q, k, v, causal=causal) ** 2),
            argnums=(0, 1, 2),
        )(q, k, v)
        g_fl = jax.grad(
            lambda q, k, v: jnp.sum(
                flash_attention(q, k, v, causal=causal, block_q=128, block_k=128) ** 2
            ),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b in zip(g_ref, g_fl):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-3
            )
