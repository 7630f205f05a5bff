"""Grouped-query attention in the paged kernel, and the promise that came
with it: with as many cached heads as query heads the kernel and the GPT-2
step programs trace to what they traced to before it could do anything
else.

The kernel (interpreted on the CPU) is compared with the XLA path
(`ops.paged_attention`, the oracle) in float32: both sum a row's 40 to 80
products in different orders, which reads 3e-7 at outputs about 1 wide;
the tolerance 2e-6 is under a hundredth of what bfloat16 operands would
move (4e-3).

The second half pins digests of the traced programs, taken on the commit
before this file existed: equal jaxprs are equal programs, so equal results
bit for bit, on any machine. A change to the kernel's multi-head path shows
here; whoever makes one on purpose re-pins the digest.
"""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm.model_runner import _StepPrograms
from ray_tpu.models.gpt import GPTConfig
from ray_tpu.ops import paged_flash
from ray_tpu.ops.attention import paged_attention
from ray_tpu.ops.paged_flash import paged_flash_attention

TOLERANCE = 2e-6


def _case(b, s, hq, hkv, d, bs=16, nb=4, layers=2, seed=0, lens=None):
    rng = np.random.RandomState(seed)
    n = 1 + b * nb
    f32 = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)  # noqa: E731
    q, nk, nv = f32(b, s, hq, d), f32(b, s, hkv, d), f32(b, s, hkv, d)
    kc, vc = f32(layers, n, bs, hkv * d), f32(layers, n, bs, hkv * d)
    tables = jnp.asarray(1 + np.arange(b * nb).reshape(b, nb), jnp.int32)
    if lens is None:
        lens = jnp.asarray(rng.randint(0, nb * bs - s, b), jnp.int32).at[0].set(0)
    return (
        (q, kc, vc, tables, jnp.asarray(lens, jnp.int32)),
        dict(new_k=nk, new_v=nv, layer=1, sm_scale=1 / 64),
    )


# (d) the kernel against the XLA path at num_kv_heads = num_heads and
# num_heads / 4, one fed token a slot (decode) and several (a chunk).
AS_BEFORE = [
    pytest.param(8, kv_heads, fed, head_dim, None, None,
                 id=f"{name}-{'decode' if fed == 1 else f'chunk{fed}'}-{head_dim}")
    for head_dim in (64, 128)
    for fed in (1, 8, 24)
    for kv_heads, name in ((8, "mha"), (2, "gqa4"))
]
# A fed chunk stacks a cached head's query heads along the rows of one
# product: the groups of Laguna's sliding and full layers and of granite's
# (9, 6, 4) at heads of 128, in q tiles of 16 tokens (no VMEM to spare), so
# 40 tokens are two and a half tiles and 48 are three. Slot 0 is a padded
# slot: nothing fed, nothing cached, a table of null blocks. The others
# start at context 0, end inside a compute block (128 tokens) and at the
# edge of one.
STACKED = [
    pytest.param(2 * group, 2, fed, 128, (0, 0, 168, 128), 16,
                 id=f"gqa{group}-chunk{fed}-tiles-of-16")
    for group in (9, 6, 4)
    for fed in (40, 48)
]
# Olmo Hybrid's full layers: as many cached heads as query heads, 30 of
# 128, so the hybrid runner's pool [layers, N, 16, 3840] and a decode step
# whose block-diagonal product has one row a cached head; a chunk in q tiles
# of 16 (two and a half of them) beside the padded slot.
ONE_QUERY_HEAD_A_CACHED_HEAD = [
    pytest.param(30, 30, 1, 128, None, None, id="mha30-decode-128"),
    pytest.param(30, 30, 1, 128, (0, 37, 168, 128), None, id="mha30-decode-128-padded-slot"),
    pytest.param(30, 30, 40, 128, (0, 0, 168, 128), 16, id="mha30-chunk40-tiles-of-16"),
]
# Falcon-H1's attention branch: 20 query heads over 4 cached heads of 128,
# so decode's block-diagonal product has rows of 5 (no power of two, and the
# one group size between granite's 4 and Laguna's 6) and a fed chunk stacks
# five q tiles a cached head; pools [layers, N, 16, 512].
FIVE_QUERY_HEADS_A_CACHED_HEAD = [
    pytest.param(20, 4, 1, 128, None, None, id="gqa5-decode-128"),
    pytest.param(20, 4, 1, 128, (0, 37, 168, 128), None, id="gqa5-decode-128-padded-slot"),
    pytest.param(20, 4, 40, 128, (0, 0, 168, 128), 16, id="gqa5-chunk40-tiles-of-16"),
    pytest.param(20, 4, 48, 128, (0, 0, 168, 128), 16, id="gqa5-chunk48-tiles-of-16"),
]


@pytest.mark.parametrize(
    "heads,kv_heads,fed,head_dim,contexts,q_tile",
    AS_BEFORE + STACKED + ONE_QUERY_HEAD_A_CACHED_HEAD + FIVE_QUERY_HEADS_A_CACHED_HEAD,
)
def test_kernel_matches_the_xla_path(
    monkeypatch, heads, kv_heads, fed, head_dim, contexts, q_tile
):
    if q_tile is not None:
        monkeypatch.setattr(paged_flash, "_Q_TILE_VMEM_BYTES", 0)
        assert paged_flash.q_tile(fed, heads, kv_heads, head_dim, 4, 16, 16, 4) == q_tile
    slots = 3 if contexts is None else len(contexts)
    args, kwargs = _case(
        slots, fed, heads, kv_heads, head_dim, seed=fed + kv_heads, lens=contexts,
        nb=4 if contexts is None else 16,
    )
    if contexts is not None:  # the padded slot
        q, kc, vc, tables, lens = args
        args = (q.at[0].set(0), kc, vc, tables.at[0].set(0), lens)
        kwargs.update({k: kwargs[k].at[0].set(0) for k in ("new_k", "new_v")})
    want = paged_attention(*args, **kwargs)
    got = paged_flash_attention(*args, **kwargs, num_kv_heads=kv_heads)
    assert got.shape == want.shape == (slots, fed, heads, head_dim)
    assert float(jnp.abs(got - want).max()) < TOLERANCE
    if contexts is not None:
        assert not np.asarray(got[0]).any()  # exact zeros


def test_one_cached_head_serves_every_query_head():
    args, kwargs = _case(2, 1, 4, 1, 128, seed=9)
    got = paged_flash_attention(*args, **kwargs)
    assert float(jnp.abs(got - paged_attention(*args, **kwargs)).max()) < TOLERANCE


@pytest.mark.parametrize("impl", ["xla", "kernel"])
def test_grouped_query_is_multi_head_over_repeated_heads(impl):
    """The kernel, fed a chunk, takes a cached head's four query heads as
    the rows of one product and a head of its own as one q tile: row by
    row the same arithmetic, so the same bits."""
    (q, kc, vc, tables, lens), kwargs = _case(2, 8, 8, 2, 64, seed=3)
    op = paged_attention if impl == "xla" else paged_flash_attention
    grouped = op(q, kc, vc, tables, lens, **kwargs)

    def repeat(pool):  # [L, N, bs, 2 * d] -> [L, N, bs, 8 * d]
        heads = pool.reshape(pool.shape[:3] + (2, 64))
        return jnp.repeat(heads, 4, axis=3).reshape(pool.shape[:3] + (8 * 64,))

    kwargs["new_k"], kwargs["new_v"] = (
        jnp.repeat(kwargs[k], 4, axis=2) for k in ("new_k", "new_v")
    )
    full = op(q, repeat(kc), repeat(vc), tables, lens, **kwargs)
    if impl == "kernel":
        assert np.array_equal(np.asarray(grouped), np.asarray(full))
    assert float(jnp.abs(grouped - full).max()) < TOLERANCE


def test_refused_shapes():
    (q, kc, vc, tables, lens), kwargs = _case(2, 1, 8, 2, 64)
    with pytest.raises(ValueError, match="num_kv_heads"):
        paged_flash_attention(q, kc, vc, tables, lens, **kwargs, num_kv_heads=4)
    with pytest.raises(ValueError, match="multiple"):
        paged_flash_attention(
            q[:, :, :7], kc, vc, tables, lens, **kwargs, num_kv_heads=2
        )
    with pytest.raises(ValueError, match="int8"):
        paged_flash_attention(
            q, kc.astype(jnp.int8), vc.astype(jnp.int8), tables, lens, **kwargs
        )


# ---------------- num_kv_heads == num_heads: the programs of before ----------------


def _digest(jaxpr) -> str:
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))
    text = re.sub(r" at [^\s:]+\.py:\d+", "", text)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _kernel_jaxpr(b, s, h, d=16, bs=8, nb=4, dtype=jnp.float32):
    n = 1 + b * nb
    sds = jax.ShapeDtypeStruct
    fed, pool = sds((b, s, h, d), dtype), sds((2, n, bs, h * d), dtype)
    args = (fed, pool, pool, sds((b, nb), jnp.int32), sds((b,), jnp.int32), fed, fed)

    def fn(q, kc, vc, tables, lens, nk, nv):
        return paged_flash_attention(
            q, kc, vc, tables, lens, new_k=nk, new_v=nv, layer=1, interpret=True
        )

    return jax.make_jaxpr(fn)(*args)


def _gpt_program_jaxprs():
    cfg = GPTConfig(
        vocab_size=512, num_layers=2, num_heads=4, embed_dim=64, mlp_ratio=4,
        max_seq_len=128, dtype=jnp.float32,
    )
    programs = _StepPrograms(cfg, 8, "pallas", jnp.float32, 1)
    params = jax.eval_shape(
        programs.model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )
    pool = jax.ShapeDtypeStruct((2, 32, 8, 64), jnp.float32)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    return {
        "gpt_decode_program": jax.make_jaxpr(programs._decode_step)(
            params, pool, pool, None, None, i32(4), i32(4), i32(4, 16), i32(4)
        ),
        "gpt_suffix_program": jax.make_jaxpr(programs._prefill_suffix_step)(
            params, pool, pool, None, None, i32(1, 16), i32(16), i32(), i32()
        ),
    }


# Taken on commit f4535eb (PR 31), the parent of the PR that added
# num_kv_heads, with the function above.
PINNED = {
    "decode_4_heads": "f35f70f0dfb08fa2",
    "suffix_4_heads": "7ac8a4becb355360",
    "decode_heads_of_128": "1e3d583d0f4eb18c",
    "suffix_heads_of_128": "f7568ba10f95a7eb",
    "gpt_decode_program": "48e837bf5aa3aefb",
    "gpt_suffix_program": "82135a7661c46a57",
    # Taken on commit 939d32a, the parent of PR 51, which sized a decode
    # walk's compute block in bytes: Olmo Hybrid's 30 heads of 128 in bf16
    # over its cell's 200-entry tables keep their 128 tokens, so its decode
    # kernel is the parent's.
    "decode_olmo_30_heads_of_128": "e94ac298a13bbba5",
}
KERNELS = {
    "decode_4_heads": (3, 1, 4),
    "suffix_4_heads": (2, 8, 4),
    "decode_heads_of_128": (2, 1, 2, 128),
    "suffix_heads_of_128": (1, 16, 2, 128),
    "decode_olmo_30_heads_of_128": (2, 1, 30, 128, 16, 200, jnp.bfloat16),
}


@pytest.mark.parametrize("name", list(KERNELS))
def test_multi_head_kernel_traces_as_before(name):
    assert _digest(_kernel_jaxpr(*KERNELS[name])) == PINNED[name]


@pytest.mark.parametrize("name", ["gpt_decode_program", "gpt_suffix_program"])
def test_gpt2_step_programs_trace_as_before(name):
    assert _digest(_gpt_program_jaxprs()[name]) == PINNED[name]


def test_a_grouped_kernel_traces_differently():
    """The digest sees the kernel's body: it is no constant."""
    (q, kc, vc, tables, lens), kwargs = _case(3, 1, 4, 2, 16, bs=8)
    grouped = jax.make_jaxpr(
        lambda *a: paged_flash_attention(*a, **kwargs, interpret=True)
    )(q, kc, vc, tables, lens)
    assert _digest(grouped) != PINNED["decode_4_heads"]
