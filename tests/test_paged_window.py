"""The lower bound on what a query reads (`window`) in both paged attention
implementations, against a masked dense attention, and the pin that
`window=None` leaves today's callers' programs as they were.

A slot's cached context is laid out through a block table whose entries
below the window are the null block, as the window cache class frees them,
and the null block holds large finite garbage: an implementation that read
it unmasked would be off by orders of magnitude. Windows start inside a
compute block (128 cached tokens), at its edge and across several, for
decode (one fed token a slot) and for chunks (several, one or more q tiles),
at 6 and at 4 query heads over 2 cached ones (the groups of Laguna's sliding
and full layers, 9 and 6 over 8, in small) and, for chunks, at 18 and 12
(those groups themselves).

Tolerance: 3e-6 absolute on outputs of order 1, float32 throughout; the
implementations differ from the dense softmax in the order of sums only.

The pin: sha256 of the lowered text (StableHLO, without the result names) of
the toy step programs of GPT-2 and granite, both attention implementations,
recorded on the parent commit of the PR that added `window` (e83482d); the
two chunk programs of granite under the kernel were re-recorded at PR 38,
which changed what a grouped model's chunk traces and nothing else here. A
change that alters what those callers trace shows here; a deliberate one
re-records the table (the helper prints it:
`PYTHONPATH=.:tests python tests/test_paged_window.py`).
"""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import paged_attention
from ray_tpu.ops.paged_flash import paged_attention_impl, paged_flash_attention

TOLERANCE = 3e-6
BS, NB, D, HKV = 16, 24, 128, 2


def dense(q, k_all, v_all, ctx, window):
    """q [S, H, d] at positions ctx .. ctx + S - 1 over k_all / v_all
    [ctx + S, Hkv, d], masked as the model defines a window."""
    s_len, heads, d = q.shape
    group = heads // k_all.shape[1]
    k, v = np.repeat(k_all, group, axis=1), np.repeat(v_all, group, axis=1)
    scores = np.einsum("qhd,khd->hqk", q, k) / np.sqrt(d)
    q_pos = ctx + np.arange(s_len)[:, None]
    k_pos = np.arange(ctx + s_len)[None, :]
    seen = k_pos <= q_pos
    if window is not None:
        seen &= q_pos - k_pos < window
    scores = np.where(seen[None], scores, -1e30)
    weights = np.exp(scores - scores.max(-1, keepdims=True))
    weights /= weights.sum(-1, keepdims=True)
    return np.einsum("hqk,khd->qhd", weights, v)


def case(s_len, contexts, window, heads, seed=0):
    """Inputs of both implementations and the dense answer."""
    rng = np.random.default_rng(seed)
    b = len(contexts)
    blocks = 1 + b * NB
    pools = [rng.standard_normal((2, blocks, BS, HKV * D)).astype(np.float32) for _ in range(2)]
    for pool in pools:
        pool[:, 0] = 1e4  # the null block: finite, and never to be weighed
    tables = np.zeros((b, NB), np.int32)
    q = rng.standard_normal((b, s_len, heads, D)).astype(np.float32)
    new_k, new_v = (
        rng.standard_normal((b, s_len, HKV, D)).astype(np.float32) for _ in range(2)
    )
    want = []
    for i, ctx in enumerate(contexts):
        ids = 1 + i * NB + np.arange(NB)
        used = -(-ctx // BS)
        tables[i, :used] = ids[:used]
        if window is not None:
            # Freed as the window class frees: blocks no query at position
            # ctx or later sees.
            tables[i, : max(ctx - window + 1, 0) // BS] = 0
        k_all, v_all = (
            np.concatenate([pool[1, ids].reshape(NB * BS, HKV, D)[:ctx], new[i]])
            for pool, new in zip(pools, (new_k, new_v))
        )
        want.append(dense(q[i], k_all, v_all, ctx, window))
    args = tuple(jnp.asarray(x) for x in (q, *pools, tables, np.asarray(contexts, np.int32)))
    return args, dict(new_k=jnp.asarray(new_k), new_v=jnp.asarray(new_v), layer=1), np.stack(want)


DECODE = [
    # (contexts of the slots, window)
    ([0, 5, 130, 300, 383], 200),   # starts inside a compute block
    ([100, 128, 129, 255, 256, 257], 128),  # at the edge of one
    ([383, 370, 40], 20),           # across: two whole blocks skipped
    ([40, 300], 1),                 # the token itself alone
    ([0, 77, 383], 1000),           # longer than any context: nothing cut
]
CHUNKS = [
    # (fed tokens, contexts, window)
    (48, [0, 100, 300], 100),
    (48, [0, 129, 336], 20),
    (160, [0, 64, 200], 130),       # two q tiles: the second's horizon is higher
    (160, [224], 16),               # new-token tiles wholly outside the window
    # A q tile and a half under a window that crosses the q tiles and two
    # compute blocks, the blocks below it null.
    (200, [0, 300], 150),
]


@pytest.mark.parametrize("heads", [6, 4])
@pytest.mark.parametrize("contexts,window", DECODE)
def test_decode_inside_the_window(contexts, window, heads):
    args, kw, want = case(1, contexts, window, heads)
    xla = paged_attention(*args, **kw, window=window)
    kernel = paged_flash_attention(*args, **kw, num_kv_heads=HKV, window=window)
    assert np.abs(np.asarray(xla) - want).max() < TOLERANCE
    assert np.abs(np.asarray(kernel) - want).max() < TOLERANCE


# A chunk stacks the query heads of a cached head along the rows of one
# product, each row masked by its own token's position: Laguna's own groups
# (9 and 6) beside the small ones.
@pytest.mark.parametrize("heads", [18, 12, 6, 4])
@pytest.mark.parametrize("s_len,contexts,window", CHUNKS)
def test_chunk_inside_the_window(s_len, contexts, window, heads):
    args, kw, want = case(s_len, contexts, window, heads)
    xla = paged_attention(*args, **kw, window=window)
    kernel = paged_flash_attention(*args, **kw, num_kv_heads=HKV, window=window)
    assert np.abs(np.asarray(xla) - want).max() < TOLERANCE
    assert np.abs(np.asarray(kernel) - want).max() < TOLERANCE


@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_a_window_of_another_length_is_another_answer(impl):
    args, kw, want = case(1, [300, 383], 200, 6)
    other = paged_attention_impl(*args, **kw, impl=impl, window=216)
    assert np.abs(np.asarray(other) - want).max() > 1000 * TOLERANCE
    same = paged_attention_impl(*args, **kw, impl=impl, window=200)
    assert np.abs(np.asarray(same) - want).max() < TOLERANCE


def test_no_window_is_the_call_it_was():
    args, kw, want = case(48, [0, 100, 300], None, 6)
    for out in (
        paged_attention(*args, **kw),
        paged_flash_attention(*args, **kw, num_kv_heads=HKV),
        paged_attention_impl(*args, **kw, impl="pallas", window=None),
    ):
        assert np.abs(np.asarray(out) - want).max() < TOLERANCE


def test_refusals():
    args, kw, _ = case(1, [40], 8, 6)
    with pytest.raises(ValueError):
        paged_flash_attention(*args, **kw, num_kv_heads=HKV, window=0)
    q, k, v, tables, lens = args
    int8 = k.astype(jnp.int8)
    scale = jnp.ones(k.shape[:3] + (HKV,), jnp.bfloat16)
    with pytest.raises(ValueError):
        paged_flash_attention(
            q[:, :, :HKV], int8, int8, tables, lens, **kw, k_scale=scale,
            v_scale=scale, window=8,
        )


# ---------------- window=None pins today's callers ----------------

PINS = {
    "gpt.decode.reference": "e93e16a7b7e88d9b",
    "gpt.suffix.reference": "8ccadbc3c2bcfd51",
    # All six of granite's re-taken at PR 46: `ops/ssd.py` takes B and C with
    # a group axis (granite's one group among them) and its one-token update
    # keeps an idle lane's state itself. GPT-2's four and Laguna's
    # (tests/test_grouped_experts_grad.py) held through the runner's change.
    "granite.jit__decode_step.None.reference": "0324a3fa67ee642e",
    # The four chunk programs re-taken at PR 41 (on e75c747 + that PR's
    # grouped experts): `routed_grouped` walks a rung of the sorted rows
    # through `ops/grouped_matmul.py`'s kernels, and a chunk returns the
    # rows walked beside the held assignments.
    "granite.jit__prefill_step.16.reference": "43e9b6ef479d2988",
    "granite.jit__prefill_suffix_step.16.reference": "7b078394829e7da7",
    "gpt.decode.pallas": "df37fa95058d4865",
    "gpt.suffix.pallas": "9a5618e5e93e45f8",
    "granite.jit__decode_step.None.pallas": "b02c8e99ab95bb2f",
    # (Re-taken at PR 38 too: a fed chunk of a grouped model takes a cached
    # head's query heads in one product.)
    "granite.jit__prefill_step.16.pallas": "ce6134a559c9a8ba",
    "granite.jit__prefill_suffix_step.16.pallas": "0bcce8fc740f00f8",
}


def lowered_programs(impl):
    """name -> lowered toy step program of GPT-2 and granite under `impl`."""
    from hybrid_toy import toy_config
    from ray_tpu.llm.config import EngineConfig
    from ray_tpu.llm.model_runner import build_runner
    from ray_tpu.models.gpt import GPTConfig

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    ecfg = EngineConfig(
        block_size=8, num_blocks=32, max_decode_slots=4, max_blocks_per_seq=8,
        prefill_buckets=(16, 32), max_prefill_tokens_per_step=16, attn_impl=impl,
    )
    gpt = build_runner(
        GPTConfig(vocab_size=128, max_seq_len=64, num_layers=2, num_heads=2,
                  embed_dim=32, dtype=jnp.float32),
        ecfg, seed=0,
    )
    yield f"gpt.decode.{impl}", gpt._decode_fn.lower(
        gpt.params, *gpt._pools, i32(4), i32(4), i32(4, 8), i32(4)
    )
    yield f"gpt.suffix.{impl}", gpt._prefill_suffix_fn.lower(
        gpt.params, *gpt._pools, i32(1, 16), i32(8), i32(), i32()
    )
    granite = build_runner(toy_config(), ecfg, seed=0)
    for name, width, lowered in granite._lowered():
        yield f"granite.{name}.{width}.{impl}", lowered


def fingerprint(lowered) -> str:
    # A result's name says how the outputs nest, not what is computed.
    text = re.sub(r'jax\.result_info = "[^"]*"', "", lowered.as_text())
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_programs_of_todays_callers_are_pinned(impl):
    found = {name: fingerprint(lowered) for name, lowered in lowered_programs(impl)}
    assert found == {name: pin for name, pin in PINS.items() if name.endswith(impl)}


if __name__ == "__main__":
    for impl in ("reference", "pallas"):
        for name, lowered in lowered_programs(impl):
            print(f'    "{name}": "{fingerprint(lowered)}",')
