"""The paged kernel's decode walk at compute blocks sized in bytes.

A decode call (one fed token a slot) walks a lane's context in compute
blocks of as many 128-token blocks as keep about 2 MiB of K and V in flight
(`paged_flash.decode_tile`), at most 512 tokens: 512 at Falcon-H1's 4 cached
heads of 128 and at Laguna's 8, 384 at GPT-2 large's 20 of 64, 128 at Olmo
Hybrid's 30 of 128, where the row fills the pipe as it is. The cases run the
kernel (interpreted) at those cells' real widths and query-head counts, the
slots and tables cut down but never under a compute block, so the rule picks
the block it picks in the cell, against `ops.paged_attention`: contexts of
nought, one token, a compute block less one, exactly one, one more, several
and ragged across lanes with an idle lane between live ones; and under
Laguna's window of 512 with the horizon inside the walk's first compute
block, at its edge, and past whole blocks that are skipped, the table
entries below the horizon the null block as the window class frees them.

The pools are bfloat16, as the cells store them (the rule counts bytes),
and q and the new tokens float32, so the kernel and the reference both
compute in float32 over the same stored values and differ in the order of
sums only: 3e-6 on outputs of order 1.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import paged_attention
from ray_tpu.ops.paged_flash import decode_tile, paged_flash_attention

TOLERANCE = 3e-6
BS = 16

# name: (query heads, cached heads, head dim, window, the cell's table
# entries, the compute block's tokens there)
SHAPES = {
    "falcon": (20, 4, 128, None, 176, 512),
    "laguna_full": (48, 8, 128, None, 896, 512),
    "laguna_window": (72, 8, 128, 512, 896, 512),
    "gpt2_large": (20, 20, 64, None, 64, 384),
    "olmo": (30, 30, 128, None, 200, 128),
}


def _edges(tile):
    return (0, 1, tile - 1, tile, tile + 1)


def _ragged(tile):  # several blocks, an idle lane between live ones
    return (2 * tile + 37, 0, tile // 2 + 5, 3 * tile - 1, 0, tile + tile // 3)


CASES = [
    pytest.param(name, contexts(SHAPES[name][5]), id=f"{name}-{contexts.__name__[1:]}")
    for name in SHAPES
    for contexts in (_edges, _ragged)
] + [
    # The horizon (context - 511) inside the walk's first block: 89, 189, and
    # one lane whose whole context is inside the window.
    pytest.param("laguna_window", (600, 700, 300), id="laguna_window-horizon-in-first-block"),
    # At a block's edge: the walk starts at block 1 and 2 exactly, and one
    # key before and after it.
    pytest.param("laguna_window", (1023, 1535, 1022, 1024), id="laguna_window-horizon-at-an-edge"),
    # Across two blocks with whole blocks below skipped, null entries in
    # the first block the walk does copy from.
    pytest.param("laguna_window", (1100, 1500, 0, 2047), id="laguna_window-across-two-blocks"),
]


@pytest.mark.parametrize("name,contexts", CASES)
def test_decode_walk_matches_the_xla_path(name, contexts):
    heads, kv_heads, d, window, cell_table, tile = SHAPES[name]
    nb = max(-(-max(contexts) // BS) + 1, tile // BS)
    # The table is cut down, the compute block is the cell's.
    assert decode_tile(BS, nb, kv_heads, d, 2)[1] == tile
    assert decode_tile(BS, cell_table, kv_heads, d, 2)[1] == tile
    rng = np.random.default_rng(sum(contexts) + heads)
    lanes = len(contexts)
    used = [-(-ctx // BS) for ctx in contexts]
    blocks = 1 + sum(used)
    pools = [
        rng.standard_normal((2, blocks, BS, kv_heads * d)).astype(np.float32)
        for _ in range(2)
    ]
    for pool in pools:
        pool[:, 0] = 1e4  # the null block: finite, and never to be weighed
        pool[0] = -77.0   # another layer's blocks
    tables = np.zeros((lanes, nb), np.int32)
    ids = 1 + rng.permutation(blocks - 1)
    for i, ctx in enumerate(contexts):
        tables[i, : used[i]], ids = ids[: used[i]], ids[used[i]:]
        if window is not None:  # freed as the window class frees
            tables[i, : max(ctx - window + 1, 0) // BS] = 0
    q = jnp.asarray(rng.standard_normal((lanes, 1, heads, d)), jnp.float32)
    new_k, new_v = (
        jnp.asarray(rng.standard_normal((lanes, 1, kv_heads, d)), jnp.float32)
        for _ in range(2)
    )
    args = (
        q, *(jnp.asarray(pool, jnp.bfloat16) for pool in pools),
        jnp.asarray(tables), jnp.asarray(contexts, jnp.int32),
    )
    kwargs = dict(new_k=new_k, new_v=new_v, layer=1, window=window)
    want = paged_attention(*args, **kwargs)
    got = paged_flash_attention(*args, **kwargs, num_kv_heads=kv_heads)
    assert got.shape == want.shape == (lanes, 1, heads, d)
    assert np.isfinite(np.asarray(got)).all()
    assert float(jnp.abs(got - want).max()) < TOLERANCE


@pytest.mark.parametrize(
    "kv_heads,head_dim,itemsize,table,want",
    [
        # (entries, tokens, compute blocks a table) at blocks of 16.
        pytest.param(4, 128, 2, 176, (32, 512, 6), id="falcon-capped-at-512-tokens"),
        pytest.param(8, 128, 2, 896, (32, 512, 28), id="laguna-2MiB"),
        pytest.param(20, 64, 2, 64, (24, 384, 3), id="gpt2-large-1.875MiB"),
        pytest.param(30, 128, 2, 200, (8, 128, 25), id="olmo-as-it-was"),
        pytest.param(8, 128, 4, 896, (16, 256, 56), id="float32-pools-half-the-tokens"),
        pytest.param(8, 128, 2, 12, (12, 192, 1), id="a-short-table-whole"),
        pytest.param(20, 64, 1, 64, (8, 128, 8), id="int8-keeps-128"),
        pytest.param(5, 64, 2, 64, (8, 128, 8), id="gathered-keeps-128"),
    ],
)
def test_the_compute_block_follows_from_the_rows_bytes(
    kv_heads, head_dim, itemsize, table, want
):
    assert decode_tile(BS, table, kv_heads, head_dim, itemsize) == want
    # Two tiles of K and of V stay inside 4 MiB wherever the block widened.
    entries, tile, _ = want
    if tile > 128:
        assert 2 * 2 * tile * kv_heads * head_dim * itemsize <= 4 * 1024 * 1024
