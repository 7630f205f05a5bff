"""Products over rows that lie in groups, for a prefix of the rows only.

The rows of a matrix [P, k] belong to E consecutive groups of `sizes` rows
(an expert's tokens, sorted together); the groups may end before the rows
do. Three Pallas kernels, each a walk over whole row tiles that ends with
the last group's last tile, so a row tile past it costs no product, no copy
and no grid step (the walk's length is a number on the device: the grid's
bound is dynamic):

  `rows_by_group`      [P, k] x [E, k, n] -> [P, n], a row times its group's
                       matrix;
  `matrices_by_group`  [P, k], [P, n] -> [E, k, n], a group's rows of the
                       first, transposed, times its rows of the second;
  `summed_by_token`    [P, D] rows in the order of the token each belongs
                       to -> [T, D], every token's rows summed, as products
                       with the 0 / 1 matrix of which row is whose.

`walk` is what the first two share, computed once for all the products of
one sort: which group and which row tile a grid step takes. The plan is
JAX's megablox kernels' (`jax.experimental.pallas.ops.tpu.megablox`), which
build it inside every call; six products a layer, a rung and a direction
traced it eighteen times in a train step (PR 41).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROW_TILE = 256  # rows a tile of a grouped product
TOKEN_TILE = 256  # tokens an output tile of the sum a token
VMEM_BUDGET = 14 * 2**20  # of the 16 MiB a kernel's blocks may take
MATRICES_BUDGET = 11 * 2**20  # the same where the kernel masks its rows in float32 besides


def round_up(n: int, to: int) -> int:
    return -(-n // to) * to


def row_tile(rows: int) -> int:
    """Rows a tile where `rows` are sorted: 256, or all of a toy's."""
    return min(ROW_TILE, round_up(rows, 16))


class Walk(NamedTuple):
    """The grid steps of a grouped product over row tiles of `tile`:
    step s takes the rows of group `group[s]` in row tile `tile_of[s]`;
    `bounds` [E + 1] are the groups' first rows and the last one's end;
    `steps` of them, a number on the device. A tile that two groups share
    is two steps; a group of no rows is one step of no rows, so that what
    it yields (a matrix of noughts) is written. All arrays: it passes
    through `lax.switch` as it is."""

    bounds: jax.Array
    group: jax.Array
    tile_of: jax.Array
    steps: jax.Array


def walk(sizes: jax.Array, rows: int) -> Walk:
    """The walk over `rows` sorted rows (whole row tiles) in groups of
    `sizes`; the same for any prefix of the rows that holds every group
    (a product clips a tile to its own last one: only a group of no rows
    that starts where the rows end names a tile past it, and its step
    writes no row)."""
    tile = row_tile(rows)
    groups = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tile
    tiles = jnp.where(sizes > 0, (ends - 1) // tile - first + 1, 1)
    until = jnp.cumsum(tiles)
    step = jnp.arange(rows // tile + groups, dtype=jnp.int32)
    group = jnp.minimum(
        jnp.searchsorted(until, step, side="right", method="compare_all"), groups - 1
    ).astype(jnp.int32)
    tile_of = (first[group] + step - (until - tiles)[group]).astype(jnp.int32)
    bounds = jnp.concatenate([starts[:1], ends]).astype(jnp.int32)
    return Walk(bounds, group, tile_of, until[-1].astype(jnp.int32))


def _tiles(rows: int, k: int, n: int, out_bytes: int, in_bytes: int, *, matrices=False):
    """(tk, tn) of a grouped product's tiles, [rows, tk] x [tk, tn] (where
    `matrices`, [rows, tk]^T x [rows, tn]): the pair of lane-tile divisors
    of k and n with the largest matrix tile whose blocks (two buffers each,
    the float32 accumulator, and where the rows are masked in float32 that
    copy) fit the budget; of equals the longer k."""
    def divisors(d):
        return [t for t in range(128, d + 1, 128) if d % t == 0] or [d]

    def blocks(tk, tn):
        if matrices:
            return (2 * in_bytes + 8) * rows * (tk + tn) + (2 * out_bytes + 4) * tk * tn
        return 2 * in_bytes * (rows * tk + tk * tn) + (2 * out_bytes + 4) * rows * tn

    budget = MATRICES_BUDGET if matrices else VMEM_BUDGET
    pairs = [
        (tk, tn) for tk in divisors(k) for tn in divisors(n) if blocks(tk, tn) <= budget
    ]
    if not pairs:
        return min(divisors(k)), min(divisors(n))
    return max(pairs, key=lambda pair: (pair[0] * pair[1], pair[0]))


def _rows_of_group(bounds, group, tile_of, s, tile: int, width: int):
    """[tile, width] bool: which rows of step s's tile are its group's."""
    row = tile_of[s] * tile + jax.lax.broadcasted_iota(jnp.int32, (tile, width), 0)
    return (row >= bounds[group[s]]) & (row < bounds[group[s] + 1])


def _rows_kernel(
    bounds, group, tile_of, lhs_ref, rhs_ref, out_ref, *acc, tile, transposed
):
    """One grid step: the rows of a tile that are the step's group's, times
    the group's matrix. With the contraction in one tile (`acc` empty: every
    product of the three served and trained models) the body has no branch;
    a branch (`pl.when`) is a `lax.cond`, and tracing one costs more than
    tracing the rest of the kernel (a fifth of a second of set-up a program
    on the chip's host, PR 41)."""
    s = pl.program_id(1)
    contract = (((1,), (1,)), ((), ())) if transposed else (((1,), (0,)), ((), ()))
    product = jax.lax.dot_general(
        lhs_ref[...], rhs_ref[...], contract, preferred_element_type=jnp.float32
    )

    def store(product):
        # The tile's other rows are another group's, written by its step
        # while the block stays where it is, or no group's.
        mine = _rows_of_group(bounds, group, tile_of, s, tile, out_ref.shape[1])
        out_ref[...] = jnp.where(
            mine, product, out_ref[...].astype(jnp.float32)
        ).astype(out_ref.dtype)

    if not acc:
        store(product)
        return
    (acc_ref,), at_k = acc, pl.program_id(2)
    acc_ref[...] = jnp.where(at_k == 0, 0.0, acc_ref[...]) + product

    @pl.when(at_k == pl.num_programs(2) - 1)
    def _():
        store(acc_ref[...])


@functools.partial(jax.jit, static_argnames=("dtype", "transposed", "interpret"))
def rows_by_group(lhs, rhs, at: Walk, dtype, *, transposed=False, interpret=False):
    """lhs [P, k] (whole row tiles) in the groups of `at`, times rhs
    [E, k, n] ([E, n, k] where `transposed`): [P, n] of `dtype`, float32
    inside. A row no group holds is left as it was: anything."""
    tile = row_tile(lhs.shape[0])
    last = lhs.shape[0] // tile - 1
    k, n = lhs.shape[1], rhs.shape[1 if transposed else 2]
    tk, tn = _tiles(tile, k, n, jnp.dtype(dtype).itemsize, lhs.dtype.itemsize)
    if transposed:
        rhs_spec = pl.BlockSpec((None, tn, tk), lambda i, s, j, b, g, t: (g[s], i, j))
    else:
        rhs_spec = pl.BlockSpec((None, tk, tn), lambda i, s, j, b, g, t: (g[s], j, i))
    return pl.pallas_call(
        functools.partial(_rows_kernel, tile=tile, transposed=transposed),
        out_shape=jax.ShapeDtypeStruct((lhs.shape[0], n), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, at.steps, k // tk),
            in_specs=[
                pl.BlockSpec(
                    (tile, tk), lambda i, s, j, b, g, t: (jnp.minimum(t[s], last), j)
                ),
                rhs_spec,
            ],
            out_specs=pl.BlockSpec(
                (tile, tn), lambda i, s, j, b, g, t: (jnp.minimum(t[s], last), i)
            ),
            scratch_shapes=[pltpu.VMEM((tile, tn), jnp.float32)] if k > tk else [],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        ),
        interpret=interpret,
        name="rows_by_group",
    )(at.bounds, at.group, at.tile_of, lhs, rhs)


def _matrices_kernel(bounds, group, tile_of, lhs_ref, rhs_ref, out_ref, acc_ref, *, tile):
    s = pl.program_id(2)
    before = jnp.maximum(s - 1, 0)
    after = jnp.minimum(s + 1, pl.num_programs(2) - 1)
    first = (s == 0) | (group[before] != group[s])
    # Another group's rows of the tile count nought; a group of no rows has
    # none, and its matrix comes out nought.
    mine = _rows_of_group(bounds, group, tile_of, s, tile, lhs_ref.shape[1])
    lhs = jnp.where(mine, lhs_ref[...].astype(jnp.float32), 0.0)
    acc_ref[...] = jnp.where(first, 0.0, acc_ref[...]) + jnp.dot(
        lhs.T.astype(lhs_ref.dtype), rhs_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when((s == pl.num_programs(2) - 1) | (group[after] != group[s]))
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def matrices_by_group(lhs, rhs, at: Walk, dtype, *, interpret=False):
    """lhs [P, k] and rhs [P, n] in the groups of `at`: [E, k, n] of
    `dtype`, a group's rows of lhs transposed times its rows of rhs, float32
    inside; nought for a group of no rows. The rows are finite."""
    tile = row_tile(lhs.shape[0])
    last = lhs.shape[0] // tile - 1
    k, n = lhs.shape[1], rhs.shape[1]
    tk, tn = _tiles(
        tile, k, n, jnp.dtype(dtype).itemsize, lhs.dtype.itemsize, matrices=True
    )
    return pl.pallas_call(
        functools.partial(_matrices_kernel, tile=tile),
        out_shape=jax.ShapeDtypeStruct((at.bounds.shape[0] - 1, k, n), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, k // tk, at.steps),
            in_specs=[
                pl.BlockSpec(
                    (tile, tk), lambda i, j, s, b, g, t: (jnp.minimum(t[s], last), j)
                ),
                pl.BlockSpec(
                    (tile, tn), lambda i, j, s, b, g, t: (jnp.minimum(t[s], last), i)
                ),
            ],
            out_specs=pl.BlockSpec((None, tk, tn), lambda i, j, s, b, g, t: (g[s], j, i)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        ),
        interpret=interpret,
        name="matrices_by_group",
    )(at.bounds, at.group, at.tile_of, lhs, rhs)


def _sum_kernel(first, count, held, token_ref, rows_ref, out_ref, *, token_tile):
    i, j = pl.program_id(0), pl.program_id(2)
    tile = rows_ref.shape[0]

    # At least one row tile a tile of tokens, so that one with no rows is
    # written too (noughts: no row of any tile is its tokens').
    @pl.when(j < jnp.maximum(count[i], 1))
    def _():
        tokens = i * token_tile + jax.lax.broadcasted_iota(
            jnp.int32, (token_tile, tile), 0
        )
        chosen = (token_ref[...] == tokens).astype(rows_ref.dtype)
        # What a product left in a row past the last group is anything, and
        # nought times anything is not nought.
        row = (first[i] + j) * tile + jax.lax.broadcasted_iota(
            jnp.int32, rows_ref.shape, 0
        )
        rows = jnp.where(row < held[0], rows_ref[...], 0)
        out_ref[...] = jnp.where(j == 0, 0.0, out_ref[...]) + jnp.dot(
            chosen, rows, preferred_element_type=jnp.float32
        )


@functools.partial(jax.jit, static_argnames=("t_len", "most", "interpret"))
def summed_by_token(rows, token, held, *, t_len: int, most: int, interpret=False):
    """rows [P, D] (whole row tiles) ordered by the token they belong to,
    `token` [P] ascending, the first `held` of them some token's and the
    rest (token t_len) anything: [t_len, D] float32, every token's rows
    summed. A tile of tokens takes the row tiles its tokens' rows span,
    found from `token`, one product each with the 0 / 1 matrix of which row
    is whose; a token has at most `most` rows, so a tile's span is bounded,
    and a row tile that no token's rows reach is not read."""
    p_len, width = rows.shape
    tile = row_tile(p_len)
    tt = min(TOKEN_TILE, round_up(t_len, 8))
    padded = round_up(t_len, tt)
    tn = width
    if width % 128 == 0:
        tn = max(t for t in range(128, 2049, 128) if width % t == 0)
    # (Compared with every edge, not searched: the search is a loop, whose
    # body's instructions lose the scope they were traced under.)
    edges = jnp.searchsorted(
        token, jnp.arange(0, padded + 1, tt, dtype=token.dtype), side="left",
        method="compare_all",
    ).astype(jnp.int32)
    first = jnp.minimum(edges[:-1] // tile, p_len // tile - 1)
    count = (edges[1:] + tile - 1) // tile - first
    steps = min(-(-tt * most // tile) + 1, p_len // tile)

    def tile_of(i, n, j, first, count, held):
        return first[i] + jnp.minimum(j, jnp.maximum(count[i] - 1, 0))

    out = pl.pallas_call(
        functools.partial(_sum_kernel, token_tile=tt),
        out_shape=jax.ShapeDtypeStruct((padded, width), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(padded // tt, width // tn, steps),
            in_specs=[
                pl.BlockSpec((1, tile), lambda *at: (0, tile_of(*at))),
                pl.BlockSpec((tile, tn), lambda *at: (tile_of(*at), at[1])),
            ],
            out_specs=pl.BlockSpec((tt, tn), lambda i, n, *_: (i, n)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="sum_by_token",
    )(first, count, jnp.reshape(held, (1,)).astype(jnp.int32), token[None, :], rows)
    return out[:t_len]
