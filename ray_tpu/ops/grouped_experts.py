"""The routed experts a chip holds, without capacity and without drops.

The router scores every expert of the layer (`num_experts`), a token takes
its `top_k` best with gates by one of three rules (`route`'s `score`): a
softmax over those `top_k` logits; a softmax over all the experts of
which the chosen ones' shares are divided by their sum and multiplied by
`scale`; or a sigmoid of every logit, the choice made on the score plus a
selection `bias` an expert, the chosen ones' own scores (without the bias)
divided by their sum and multiplied by `scale`. This chip computes the part of the result that the experts it holds
give: what the absent experts would add is left out, the gates are not
renormalised over the held ones. `local_of` [num_experts] maps an expert's
id to its row in the held weights, or -1. A token's result depends on no
other token in the batch, which a server needs: the same request gives the
same logits alone and among others.

Two forms of the same sum. `routed_dense` sends every token through every
held expert and weighs by the gate (nought where the token did not choose
it): for the few tokens of a decode step, whose time is reading the
experts' matrices whatever is computed. `routed_grouped` sorts the
assignments by expert, the ones no expert here serves last, and works on a
prefix of that order that holds every held one: the rows are gathered,
multiplied (grouped products, Pallas kernels whose grid ends at the last
group's last tile), gated and summed back a token over that prefix alone, so
the work is that of the assignments this chip holds: for a prompt's chunk,
and for a training step, where it carries its own backward.

The prefix is one of a short ladder of static lengths (`ladder`), the first
a quarter over the share of the experts held here, each next one four times
as long, the last every row: the rung is chosen on the device from the held
count (`lax.switch`), so any imbalance is exact and dropless, and
`rows_walked` says which rung a count takes.

An expert is `w_out (silu(g) * u)`, `[g, u] = w_in x`; w_in [E, D, 2F],
w_out [E, F, D].
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.grouped_matmul import (
    matrices_by_group, round_up, row_tile, rows_by_group, summed_by_token, walk,
)


def route(
    x: jax.Array, router: jax.Array, top_k: int, *,
    score: str = "chosen", scale: float = 1.0, bias=None,
) -> Tuple[jax.Array, jax.Array]:
    """x [T, D], router [D, num_experts] -> (expert ids [T, k], gates
    [T, k] float32). Logits and the softmax are float32. `score`
    "chosen": the gates are a softmax over the `top_k` chosen logits.
    "all": a softmax over every expert, the `top_k` largest, their shares
    divided by their sum and multiplied by `scale`. "sigmoid": a sigmoid
    of every logit; the `top_k` largest of score + `bias` [num_experts]
    (which moves the choice and not the weights: nought where None) are
    chosen, their scores divided by their sum and multiplied by `scale`."""
    logits = jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    if score == "chosen":
        top, ids = jax.lax.top_k(logits, top_k)
        return ids, jax.nn.softmax(top, axis=-1)
    if score == "all":
        top, ids = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    elif score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        chooser = scores if bias is None else scores + bias.astype(jnp.float32)
        ids = jax.lax.top_k(chooser, top_k)[1]
        top = jnp.take_along_axis(scores, ids, axis=-1)
    else:
        raise ValueError(f"unknown router score {score!r}")
    return ids, top / jnp.sum(top, axis=-1, keepdims=True) * scale


def _gated(h: jax.Array) -> jax.Array:
    g, u = jnp.split(h, 2, axis=-1)
    return jax.nn.silu(g) * u


def routed_dense(
    x: jax.Array, ids: jax.Array, gates: jax.Array, local_of: jax.Array,
    w_in: jax.Array, w_out: jax.Array,
) -> jax.Array:
    """[T, D] float32: the held experts' gated sum for every token."""
    held = w_in.shape[0]
    local = local_of[ids]  # [T, k]
    # Gate of token t for held expert e, nought where it did not choose it.
    weight = jnp.sum(
        jnp.where(
            local[..., None] == jnp.arange(held)[None, None, :],
            gates[..., None], 0.0,
        ),
        axis=1,
    )  # [T, E]
    h = jnp.einsum("td,edf->etf", x, w_in, preferred_element_type=jnp.float32)
    act = _gated(h) * weight.T[..., None]
    return jnp.einsum(
        "etf,efd->td", act.astype(x.dtype), w_out,
        preferred_element_type=jnp.float32,
    )


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def ladder(rows: int, share: float) -> Tuple[int, ...]:
    """The prefix lengths `routed_grouped` may walk of `rows` sorted
    assignments where `share` of the experts are held: whole row tiles; the
    first a quarter over the held share, each next one four times as long,
    the last every row. Short, because a rung is a copy of the program:
    its eight kernels are traced again for every one (a second of set-up a
    rung of the Mellum step on the chip's host, PR 41)."""
    tile = row_tile(rows)
    every = round_up(rows, tile)
    rungs, rung = [], round_up(max(int(1.25 * share * rows), 1), tile)
    while rung < every:
        rungs.append(rung)
        rung *= 4
    return tuple(rungs) + (every,)


def _rung_of(held, rungs: Tuple[int, ...]):
    """The first rung that takes `held` rows (the last takes any)."""
    return sum((held > rung).astype(jnp.int32) for rung in rungs[:-1])


def rows_walked(held, rows: int, share: float):
    """Rows of the sorted order that `routed_grouped` visits when `held`
    of its `rows` assignments are served here: the rung, at most `rows`."""
    rungs = ladder(rows, share)
    lengths = jnp.minimum(jnp.asarray(rungs, jnp.int32), rows)
    return lengths[_rung_of(held, rungs)]


def _sorted_by_expert(ids, gates, local_of, valid, held: int):
    """The assignments numbered choice-major (choice * T + token) and sorted
    by held expert, absent ones last: (order [k * T rounded up to a row
    tile], the padding numbered past the last assignment; each sorted
    row's gate; sizes [held]). One sort carries the numbers and the gates:
    a gather of as many scalars costs as much again."""
    local = jnp.where(valid[:, None], local_of[ids], -1).T  # [k, T]
    key = jnp.where(local >= 0, local, held).reshape(-1)  # absent last
    rows = key.shape[0]
    _, order, gate = jax.lax.sort(
        (key, jnp.arange(rows, dtype=jnp.int32), gates.T.reshape(-1)),
        num_keys=1, is_stable=True,
    )
    sizes = jnp.sum(
        key[:, None] == jnp.arange(held, dtype=key.dtype)[None, :], axis=0,
        dtype=jnp.int32,
    )
    padding = round_up(rows, row_tile(rows)) - rows
    order = jnp.concatenate([order, rows + jnp.arange(padding, dtype=jnp.int32)])
    return order, jnp.pad(gate, (0, padding)), sizes


def _prefix(order, gate, held, t_len: int, p: int):
    """Of the first `p` sorted rows, the first `held` of them served here:
    the token each belongs to, whether an expert here serves it, and its
    gate, nought where none does."""
    here = jnp.arange(p, dtype=jnp.int32) < held
    return order[:p] % t_len, here, jnp.where(here, gate[:p], 0.0)


def _summed(rows, token, here, held, t_len: int, k: int, *, interpret):
    """[t_len, D] float32: the held rows summed a token."""
    key, by_token = jax.lax.sort(
        (jnp.where(here, token, t_len), jnp.arange(token.shape[0], dtype=jnp.int32)),
        num_keys=1,
    )
    return summed_by_token(
        rows[by_token], key, held, t_len=t_len, most=k, interpret=interpret
    )


def _forward_rung(p, k, interpret, x, w_in, w_out, order, gate, held, at):
    t_len = x.shape[0]
    token, here, gate = _prefix(order, gate, held, t_len, p)
    # The products round to the operands' type on the way out (float32
    # inside): of a chunk's rows by 4,096 a float32 copy is a third of a
    # gigabyte, written and read again in every layer.
    h = rows_by_group(x[token], w_in, at, x.dtype, interpret=interpret)
    # The gate goes in before the second product, so what comes out of it
    # is summed a token as it is.
    act = jnp.where(
        here[:, None], _gated(h.astype(jnp.float32)) * gate[:, None], 0.0
    ).astype(x.dtype)
    y = rows_by_group(act, w_out, at, x.dtype, interpret=interpret)
    out = _summed(y, token, here, held, t_len, k, interpret=interpret)
    # h for the backward, as long as the longest rung's: where nothing is
    # differentiated XLA drops it from the branches (a chunk program's
    # compiled text holds no such pad: PR 41).
    return out, jnp.pad(h, ((0, order.shape[0] - p), (0, 0)))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _forward(x, ids, gates, local_of, w_in, w_out, valid, *, interpret):
    """(out, what the backward needs). One traced function a shape, with
    and without a backward, so a program traces it and lowers its kernels
    once however many layers call it."""
    held = w_in.shape[0]
    order, gate, sizes = _sorted_by_expert(ids, gates, local_of, valid, held)
    rungs = ladder(ids.size, held / local_of.shape[0])
    total = jnp.sum(sizes)
    out, h = jax.lax.switch(
        _rung_of(total, rungs),
        [functools.partial(_forward_rung, p, ids.shape[1], interpret) for p in rungs],
        x, w_in, w_out, order, gate, total, walk(sizes, order.shape[0]),
    )
    return out, (x, gates, local_of, w_in, w_out, order, gate, sizes, h)


@jax.custom_vjp
def routed_grouped(
    x: jax.Array, ids: jax.Array, gates: jax.Array, local_of: jax.Array,
    w_in: jax.Array, w_out: jax.Array, valid: jax.Array,
) -> jax.Array:
    """As `routed_dense`, by grouped products over the assignments sorted
    by expert, of which only a prefix that holds every held one is touched
    (`ladder`). `valid` [T] marks the real tokens: a bucket's padding is
    routed nowhere. Differentiable in x, gates, w_in and w_out, with no
    capacity either way: the backward is two more grouped products for the
    rows and two whose contracting axis is the ragged one for the
    matrices, over the same prefix."""
    return _grouped_forward(x, ids, gates, local_of, w_in, w_out, valid)[0]


def _grouped_forward(x, ids, gates, local_of, w_in, w_out, valid):
    return _forward(x, ids, gates, local_of, w_in, w_out, valid, interpret=_on_cpu())


def _backward_rung(
    p, interpret, x, gates, w_in, w_out, order, gate, held, h, d_out, at
):
    """d_out [T, D] float32. With r a sorted row of token t(r), choice
    c(r), expert e(r), gate g(r) (nought where absent), a = silu(h_g) h_u:
        du = d_out[t(r)] W2_e^T            d gate = a . du
        dW2_e = sum_r (g a)^T d_out[t(r)]  da = g du
        dh = da * d(gated)/dh              dW1_e = sum_r x[t(r)]^T dh
        dx[t] = sum over r of token t of dh W1_e^T."""
    t_len, k = gates.shape
    dtype = x.dtype
    token, here, gate = _prefix(order, gate, held, t_len, p)
    rows, d_rows = x[token], d_out.astype(dtype)[token]
    # Past the last group the forward's product left anything in h.
    h32 = jnp.where(here[:, None], h[:p].astype(jnp.float32), 0.0)
    act = _gated(h32)
    du = rows_by_group(
        d_rows, w_out, at, jnp.float32, transposed=True, interpret=interpret
    )
    du = jnp.where(here[:, None], du, 0.0)  # past the last group: anything
    d_gate = jnp.sum(act * du, axis=-1)
    d_w_out = matrices_by_group(
        (act * gate[:, None]).astype(dtype), d_rows, at, w_out.dtype,
        interpret=interpret,
    )
    gate_half, up_half = jnp.split(h32, 2, axis=-1)
    sig = jax.nn.sigmoid(gate_half)
    da = du * gate[:, None]
    dh = jnp.concatenate(
        [da * up_half * sig * (1.0 + gate_half * (1.0 - sig)), da * gate_half * sig],
        axis=-1,
    ).astype(dtype)
    d_w_in = matrices_by_group(rows, dh, at, w_in.dtype, interpret=interpret)
    d_sorted = rows_by_group(dh, w_in, at, dtype, transposed=True, interpret=interpret)
    d_x = _summed(d_sorted, token, here, held, t_len, k, interpret=interpret)
    # Back where the gates are, (choice, token): a row's number is its place.
    d_gates = jnp.zeros((k * t_len,), d_gate.dtype).at[order[:p]].set(
        d_gate, mode="drop", unique_indices=True
    )
    return (
        d_x.astype(dtype), d_gates.reshape(k, t_len).T.astype(gates.dtype),
        d_w_in, d_w_out,
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def _backward(
    x, gates, local_of, w_in, w_out, order, gate, sizes, h, d_out, *, interpret
):
    rungs = ladder(gates.size, w_in.shape[0] / local_of.shape[0])
    total = jnp.sum(sizes)
    return jax.lax.switch(
        _rung_of(total, rungs),
        [functools.partial(_backward_rung, p, interpret) for p in rungs],
        x, gates, w_in, w_out, order, gate, total, h, d_out,
        walk(sizes, order.shape[0]),
    )


def _grouped_backward(residuals, d_out):
    d_x, d_gates, d_w_in, d_w_out = _backward(
        *residuals, d_out, interpret=_on_cpu()
    )
    return d_x, None, d_gates, None, d_w_in, d_w_out, None


routed_grouped.defvjp(_grouped_forward, _grouped_backward)
