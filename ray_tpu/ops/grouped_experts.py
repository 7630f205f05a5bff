"""The routed experts a chip holds, without capacity and without drops.

The router scores every expert of the layer (`num_experts`), a token takes
its `top_k` best with gates by one of two rules (`route`'s `score`): a
softmax over those `top_k` logits, or a softmax over all the experts of
which the chosen ones' shares are divided by their sum and multiplied by
`scale`. This chip computes the part of the result that the experts it holds
give: what the absent experts would add is left out, the gates are not
renormalised over the held ones. `local_of` [num_experts] maps an expert's
id to its row in the held weights, or -1. A token's result depends on no
other token in the batch, which a server needs: the same request gives the
same logits alone and among others.

Two forms of the same sum. `routed_dense` sends every token through every
held expert and weighs by the gate (nought where the token did not choose
it): for the few tokens of a decode step, whose time is reading the
experts' matrices whatever is computed. `routed_grouped` sorts the held
assignments by expert and takes two grouped products
(`jax.lax.ragged_dot`, a grouped-matmul kernel on the TPU), so the work is
that of the assignments made: for a prompt's chunk, and for a training
step, where it carries its own backward.

An expert is `w_out (silu(g) * u)`, `[g, u] = w_in x`; w_in [E, D, 2F],
w_out [E, F, D].
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def route(
    x: jax.Array, router: jax.Array, top_k: int, *,
    score: str = "chosen", scale: float = 1.0,
) -> Tuple[jax.Array, jax.Array]:
    """x [T, D], router [D, num_experts] -> (expert ids [T, k], gates
    [T, k] float32). Logits and the softmax are float32. `score`
    "chosen": the gates are a softmax over the `top_k` chosen logits.
    "all": a softmax over every expert, the `top_k` largest, their shares
    divided by their sum and multiplied by `scale`."""
    logits = jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    if score == "chosen":
        top, ids = jax.lax.top_k(logits, top_k)
        return ids, jax.nn.softmax(top, axis=-1)
    if score != "all":
        raise ValueError(f"unknown router score {score!r}")
    top, ids = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
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


def _sorted_by_expert(ids, local_of, valid, held: int):
    """The assignments numbered choice-major (choice * T + token) and sorted
    by held expert, absent ones last: (local [k, T], -1 where no expert
    here serves the choice; order [k * T]; sizes [held])."""
    local = jnp.where(valid[:, None], local_of[ids], -1).T  # [k, T]
    key = jnp.where(local >= 0, local, held).reshape(-1)  # absent last
    order = jnp.argsort(key, stable=True)
    sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
    return local, order, sizes


def _grouped_forward(x, ids, gates, local_of, w_in, w_out, valid):
    t_len, k = ids.shape
    held = w_in.shape[0]
    # Sorted back, the products are [k, T, D] and the sum over a token's
    # choices runs over the leading axis. ([T, k, D] would put k = 10 on the
    # second-minor axis, which the TPU pads to its tile and copies: 3 ms a
    # layer of a 2,048-token chunk, more than both products; chip run,
    # PR 32.)
    local, order, sizes = _sorted_by_expert(ids, local_of, valid, held)
    rows = x[order % t_len]  # [k * T, D]
    # The products round to the operands' type on the way out (float32
    # inside): of a chunk's 20,480 rows by 4,096 a float32 copy is a third
    # of a gigabyte, written, sorted back and read again in every layer.
    h = jax.lax.ragged_dot(rows, w_in, sizes, preferred_element_type=x.dtype)
    act = _gated(h.astype(jnp.float32)).astype(x.dtype)
    y = jax.lax.ragged_dot(act, w_out, sizes, preferred_element_type=x.dtype)
    # Back in the order of (choice, token), where the gates are. A choice
    # no expert here serves sorted past the last group: whatever the
    # product left in its row is not part of the sum.
    y = y[jnp.argsort(order)].reshape(k, t_len, -1)
    weight = jnp.where(local >= 0, gates.T, 0.0)
    out = jnp.sum(
        jnp.where(local[..., None] >= 0, y.astype(jnp.float32), 0.0)
        * weight[..., None],
        axis=0,
    )
    return out, (x, gates, w_in, w_out, local, order, sizes, h)


@jax.custom_vjp
def routed_grouped(
    x: jax.Array, ids: jax.Array, gates: jax.Array, local_of: jax.Array,
    w_in: jax.Array, w_out: jax.Array, valid: jax.Array,
) -> jax.Array:
    """As `routed_dense`, by grouped products over the assignments sorted
    by expert. `valid` [T] marks the real tokens: a bucket's padding is
    routed nowhere. Differentiable in x, gates, w_in and w_out, with no
    capacity either way: the backward is two more grouped products for the
    rows and two whose contracting axis is the ragged one for the
    matrices."""
    return _grouped_forward(x, ids, gates, local_of, w_in, w_out, valid)[0]


def _grouped_backward(residuals, d_out):
    """d_out [T, D] float32. With r a sorted row of token t(r), choice
    c(r), expert e(r), gate g(r) (nought where absent), a = silu(h_g) h_u:
        du = d_out[t(r)] W2_e^T            d gate = a . du
        dW2_e = sum_r (g a)^T d_out[t(r)]  da = g du
        dh = da * d(gated)/dh              dW1_e = sum_r x[t(r)]^T dh
        dx[t] = sum over r of token t of dh W1_e^T."""
    x, gates, w_in, w_out, local, order, sizes, h = residuals
    k, t_len = local.shape
    dtype = x.dtype
    token = order % t_len
    here = (local >= 0).reshape(-1)[order]  # sorted: the held rows first
    g = jnp.where(here, gates.T.reshape(-1)[order], 0.0)[:, None]  # [k * T, 1]
    rows, d_rows = x[token], d_out.astype(dtype)[token]
    # Past the last group the forward's product left anything in h.
    h32 = jnp.where(here[:, None], h.astype(jnp.float32), 0.0)
    act = _gated(h32)
    du = jax.lax.ragged_dot(
        d_rows, w_out.swapaxes(1, 2), sizes, preferred_element_type=jnp.float32
    )
    du = jnp.where(here[:, None], du, 0.0)  # past the last group: anything
    d_gate = jnp.sum(act * du, axis=-1)
    ragged_rows = jax.lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((0,), (0,)), ((), ())),
        lhs_ragged_dimensions=[0], rhs_group_dimensions=[],
    )
    d_w_out = jax.lax.ragged_dot_general(
        (act * g).astype(dtype), d_rows, sizes, ragged_rows,
        preferred_element_type=jnp.float32,
    )
    gate_half, up_half = jnp.split(h32, 2, axis=-1)
    sig = jax.nn.sigmoid(gate_half)
    da = du * g
    dh = jnp.concatenate(
        [da * up_half * sig * (1.0 + gate_half * (1.0 - sig)), da * gate_half * sig],
        axis=-1,
    ).astype(dtype)
    d_w_in = jax.lax.ragged_dot_general(
        rows, dh, sizes, ragged_rows, preferred_element_type=jnp.float32
    )
    d_sorted = jax.lax.ragged_dot(
        dh, w_in.swapaxes(1, 2), sizes, preferred_element_type=dtype
    )
    back = jnp.argsort(order)
    d_x = jnp.sum(
        jnp.where(
            local[..., None] >= 0,
            d_sorted[back].reshape(k, t_len, -1).astype(jnp.float32), 0.0,
        ),
        axis=0,
    )
    d_gates = d_gate[back].reshape(k, t_len).T
    return (
        d_x.astype(dtype), None, d_gates.astype(gates.dtype), None,
        d_w_in.astype(w_in.dtype), d_w_out.astype(w_out.dtype), None,
    )


routed_grouped.defvjp(_grouped_forward, _grouped_backward)
