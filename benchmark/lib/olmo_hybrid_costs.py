"""Operations and bytes of the gated-delta-rule layers of an `olmo_hybrid`
model, from shapes alone; scopes, peaks and the split of a trace's device
time by part are `lib/hybrid_costs.py`'s, and the full-attention layers'
are `lib/laguna_costs.py`'s (the scopes have Laguna's names).

The shapes are the ones the engine's `stats()` publishes for such a model
(`recurrent_shape`: `num_layers`, `num_heads`, `key_dim`, `value_dim`,
`conv_width`, `conv_dim`, `chunk_size`, `state_itemsize`, `conv_itemsize`);
the counts are its counters. Each function counts what the algorithm needs,
whatever implements the part, and nothing that an implementation computes
twice or over a whole square where the causal half is needed: a share built
on it cannot pass 100% for doing more work.
"""

from __future__ import annotations

from lib.hybrid_costs import (  # noqa: F401  (one import for the readers)
    DECODE,
    PREFILL,
    busy_share,
    peaks,
    traced_work,
)

SCAN_SCOPE = r"^llm\.mixer\.gdn\.scan$"
UPDATE_SCOPE = r"^llm\.mixer\.gdn\.update$"
GDN_SCOPES = r"^llm\.mixer\.gdn\."


def state_slot_bytes(shape: dict) -> int:
    """One sequence's recurrent state over every gated-delta-rule layer: the
    float32 state [heads, key size, value size] and the convolution's tail
    (q, k and v of the last `conv_width` - 1 positions)."""
    state = shape["num_heads"] * shape["key_dim"] * shape["value_dim"]
    tail = (shape["conv_width"] - 1) * shape["conv_dim"]
    return shape["num_layers"] * (
        state * shape["state_itemsize"] + tail * shape["conv_itemsize"]
    )


def scan_flops_per_token(shape: dict) -> float:
    """Operations a token of the chunked delta rule in ONE layer, between
    the convolution and the gated norm, at the published chunk (a multiply
    and an add are two). A head and token: the causal halves of K K^T and
    Q K^T (2 x key x chunk / 2 each), the unit lower-triangular solve
    applied to beta K and beta V by substitution (row i takes i rows of
    width key + value: (key + value) x chunk on average), the causal half
    of the product with the corrected values (value x chunk), and 3 x 2 x
    key x value for the carried state's two read-outs (W S and Q S) and
    the chunk's contribution to the state (K^T D)."""
    chunk, key, value = shape["chunk_size"], shape["key_dim"], shape["value_dim"]
    inside = chunk * (2 * key + (key + value) + value)
    return float(shape["num_heads"] * (inside + 3 * 2 * key * value))


def scan_flops(tokens: float, shape: dict) -> float:
    """`scan_flops_per_token` over `tokens` real tokens and every layer."""
    return tokens * shape["num_layers"] * scan_flops_per_token(shape)


def parameter_count(model: dict) -> int:
    """Parameters of a configuration's `model` section as it is held: the
    layers of `layer_types`, embedding, head and final norm."""
    d = model["hidden_size"]
    heads = model["linear_num_value_heads"]
    key = model["linear_num_key_heads"] * model["linear_key_head_dim"]
    value = heads * model["linear_value_head_dim"]
    linear = (
        2 * d * key + 3 * d * value + 2 * d * heads
        + model["linear_conv_kernel_dim"] * (2 * key + value) + 2 * heads
        + model["linear_value_head_dim"]
    )
    kv = model["num_key_value_heads"] * (d // model["num_attention_heads"])
    full = 2 * d * d + 2 * d * kv + d + kv
    per_layer = 3 * d * model["intermediate_size"] + 2 * d
    mixers = sum(
        linear if kind == "linear_attention" else full for kind in model["layer_types"]
    )
    return mixers + len(model["layer_types"]) * per_layer + 2 * model["vocab_size"] * d + d
