"""Parameters, bytes and operations of a `falcon_h1` model, from shapes
alone; scopes, peaks and the split of a trace's device time by part are
`lib/hybrid_costs.py`'s (the Mamba-2 mixer has granite's scope names) and
`lib/laguna_costs.py`'s (attention has Laguna's).

The shapes are the ones the engine's `stats()` publishes for such a model
(`recurrent_shape` with `num_groups`, `head_shape`); each function counts
what the algorithm needs, whatever implements the part: a share built on it
cannot pass 100% for doing more work.
"""

from __future__ import annotations

from lib.hybrid_costs import (  # noqa: F401  (one import for the readers)
    DECODE,
    peaks,
    runs,
    scope_seconds,
)

HEAD_SCOPE = r"^llm\.head$"


def layer_parameter_count(model: dict) -> int:
    """One layer of a configuration's `model` section: the Mamba-2 branch
    (in_proj, out_proj, the convolution and its bias, A_log, D, dt_bias, the
    gated norm), the attention branch (q, k, v, o), the MLP (gate and up,
    down) and two norms."""
    d, heads = model["hidden_size"], model["mamba_n_heads"]
    d_ssm = model["mamba_d_ssm"]
    grouped = model["mamba_n_groups"] * model["mamba_d_state"]
    conv_dim = d_ssm + 2 * grouped
    mamba = (
        d * (d_ssm + conv_dim + heads) + d_ssm * d
        + (model["mamba_d_conv"] + 1) * conv_dim + 3 * heads + d_ssm
    )
    q = model["num_attention_heads"] * model["head_dim"]
    kv = model["num_key_value_heads"] * model["head_dim"]
    attention = 2 * d * q + 2 * d * kv
    mlp = 3 * d * model["intermediate_size"]
    return mamba + attention + mlp + 2 * d


def parameter_count(model: dict) -> int:
    """Parameters of a configuration's `model` section as it is held: its
    layers, embedding, untied head and final norm."""
    d = model["hidden_size"]
    return (
        model["num_hidden_layers"] * layer_parameter_count(model)
        + 2 * model["vocab_size"] * d + d
    )


def head_bytes(shape: dict) -> int:
    """What one run of the head has to read of its matrix (`head_shape`):
    every row once, whatever the rows of logits it makes."""
    return shape["vocab_size"] * shape["hidden_size"] * shape["weight_itemsize"]


def scan_flops_per_token(shape: dict) -> float:
    """Operations a token of the chunked scan in ONE layer, between in_proj
    and out_proj, with the groups counted (`recurrent_shape`): inside a
    chunk the causal half of C B^T for every group (groups x state x chunk)
    and of its product with x (heads x head size x chunk), and 2 x 2 x heads
    x head size x state for the chunk's contribution to the state and the
    carried state's read-out. `hybrid_costs.ssd_scan_cost` counts one
    group's C B^T, so it reads a model with more a little low, never high."""
    inner = shape["num_heads"] * shape["head_dim"]
    chunk, state = shape["chunk_size"], shape["state_size"]
    return float(
        shape.get("num_groups", 1) * state * chunk + inner * chunk + 4 * inner * state
    )
