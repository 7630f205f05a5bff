"""Operations and bytes of the KDA layers of a `solar_open2` model (the delta
rule with a decay a key channel), from shapes alone; scopes, peaks and the
split of a trace's device time by part are `lib/hybrid_costs.py`'s, the
routed experts' are its `expert_bytes` / `expert_params`, and the attention
layer's are `lib/laguna_costs.py`'s (the scopes have Laguna's names).

The shapes are the ones the engine's `stats()` publishes for such a model
(`recurrent_shape`: `num_layers`, `num_heads`, `key_dim`, `value_dim`,
`decay_width`, `conv_width`, `conv_dim`, `chunk_size`, `state_itemsize`,
`conv_itemsize`); the counts are its counters. Each function counts what the
algorithm needs, whatever implements the part, and nothing that an
implementation computes twice or over a whole square where the causal half
is needed: a share built on it cannot pass 100% for doing more work, and a
later kernel cannot pass it by doing less.
"""

from __future__ import annotations

from lib.hybrid_costs import (  # noqa: F401  (one import for the readers)
    DECODE,
    PREFILL,
    busy_share,
    peaks,
    traced_work,
)

# A slot's bytes and the chunked scan's operations are the scalar rule's, from
# the same keys of `recurrent_shape`: a head's float32 state [key, value] and
# the convolution's tail; a head and token the causal halves of the two
# decayed products K K^T and Q K^T (2 x key x chunk / 2 each), the unit
# lower-triangular solve applied to beta K exp(run) and beta V by substitution
# ((key + value) x chunk on average), the causal half of the product with the
# corrected values (value x chunk) and 3 x 2 x key x value for the carried
# state's two read-outs and the chunk's contribution to it. A decay a channel
# adds no product: it rides on the operands (`decay_width` multiplies a token
# and product, not counted), so a share built on this count cannot pass 100%
# for an implementation's extra scaling.
from lib.olmo_hybrid_costs import (  # noqa: F401
    scan_flops,
    scan_flops_per_token,
    state_slot_bytes,
)

SCAN_SCOPE = r"^llm\.mixer\.kda\.scan$"
UPDATE_SCOPE = r"^llm\.mixer\.kda\.update$"
KDA_SCOPES = r"^llm\.mixer\.kda\."


def parameter_count(model: dict) -> int:
    """Parameters of a configuration's `model` section as it is held: its
    `num_hidden_layers` layers (`gqa_layers` of them attention, the rest
    KDA), `experts_held` routed experts a layer, embedding, head, final norm."""
    d, rank = model["hidden_size"], model["kda_low_rank"]
    heads, width = model["kda_num_heads"], model["kda_num_heads"] * model["kda_head_dim"]
    kda = (
        4 * d * width + d * heads + 2 * (d * rank + rank * width) + 2 * width
        + heads + model["short_conv_kernel_size"] * 3 * width + model["kda_head_dim"]
    )
    attn = model["num_attention_heads"] * model["head_dim"]
    kv = model["num_key_value_heads"] * model["head_dim"]
    gqa = 3 * d * attn + 2 * d * kv
    expert = 3 * d * model["moe_intermediate_size"]
    per_layer = (
        2 * d + d * model["n_routed_experts"] + model["n_routed_experts"]
        + (len(model["experts_held"]) + model["n_shared_experts"]) * expert
    )
    layers, full = model["num_hidden_layers"], len(model["gqa_layers"])
    return (
        full * gqa + (layers - full) * kda + layers * per_layer
        + 2 * model["vocab_size"] * d + d
    )
