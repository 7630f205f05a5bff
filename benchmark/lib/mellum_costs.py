"""Operations of a `mellum` train step, from shapes and counts alone,
whatever implements them; recomputation (a layer taken again in the
backward pass, a flash kernel's second pass over the scores) never counts.

Forward plus backward is three times the forward's products: 6 operations a
weight of the projections, the router and the head for every token; 6 x
the 3 x hidden x width weights of one expert for every assignment to a HELD
expert, as the step counted them in the window (what an absent expert
would do is another chip's); 12 x heads x head size a mask-VISIBLE (query,
key) pair (QK^T and PV, forward and twice backward): `T (T + 1) / 2` a
sequence in a full layer, `lib/laguna_costs.window_pairs(0, T, window)` in a
sliding one. So no share of a peak built on these can pass 100%.

The `model` argument is the configuration file's `model` section.
"""

from __future__ import annotations

from typing import Optional

from lib.hybrid_costs import peaks, runs, scope_seconds
from lib.laguna_costs import window_pairs

STEP = r"^jit_step$"
MOE_SCOPE = r"^llm\.moe\.routed$"
FULL_SCOPE = r"^llm\.mixer\.attention\.full$"
WINDOW_SCOPE = r"^llm\.mixer\.attention\.window$"
ATTENTION_SCOPES = r"^llm\.mixer\.attention\.(full|window)$"
FULL, SLIDING = "full_attention", "sliding_attention"


def rows_held(model: dict) -> int:
    first, end = model["vocab_rows"]
    return end - first


def attention_params(model: dict) -> int:
    """q, k, v and o of one layer."""
    d, hd = model["hidden_size"], model["head_dim"]
    return 2 * d * hd * (model["num_attention_heads"] + model["num_key_value_heads"])


def expert_params(model: dict) -> int:
    """One routed expert: gate and up [D, 2F], down [F, D]."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def parameter_count(model: dict) -> int:
    """Parameters as held: the layers of `layer_types`, `experts_held`
    experts a layer, the slice of embedding and head, the norms."""
    d = model["hidden_size"]
    layer = (
        attention_params(model) + 2 * d + d * model["num_experts"]
        + len(model["experts_held"]) * expert_params(model)
    )
    return len(model["layer_types"]) * layer + 2 * rows_held(model) * d + d


def dense_flops_per_token(model: dict) -> float:
    """Projections and router of every layer, and the head: 6 a weight."""
    d = model["hidden_size"]
    layer = attention_params(model) + d * model["num_experts"]
    return 6.0 * (len(model["layer_types"]) * layer + d * rows_held(model))


def visible_pairs(model: dict, kind: str, tokens: int) -> int:
    """(query, key) pairs the mask of one layer of `kind` leaves visible in
    one sequence of `tokens`."""
    if kind == SLIDING:
        return window_pairs(0, tokens, model["sliding_window"])
    return tokens * (tokens + 1) // 2


def attention_flops(model: dict, kind: str, tokens: int) -> float:
    """QK^T and PV over the visible pairs of one sequence in ONE layer of
    `kind`, forward and backward."""
    width = model["num_attention_heads"] * model["head_dim"]
    return 12.0 * visible_pairs(model, kind, tokens) * width


def expert_flops(model: dict, held_assignments: float) -> float:
    """The held experts' products for `held_assignments` (token, expert)
    assignments, forward and backward."""
    return 6.0 * expert_params(model) * held_assignments


def train_flops_per_token(model: dict, tokens: int, held_per_token: float) -> float:
    """Model operations of a train step a token: `tokens` a sequence,
    `held_per_token` held assignments a token summed over the layers (the
    window's count over its tokens)."""
    attention = sum(
        attention_flops(model, kind, tokens) for kind in model["layer_types"]
    ) / tokens
    return dense_flops_per_token(model) + attention + expert_flops(model, held_per_token)


def traced_steps(collected: dict) -> int:
    return runs(collected, STEP)


def step_scope_seconds(collected: dict, scope: str) -> Optional[float]:
    """Device seconds of the traced train steps' operations under `scope`,
    forward and backward. None where the program publishes no scope map
    (an older commit) or nothing ran under it."""
    return scope_seconds(collected, STEP, scope)


def attention_roofline(collected: dict, kind: str, scope: str) -> Optional[float]:
    """Percent of the bf16 peak in the attention of the layers of `kind`:
    the visible pairs' operations of the traced steps over the seconds of
    the operations under `scope`."""
    train = (collected or {}).get("train") or {}
    model = train.get("model")
    seconds = step_scope_seconds(collected, scope) if model else None
    if not seconds:
        return None
    layers = sum(1 for k in model["layer_types"] if k == kind)
    flops = (
        attention_flops(model, kind, train["tokens_per_sequence"]) * layers
        * train["sequences_per_step"] * traced_steps(collected)
    )
    return 100.0 * flops / peaks()["bf16_flops_per_s"] / seconds


def scope_share(collected: dict, scope: str) -> Optional[float]:
    """Percent of device busy time under the scopes matching `scope` in the
    train step."""
    seconds = step_scope_seconds(collected, scope)
    busy = (collected.get("trace") or {}).get("busy_s")
    return 100.0 * seconds / busy if seconds and busy else None
