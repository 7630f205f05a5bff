"""Toy `laguna` sizes shared by the Laguna tests: the pattern of the real
model (a full-attention layer then three sliding-window layers, then one
more full layer; 4 query heads on the full layers and 6 on the sliding ones
over 2 cached heads; a leading dense MLP, then routed experts of which half
are held; YaRN on half of a full layer's head, whole default rotation on a
sliding layer's; an untied head) at widths the CPU runs in milliseconds, in
float32 so that a comparison with the float32 reference can be tight. The
window (12) is shorter than every test's context and not a multiple of the
block sizes used, and YaRN's original length (16) is passed well inside
them."""

import jax.numpy as jnp

from ray_tpu.models import laguna as lg

WINDOW = 12
LAYERS = lg.LAGUNA_PERIOD + (lg.FULL,)
HEADS = (4, 6, 6, 6, 4)
ROPE = {
    lg.FULL: {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 8,
        "original_max_position_embeddings": 16, "beta_slow": 1, "beta_fast": 4,
        "attention_factor": 1.2, "partial_rotary_factor": 0.5,
    },
    lg.SLIDING: {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1},
}


def toy_config(experts_held=(0, 1, 2, 3), **changes):
    fields = dict(
        vocab_size=512, hidden_size=64, intermediate_size=96, layer_types=LAYERS,
        num_attention_heads_per_layer=HEADS,
        mlp_layer_types=(lg.DENSE,) + (lg.SPARSE,) * (len(LAYERS) - 1),
        num_key_value_heads=2, head_dim=16, rope_parameters=ROPE,
        sliding_window=WINDOW, num_experts=8, num_experts_per_tok=3,
        moe_intermediate_size=32, shared_expert_intermediate_size=32,
        experts_held=tuple(experts_held), max_position_embeddings=256,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    fields.update(changes)
    return lg.LagunaConfig(**fields)


def held_params(params, cfg_all, held):
    """The parameter tree of a chip that holds only `held` of the experts of
    `params` (a tree with every expert): the same weights, cut."""
    rows = jnp.asarray([cfg_all.experts_held.index(e) for e in held])
    layers = [
        {**p, "experts_in": p["experts_in"][rows], "experts_out": p["experts_out"][rows]}
        if "experts_in" in p else p
        for p in params["layers"]
    ]
    return {**params, "layers": layers}
