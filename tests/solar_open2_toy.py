"""Toy `solar_open2` sizes shared by the model's tests: the pattern of the
real model (a grouped-query attention layer with an elementwise gate, three
KDA layers, then one more attention layer; 4 query heads over 2 cached; a
chunk that does not divide most lengths; routed experts behind a sigmoid
router with a selection bias in every layer, of which half are held; a
shared expert; an untied head) at widths the CPU runs in milliseconds, in
float32 so that a comparison with the float32 reference can be tight."""

import jax.numpy as jnp

from ray_tpu.models import solar_open2 as so

LAYERS = 5
GQA_LAYERS = (0, 4)


def toy_config(experts_held=(0, 1, 2, 3), **changes):
    fields = dict(
        vocab_size=512, hidden_size=64, num_hidden_layers=LAYERS,
        gqa_layers=GQA_LAYERS, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, kda_num_heads=4, kda_head_dim=8, kda_low_rank=8, kda_chunk=8,
        n_routed_experts=8, num_experts_per_tok=3, moe_intermediate_size=32,
        experts_held=tuple(experts_held), max_position_embeddings=256,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    fields.update(changes)
    return so.SolarOpen2Config(**fields)


def held_params(params, cfg_all, held):
    """The parameter tree of a chip that holds only `held` of the experts of
    `params` (a tree with every expert): the same weights, cut."""
    rows = jnp.asarray([cfg_all.experts_held.index(e) for e in held])
    layers = [
        {**p, "experts_in": p["experts_in"][rows], "experts_out": p["experts_out"][rows]}
        for p in params["layers"]
    ]
    return {**params, "layers": layers}
