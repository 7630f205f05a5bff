"""Toy `falcon_h1` sizes shared by the model's tests: the pattern of the
real model (a Mamba-2 mixer with two groups of B and C beside grouped-query
attention at five query heads a cached head in every layer, the published
multipliers, a chunk that does not divide most lengths) at widths the CPU
runs in milliseconds, in float32 so that a comparison with the float32
reference can be tight. `attention_in_multiplier` is 0.5 and not the
published 1, so that leaving it out shows."""

import jax.numpy as jnp

from ray_tpu.models import falcon_h1 as fh


def toy_config(**changes):
    fields = dict(
        vocab_size=512, hidden_size=64, intermediate_size=96, num_hidden_layers=3,
        num_attention_heads=10, num_key_value_heads=2, head_dim=16,
        mamba_n_heads=4, mamba_d_head=16, mamba_d_ssm=64, mamba_d_state=16,
        mamba_n_groups=2, mamba_chunk_size=8, attention_in_multiplier=0.5,
        max_position_embeddings=256, dtype=jnp.float32, param_dtype=jnp.float32,
    )
    fields.update(changes)
    return fh.FalconH1Config(**fields)
