"""Toy `olmo_hybrid` sizes shared by the model's tests: the pattern of the
real model (gated-delta-rule layers around one full-attention layer with as
many cached heads as query heads, a chunk that does not divide most
lengths) at widths the CPU runs in milliseconds, in float32 so that a
comparison with the float32 reference can be tight."""

import jax.numpy as jnp

from ray_tpu.models import olmo_hybrid as oh

LAYERS = ("linear_attention", "linear_attention", "full_attention", "linear_attention")


def toy_config(**changes):
    fields = dict(
        vocab_size=512, hidden_size=64, intermediate_size=96, layer_types=LAYERS,
        num_attention_heads=4, num_key_value_heads=4, linear_num_key_heads=4,
        linear_num_value_heads=4, linear_key_head_dim=8, linear_value_head_dim=16,
        gdn_chunk=8, max_position_embeddings=256, dtype=jnp.float32,
        param_dtype=jnp.float32,
    )
    fields.update(changes)
    return oh.OlmoHybridConfig(**fields)
