"""Toy `mellum` sizes shared by the Mellum tests: the pattern of the real
model (three sliding-window layers then a full one, 8 query heads over 2 key
heads, every MLP routed experts of which a part is held, YaRN on the full
layer and default frequencies on the sliding ones over the whole head, an
untied head, a slice of the vocabulary) at widths the CPU runs in
milliseconds, in float32 so that a comparison with the float32 reference
can be tight. The window (12) is shorter than the sequences and not a
multiple of the flash kernels' block, and YaRN's original length (16) is
passed well inside them."""

import jax.numpy as jnp

from ray_tpu.models import mellum

WINDOW = 12
ROPE = {
    mellum.FULL: {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 8,
        "original_max_position_embeddings": 16, "beta_fast": 4, "beta_slow": 1,
        "attention_factor": 1.2,
    },
    mellum.SLIDING: {"rope_type": "default", "rope_theta": 500000},
}


def toy_config(**changes):
    fields = dict(
        vocab_size=256, hidden_size=64, layer_types=mellum.MELLUM_PERIOD,
        num_attention_heads=8, num_key_value_heads=2, head_dim=16,
        rope_parameters=ROPE, sliding_window=WINDOW, num_experts=8,
        num_experts_per_tok=3, moe_intermediate_size=32,
        experts_held=(0, 1, 2, 3), vocab_rows=(0, 128),
        max_position_embeddings=256, dtype=jnp.float32, param_dtype=jnp.float32,
    )
    fields.update(changes)
    return mellum.MellumConfig(**fields)
