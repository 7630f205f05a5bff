"""Toy `granitemoehybrid` sizes shared by the hybrid model's tests: the
pattern of the real model (Mamba-2 layers around one grouped-query attention
layer, routed experts of which half are held, a chunk that does not divide
most lengths) at widths the CPU runs in milliseconds, in float32 so that a
comparison with the float32 reference can be tight."""

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import granite_hybrid as gh

LAYERS = ("mamba", "mamba", "attention", "mamba")


def toy_config(experts_held=(0, 1, 2, 3), **changes):
    fields = dict(
        vocab_size=512, hidden_size=64, layer_types=LAYERS,
        num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=8,
        mamba_d_head=16, mamba_d_state=16, mamba_chunk_size=8,
        intermediate_size=32, shared_intermediate_size=48,
        num_local_experts=8, num_experts_per_tok=2,
        experts_held=tuple(experts_held), attention_multiplier=0.0625,
        max_position_embeddings=256, dtype=jnp.float32,
        param_dtype=jnp.float32,
    )
    fields.update(changes)
    return gh.GraniteHybridConfig(**fields)


def assert_idle_lanes_keep_their_state(runner, pools_expected):
    """One decode step of `runner` (a `HybridRunner` of four lanes and tables
    of twelve blocks) over pools of noise with lanes 1 and 3 idle (context
    length 0): every array a state slot keeps is theirs bit for bit after it,
    and the decoding lanes' moved. The kind's decode sees to it; the runner
    puts what it returns in the pools."""
    rng = np.random.RandomState(0)
    runner.state = jax.tree_util.tree_map(
        lambda pool: jnp.asarray(rng.standard_normal(pool.shape), pool.dtype),
        runner.state,
    )
    before = jax.tree_util.tree_map(np.asarray, runner.state)
    lens = np.array([3, 0, 5, 0], np.int32)
    runner.decode(
        np.arange(1, 5, dtype=np.int32), lens.copy(), np.zeros((4, 12), np.int32), lens
    )
    after = jax.tree_util.tree_map(np.asarray, runner.state)
    pools = list(zip(*map(jax.tree_util.tree_leaves, (before, after))))
    assert len(pools) == pools_expected
    for was, now in pools:
        np.testing.assert_array_equal(now[lens == 0], was[lens == 0])
        assert all((now[lane] != was[lane]).any() for lane in np.flatnonzero(lens))


def held_params(params, cfg_all, held):
    """The parameter tree of a chip that holds only `held` of the experts of
    `params` (a tree with every expert): the same weights, cut."""
    rows = [cfg_all.experts_held.index(e) for e in held]
    layers = [
        {**p, "experts_in": p["experts_in"][jnp.asarray(rows)],
         "experts_out": p["experts_out"][jnp.asarray(rows)]}
        for p in params["layers"]
    ]
    return {**params, "layers": layers}
