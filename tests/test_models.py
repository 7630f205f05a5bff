"""Model zoo: forward shapes, gradients, sharded init on the virtual mesh."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import flax.linen as nn
import optax

from ray_tpu.models import (
    GPT,
    GPTConfig,
    ResNet18,
    ResNet50,
    cross_entropy_loss,
)
from ray_tpu.parallel import MeshSpec, TP_RULES
from ray_tpu.models.gpt import logical_axis_rules


def test_resnet18_forward():
    model = ResNet18(num_classes=10, small_inputs=True, dtype=jnp.float32)
    x = jnp.ones((2, 32, 32, 3))
    params = model.init(jax.random.PRNGKey(0), x, train=False)
    logits = model.apply(params, x, train=False)
    assert logits.shape == (2, 10)
    assert logits.dtype == jnp.float32


def test_resnet50_param_count():
    model = ResNet50(num_classes=1000, dtype=jnp.float32)
    params = model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 64, 64, 3)), train=False
    )
    n = sum(p.size for p in jax.tree_util.tree_leaves(params))
    # ~25.6M params (GroupNorm variant; BN has the same weight count).
    assert 24e6 < n < 27e6


def test_resnet_train_step_decreases_loss():
    model = ResNet18(num_classes=10, small_inputs=True, dtype=jnp.float32)
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (8, 32, 32, 3))
    y = jax.random.randint(key, (8,), 0, 10)
    params = model.init(key, x, train=False)
    tx = optax.adam(1e-3)
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state):
        def loss_fn(p):
            logits = model.apply(p, x, train=True)
            return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss

    losses = []
    for _ in range(10):
        params, opt_state, loss = step(params, opt_state)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


@pytest.fixture(scope="module")
def tiny_gpt():
    cfg = GPTConfig(
        vocab_size=256,
        num_layers=2,
        num_heads=4,
        embed_dim=128,
        max_seq_len=128,
        dtype=jnp.float32,
        attention_impl="reference",
    )
    model = GPT(cfg)
    tokens = jnp.arange(2 * 64).reshape(2, 64) % 256
    params = model.init(jax.random.PRNGKey(0), tokens)
    return cfg, model, tokens, params


def test_gpt_forward(tiny_gpt):
    cfg, model, tokens, params = tiny_gpt
    logits = model.apply(params, tokens)
    assert logits.shape == (2, 64, 256)


def test_gpt_loss_and_grad(tiny_gpt):
    cfg, model, tokens, params = tiny_gpt

    def loss_fn(p):
        logits = model.apply(p, tokens)
        return cross_entropy_loss(logits[:, :-1], tokens[:, 1:])

    loss, grads = jax.value_and_grad(loss_fn)(params)
    assert np.isfinite(float(loss))
    gnorm = optax.global_norm(grads)
    assert float(gnorm) > 0


def test_gpt_causality(tiny_gpt):
    """Future tokens must not affect past logits."""
    cfg, model, tokens, params = tiny_gpt
    logits1 = model.apply(params, tokens)
    perturbed = tokens.at[:, -1].set((tokens[:, -1] + 1) % 256)
    logits2 = model.apply(params, perturbed)
    np.testing.assert_allclose(
        np.asarray(logits1[:, :-1]), np.asarray(logits2[:, :-1]), atol=1e-5
    )


def test_gpt_cache_carrying_forward(tiny_gpt):
    """The decode=paged / return_kv generation variants reuse the training
    parameters (no fork) and reproduce the plain forward's math."""
    from ray_tpu.models.gpt import collect_kv_caches

    cfg, model, tokens, params = tiny_gpt
    # Prefill: logits unchanged, per-layer K/V exposed via intermediates.
    logits_plain = model.apply(params, tokens)
    logits_kv, state = model.apply(
        params, tokens, return_kv=True, mutable=["intermediates"]
    )
    np.testing.assert_allclose(
        np.asarray(logits_plain), np.asarray(logits_kv), atol=1e-5
    )
    kvs = collect_kv_caches(state["intermediates"], cfg.num_layers)
    b, s = tokens.shape
    assert len(kvs) == cfg.num_layers
    assert kvs[0][0].shape == (b, s, cfg.num_heads, cfg.head_dim)

    # Decode: scatter seq 0's prompt K/V into a paged cache, then a one-token
    # cached step must match the full forward on prompt+token.
    block_size, num_blocks, nb_pad = 16, 8, 4
    n_blocks = s // block_size
    # The stored form: heads and head size merged on the minor axis.
    shape = (cfg.num_layers, num_blocks, block_size, cfg.embed_dim)
    k_cache, v_cache = jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32)
    blocks = jnp.arange(1, n_blocks + 1)
    for layer, (k, v) in enumerate(kvs):
        paged = (n_blocks, block_size, cfg.embed_dim)
        k_cache = k_cache.at[layer, blocks].set(k[0].reshape(paged))
        v_cache = v_cache.at[layer, blocks].set(v[0].reshape(paged))
    next_tok = jnp.argmax(logits_kv[0, s - 1]).astype(jnp.int32)
    table = jnp.zeros((1, nb_pad), jnp.int32).at[0, :n_blocks].set(blocks)
    dec_logits, dec_state = model.apply(
        params,
        next_tok[None, None],
        positions=jnp.full((1, 1), s),
        paged_caches=(k_cache, v_cache, table, jnp.asarray([s], jnp.int32)),
        mutable=["intermediates"],
    )
    full = model.apply(
        params, jnp.concatenate([tokens[0:1], next_tok[None, None]], axis=1)
    )
    np.testing.assert_allclose(
        np.asarray(dec_logits[0, 0]), np.asarray(full[0, s]), atol=2e-4
    )
    # The new token's K/V comes back for the caller's cache write.
    dec_kvs = collect_kv_caches(dec_state["intermediates"], cfg.num_layers)
    assert dec_kvs[0][0].shape == (1, 1, cfg.num_heads, cfg.head_dim)


def test_gpt_tp_sharded_init():
    """Logical axis annotations map onto the mesh: mlp kernels sharded on tp."""
    mesh = MeshSpec(fsdp=2, tp=4).build()
    cfg = GPTConfig(
        vocab_size=256, num_layers=1, num_heads=4, embed_dim=128,
        max_seq_len=64, dtype=jnp.float32, attention_impl="reference",
    )
    model = GPT(cfg)
    tokens = jnp.zeros((1, 16), jnp.int32)
    abstract = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), tokens))
    specs = nn.get_partition_spec(abstract)
    rules = logical_axis_rules(TP_RULES)
    shardings = nn.logical_to_mesh_sharding(specs, mesh, rules)
    mlp_spec = shardings["params"]["h_0"]["mlp_in"]["kernel"].spec
    assert mlp_spec == jax.sharding.PartitionSpec("fsdp", "tp")

    init_fn = jax.jit(
        lambda: model.init(jax.random.PRNGKey(0), tokens), out_shardings=shardings
    )
    params = nn.meta.unbox(init_fn())
    kernel = params["params"]["h_0"]["mlp_in"]["kernel"]
    # 128x512 kernel split over fsdp(2) x tp(4) = 8 devices.
    assert kernel.sharding.shard_shape(kernel.shape) == (64, 128)
