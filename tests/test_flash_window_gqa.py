"""The training flash kernels under grouped queries and a sliding window
(`ray_tpu.ops.flash_attention.flash_attention`'s `window`, and fewer key
heads than query heads): forward, dQ, dK and dV against dense masked
attention with K and V repeated, and the pins: at `window=None` and one
query head a key head the kernels' jaxprs and the GPT train step's lowered
program are what they were before either existed. And the names the forward
gives its two residuals (`RESIDUAL_NAMES`): a checkpoint whose policy asks
for them runs the forward kernel once and gives the same gradients, and
where no policy asks they lower to nothing.

`PYTHONPATH=. python tests/test_flash_window_gqa.py` prints the table to
re-record after a DELIBERATE change to what those programs trace.
"""

import functools
import gc
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import mha_reference
from ray_tpu.ops.flash_attention import RESIDUAL_NAMES, _band_steps, flash_attention

BLOCK = 64
WINDOWS = {"no_window": None, "inside_a_block": 24, "across_blocks": 100}
GROUPS = {"group_1": 1, "group_8": 8}
LENGTHS = {"one_block": 64, "four_blocks": 256}
QUANTITIES = ("forward", "dq", "dk", "dv")


def dense(q, k, v, window):
    """Causal attention inside the window, K and V repeated to the query
    heads: the plain form the kernels are held to."""
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(t, group, axis=2) for t in (k, v))
    s = q.shape[1]
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = j <= i if window is None else (j <= i) & (j > i - window)
    return mha_reference(q, k, v, bias=jnp.where(seen, 0.0, -1e30)[None, None])


@functools.lru_cache(maxsize=None)
def both(window_name, group_name, length_name):
    window, group, s = WINDOWS[window_name], GROUPS[group_name], LENGTHS[length_name]
    keys = jax.random.split(jax.random.PRNGKey(s + group), 4)
    q = jax.random.normal(keys[0], (2, s, 2 * group, 16), jnp.float32)
    k, v = (jax.random.normal(key, (2, s, 2, 16), jnp.float32) for key in keys[1:3])
    weight = jax.random.normal(keys[3], q.shape, jnp.float32)

    def run(attend):
        out, pull = jax.vjp(attend, q, k, v)
        return dict(zip(QUANTITIES, (out, *pull(weight))))

    return (
        run(lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=window, block_q=BLOCK, block_k=BLOCK)),
        run(lambda q, k, v: dense(q, k, v, window)),
    )


@pytest.fixture(scope="module", autouse=True)
def _leave_a_small_heap():
    """What this file traced goes when it is done: the worker that ran it
    runs other files after, and some of them time a full `gc.collect()`."""
    yield
    for cached in (both,):
        cached.cache_clear()
    jax.clear_caches()
    gc.collect()


@pytest.mark.parametrize("quantity", QUANTITIES)
@pytest.mark.parametrize("length", list(LENGTHS))
@pytest.mark.parametrize("group", list(GROUPS))
@pytest.mark.parametrize("window", list(WINDOWS))
def test_kernels_agree_with_dense_attention(window, group, length, quantity):
    got, want = both(window, group, length)
    assert got[quantity].shape == want[quantity].shape
    np.testing.assert_allclose(got[quantity], want[quantity], rtol=2e-4, atol=2e-4)


def test_a_window_one_key_off_is_seen():
    """The comparison resolves one key: the kernels under window 24 are far
    from dense attention under window 25."""
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 128, 8, 16), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(2), (1, 128, 1, 16), jnp.float32)
    mine = flash_attention(q, k, k, causal=True, window=24, block_q=BLOCK, block_k=BLOCK)
    assert float(jnp.max(jnp.abs(mine - dense(q, k, k, 24)))) < 1e-4
    assert float(jnp.max(jnp.abs(mine - dense(q, k, k, 25)))) > 1e-2


@pytest.mark.parametrize(
    "window,block_q,block_k,blocks,want",
    [
        (1024, 1024, 1024, 8, (2, 2)),  # the cell's sliding layers
        (1024, 512, 512, 16, (3, 3)),
        (24, 64, 64, 4, (2, 2)),
        (100, 64, 64, 4, (3, 3)),
        (1, 64, 64, 4, (1, 1)),
        (4096, 64, 64, 4, (4, 4)),  # wider than the sequence: every block
    ],
)
def test_the_grid_shrinks_to_the_band(window, block_q, block_k, blocks, want):
    assert _band_steps(window, block_q, block_k, blocks, blocks) == want


def test_bad_shapes_are_refused():
    q = jnp.zeros((1, 64, 6, 16))
    k = jnp.zeros((1, 64, 4, 16))
    with pytest.raises(ValueError, match="query heads"):
        flash_attention(q, k, k, causal=True)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q[:, :, :4], k, k, causal=False, window=8)


# ---------------- window None, group 1: the programs of before ----------------


def _digest(program) -> str:
    text = re.sub(r"0x[0-9a-f]+", "0x", str(program))
    text = re.sub(r" at [^\s:]+\.py:\d+", "", text)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


CASES = {  # sequence, block, causal, head size
    "causal_blocks": (256, 128, True, 64),
    "causal_one_block": (128, 128, True, 64),
    "full_blocks": (256, 128, False, 64),
    "causal_default_blocks_d128": (2048, None, True, 128),
}


def _flash_case(name, heads=4, window=None):
    """(the call, the gradient of its sum, the shapes both take) of a case."""
    s, block, causal, d = CASES[name]
    x = jax.ShapeDtypeStruct((2, s, 4, d), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((2, s, heads, d), jnp.bfloat16)

    def attend(q, k, v):
        return flash_attention(
            q, k, v, causal=causal, block_q=block, block_k=block, window=window
        )

    grad = jax.grad(
        lambda q, k, v: attend(q, k, v).astype(jnp.float32).sum(), argnums=(0, 1, 2)
    )
    return attend, grad, (x, kv, kv)


def _flash_jaxprs(name, **change):
    attend, grad, shapes = _flash_case(name, **change)
    return {
        "forward": jax.make_jaxpr(attend)(*shapes),
        "grad": jax.make_jaxpr(grad)(*shapes),
    }


def _flash_grad_text(name, **change):
    """The lowered text of the jitted gradient, under no checkpoint: what a
    caller that asks for no name runs."""
    _, grad, shapes = _flash_case(name, **change)
    text = jax.jit(grad).lower(*shapes).as_text()
    # A private function's symbol ends in a counter of the lowering's own.
    return re.sub(r"@(\w+?)_\d+\b", r"@\1", text)


def _gpt_train_step_text():
    """The lowered text of `benchmark/runners/train.py`'s step at toy widths."""
    import flax.linen as nn
    import optax

    from ray_tpu.models.gpt import GPT, GPTConfig, cross_entropy_loss

    cfg = GPTConfig(
        vocab_size=512, num_layers=2, num_heads=4, embed_dim=64, mlp_ratio=4,
        max_seq_len=128, dtype=jnp.bfloat16, attention_impl="flash",
    )
    model, tx = GPT(cfg), optax.adamw(3e-4)
    params = jax.eval_shape(
        lambda: nn.meta.unbox(
            model.init(jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32))
        )
    )

    def step(params, opt_state, tokens):
        def loss_fn(p):
            return cross_entropy_loss(model.apply(p, tokens)[:, :-1], tokens[:, 1:])

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return jax.jit(step, donate_argnums=(0, 1)).lower(
        params, jax.eval_shape(tx.init, params),
        jax.ShapeDtypeStruct((2, 128), jnp.int32),
    ).as_text()


# The jaxprs were taken on commit fc42792 (PR 38), the parent of the PR that
# added `window` and the key heads' own count, and taken again at PR 44,
# which names the forward's output and log-sum-exp: two `name` equations a
# call and no operation, which is what the `.lowered` lines of the flash
# calls hold: they are commit 6a6da49's (PR 43, before the names).
PINNED = {
    "causal_blocks.forward": "dbbc75b6ed5e1e4b",
    "causal_blocks.grad": "9fdf073ea302f9ab",
    "causal_one_block.forward": "eda48acf9700ec9f",
    "causal_one_block.grad": "e722674885b9b7d1",
    "full_blocks.forward": "f3b50f3db4a1c040",
    "full_blocks.grad": "1802f1f01d28ad8c",
    "causal_default_blocks_d128.forward": "b45ee1c45e3f018b",
    "causal_default_blocks_d128.grad": "5d6e89dfa6c8c254",
    "gpt_train_step.lowered": "88d7b9d8031c969f",
}
LOWERED = {  # case, the call's other arguments, the digest
    "causal_blocks": ("causal_blocks", {}, "335338f90c8d14d4"),
    "causal_one_block": ("causal_one_block", {}, "092880edcf8a0fc7"),
    "full_blocks": ("full_blocks", {}, "32349f34627383fc"),
    "causal_default_blocks_d128": ("causal_default_blocks_d128", {}, "46aa9eeb8e160450"),
    "window_and_group": ("causal_blocks", {"heads": 2, "window": 100}, "82aefb032dad7db7"),
}


@pytest.mark.parametrize("name", [n for n in PINNED if n != "gpt_train_step.lowered"])
def test_plain_kernels_trace_as_before(name):
    case, which = name.split(".")
    assert _digest(_flash_jaxprs(case)[which]) == PINNED[name]


def test_gpt_train_step_lowers_as_before():
    assert _digest(_gpt_train_step_text()) == PINNED["gpt_train_step.lowered"]


@pytest.mark.parametrize("name", list(LOWERED))
def test_with_no_policy_the_names_lower_to_nothing(name):
    case, arguments, want = LOWERED[name]
    assert _digest(_flash_grad_text(case, **arguments)) == want


@pytest.mark.parametrize("change", [{"heads": 2}, {"window": 100}])
def test_the_digest_sees_a_group_and_a_window(change):
    """The pins are no constants: either argument changes every kernel."""
    for which, jaxpr in _flash_jaxprs("causal_blocks", **change).items():
        assert _digest(jaxpr) != PINNED["causal_blocks." + which]


# ---------------- a checkpoint that asks for the named residuals ----------------


@pytest.mark.parametrize("window", [None, 100], ids=["full", "window"])
def test_a_checkpoint_that_keeps_the_names_runs_the_forward_kernel_once(window):
    """Four query heads over two key heads, blocks of 64 over 256 keys: the
    gradients of the unwrapped call to the bit, and three kernels (forward,
    dQ, dK/dV) where the bare checkpoint takes the forward one again."""
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(keys[0], (2, 256, 4, 16), jnp.float32)
    k, v = (jax.random.normal(key, (2, 256, 2, 16), jnp.float32) for key in keys[1:3])
    weight = jax.random.normal(keys[3], q.shape, jnp.float32)

    def total(q, k, v):
        out = flash_attention(
            q, k, v, causal=True, window=window, block_q=BLOCK, block_k=BLOCK
        )
        return jnp.sum(out * weight)

    def grad(wrap):
        return jax.grad(wrap(total), argnums=(0, 1, 2))

    def kernels(wrap):
        return len(re.findall(r"pallas_call\[", str(jax.make_jaxpr(grad(wrap))(q, k, v))))

    keep = jax.checkpoint_policies.save_only_these_names(*RESIDUAL_NAMES)
    kept = functools.partial(jax.checkpoint, policy=keep)
    assert kernels(lambda f: f) == 3
    assert kernels(jax.checkpoint) == 4
    assert kernels(kept) == 3
    for mine, theirs in zip(grad(kept)(q, k, v), grad(lambda f: f)(q, k, v)):
        assert float(jnp.linalg.norm(theirs)) > 0
        np.testing.assert_array_equal(mine, theirs)


if __name__ == "__main__":
    for case in CASES:
        for which, jaxpr in _flash_jaxprs(case).items():
            print(f'    "{case}.{which}": "{_digest(jaxpr)}",')
    print(f'    "gpt_train_step.lowered": "{_digest(_gpt_train_step_text())}",')
    for name, (case, arguments, _) in LOWERED.items():
        print(f'    "{name}": ("{case}", {arguments}, "{_digest(_flash_grad_text(case, **arguments))}"),')
