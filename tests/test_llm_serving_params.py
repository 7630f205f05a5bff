"""The GPT runner holds its matrices in the compute dtype (PR 33).

`models.gpt.serving_params` rounds once, when the runner takes a tree, the
leaves the model's modules round in every call: the kernels and biases of a
block's four dense layers and the two embedding tables. That is the same
mathematics (every product already multiplied those bfloat16 values), so
these hold the held tree and the handed one to *equal bits*: logits,
sampled tokens and the K/V the step programs write. What it buys is bytes a
step, which only the chip shows (`tests/test_tpu_compile.py` reads the
compiled programs; PERF.md has the times).
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import EngineConfig, LLMEngine
from ray_tpu.llm.model_runner import _StepPrograms
from ray_tpu.models.gpt import GPT, GPTConfig, serving_params

SMALL = dict(
    vocab_size=128, num_layers=2, num_heads=4, embed_dim=64, max_seq_len=128,
    attention_impl="reference",
)
BF16 = GPTConfig(dtype=jnp.bfloat16, **SMALL)
BF16_EXPERTS = GPTConfig(dtype=jnp.bfloat16, num_experts=2, **SMALL)
F32 = GPTConfig(dtype=jnp.float32, **SMALL)

# (owning module, parameter): what flax's promote_dtype rounds in every call.
ROUNDED = {
    (module, leaf)
    for module in ("attn_qkv", "attn_proj", "mlp_in", "mlp_out")
    for leaf in ("kernel", "bias")
} | {("wte", "embedding"), ("wpe", "embedding")}


def init(cfg, seed=0):
    return GPT(cfg).init(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))


def boxed_leaves(tree) -> dict:
    """{path of names: leaf, boxes left on} of a parameter tree."""
    flat, _ = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, nn.LogicallyPartitioned)
    )
    return {tuple(key.key for key in path): leaf for path, leaf in flat}


def array(leaf):
    """The array itself: a box is a pytree node and comes back as a new
    box around the same array, so identity is asked of what it holds."""
    return leaf.value if isinstance(leaf, nn.LogicallyPartitioned) else leaf


# ---------------- (a) which leaves, and what becomes of the others ----------------


@pytest.mark.parametrize("cfg", [BF16, BF16_EXPERTS], ids=["dense", "experts"])
def test_exactly_the_leaves_the_modules_round_are_cast(cfg):
    handed = init(cfg)
    before, after = boxed_leaves(handed), boxed_leaves(serving_params(cfg, handed))
    assert before.keys() == after.keys()
    cast = set()
    for path, leaf in after.items():
        if array(leaf) is array(before[path]):
            continue  # the same array: never touched
        cast.add(path[-2:])
        assert "moe_mlp" not in path
        # Boxes survive, names and all: llm_shard_params reads them.
        assert isinstance(leaf, nn.LogicallyPartitioned)
        assert leaf.names == before[path].names
        assert leaf.value.dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(leaf.value.astype(jnp.float32)),
            np.asarray(before[path].value.astype(jnp.bfloat16).astype(jnp.float32)),
        )
    assert cast == ROUNDED
    # Every leaf of those kinds was cast, in every block; nothing else was.
    for path, leaf in after.items():
        assert (array(leaf) is not array(before[path])) == (path[-2:] in ROUNDED), path
        assert type(leaf) is type(before[path])
    # The caller's tree is the caller's: still float32, still alive.
    assert before[("params", "wte", "embedding")].value.dtype == jnp.float32
    assert not before[("params", "wte", "embedding")].value.is_deleted()


def test_norms_and_experts_are_the_same_objects():
    handed = init(BF16_EXPERTS)
    held = serving_params(BF16_EXPERTS, handed)["params"]
    for name in ("ln_1", "ln_2"):
        for leaf in ("scale", "bias"):
            assert held["h_0"][name][leaf] is handed["params"]["h_0"][name][leaf]
            assert held["h_0"][name][leaf].dtype == jnp.float32
    assert held["ln_f"]["scale"] is handed["params"]["ln_f"]["scale"]
    # Block 1 is the one with experts (moe_every=2): its subtree whole.
    experts = handed["params"]["h_1"]["moe_mlp"]
    assert set(experts) == {"router", "w_in", "w_out"}
    for name, leaf in experts.items():
        assert held["h_1"]["moe_mlp"][name].value is leaf.value
        assert held["h_1"]["moe_mlp"][name].names == leaf.names
    assert experts["router"].value.dtype == jnp.float32
    # ... while the dense layers beside them are cast.
    assert held["h_1"]["attn_qkv"]["kernel"].value.dtype == jnp.bfloat16


def test_float32_compute_returns_the_tree_untouched():
    handed = init(F32)
    assert serving_params(F32, handed) is handed


def test_a_tree_already_held_is_returned_as_it_is():
    held = serving_params(BF16, init(BF16))
    assert serving_params(BF16, held) is held


def test_an_unboxed_tree_is_cast_too():
    """A checkpoint saved unboxed, numpy leaves: same rule, same bits."""
    handed = jax.tree_util.tree_map(np.asarray, nn.meta.unbox(init(BF16)))
    held = serving_params(BF16, handed)
    assert held["params"]["h_0"]["mlp_in"]["kernel"].dtype == jnp.bfloat16
    assert held["params"]["h_0"]["ln_1"]["scale"] is handed["params"]["h_0"]["ln_1"]["scale"]
    np.testing.assert_array_equal(
        np.asarray(held["params"]["wpe"]["embedding"]),
        handed["params"]["wpe"]["embedding"].astype(jnp.bfloat16),
    )


def test_a_numpy_tree_is_rounded_on_the_host_to_the_device_bits():
    """A checkpoint never visits a device to be rounded: the guard refuses
    the implicit transfer a jitted cast of numpy leaves would make (the
    whole tree through chip 0, which a tensor-parallel model may not fit),
    and numpy's rounding is the device's, bit for bit."""
    boxed = init(BF16)
    handed = jax.tree_util.tree_map(np.asarray, boxed)
    with jax.transfer_guard("disallow"):
        held = serving_params(BF16, handed)
    on_device = serving_params(BF16, boxed)
    for path, leaf in boxed_leaves(held).items():
        assert isinstance(array(leaf), np.ndarray), path
        want = np.asarray(array(boxed_leaves(on_device)[path]))
        assert array(leaf).dtype == want.dtype
        np.testing.assert_array_equal(
            array(leaf).view(np.uint8), want.view(np.uint8)
        )


def test_a_leaf_is_cast_on_the_device_it_lives_on():
    there = jax.devices()[3]
    handed = jax.device_put(init(BF16), there)
    held = serving_params(BF16, handed)
    for path, leaf in boxed_leaves(held).items():
        assert array(leaf).devices() == {there}, path


# ---------------- (b) equal bits through the step programs ----------------

BLOCK, BLOCKS, TABLE, SLOTS = 8, 16, 8, 2
PROMPT = [int(t) for t in np.random.RandomState(0).randint(0, 128, size=13)]


def _same_bits(a, b):
    assert a.dtype == b.dtype and a.shape == b.shape
    np.testing.assert_array_equal(
        np.asarray(a.astype(jnp.float32)), np.asarray(b.astype(jnp.float32))
    )


@pytest.fixture(scope="module")
def both_trees():
    handed = init(BF16, seed=3)
    return handed, serving_params(BF16, handed)


@pytest.fixture(scope="module")
def after_prefill(both_trees):
    """Per tree: (pools, next token) of a full prefill of PROMPT's first 8
    tokens into block 1, through the runner's own program."""
    programs = _StepPrograms(BF16, BLOCK, "reference", jnp.bfloat16, 1)
    out = []
    for params in both_trees:
        pool = jnp.zeros((BF16.num_layers, BLOCKS, BLOCK, BF16.embed_dim), jnp.bfloat16)
        tokens = jnp.asarray([PROMPT[:8]], jnp.int32)
        out.append(programs.prefill_fn(
            params, pool, jnp.array(pool), None, None, tokens,
            jnp.asarray([1], jnp.int32), jnp.int32(8),
        ))
    return programs, out


def _paged_logits(programs, params, pools, tokens, positions, tables, lens):
    logits, _ = programs.model.apply(
        params, tokens, positions=positions,
        paged_caches=(pools[0], pools[1], tables, lens, None, None),
        paged_impl="reference", mutable=["intermediates"],
    )
    return logits


def test_full_prefill_is_bit_for_bit(both_trees, after_prefill):
    programs, ((pools_a, token_a), (pools_b, token_b)) = after_prefill
    tokens = jnp.asarray([PROMPT[:8]], jnp.int32)
    logits = [
        programs.model.apply(p, tokens, return_kv=True, mutable=["intermediates"])[0]
        for p in both_trees
    ]
    assert logits[0].dtype == jnp.bfloat16
    _same_bits(*logits)
    assert int(token_a) == int(token_b)
    _same_bits(pools_a[0], pools_b[0])
    _same_bits(pools_a[1], pools_b[1])
    assert float(jnp.abs(pools_a[0][:, 1].astype(jnp.float32)).max()) > 0


def test_suffix_chunk_is_bit_for_bit(both_trees, after_prefill):
    programs, prefilled = after_prefill
    suffix = np.zeros((1, 8), np.int32)
    suffix[0, :5] = PROMPT[8:13]
    table = np.zeros((TABLE,), np.int32)
    table[:2] = (1, 2)
    outs, logits = [], []
    for params, (pools, _) in zip(both_trees, prefilled):
        logits.append(_paged_logits(
            programs, params, pools, jnp.asarray(suffix),
            jnp.asarray([[8, 9, 10, 11, 12, 0, 0, 0]], jnp.int32),
            jnp.asarray(table)[None], jnp.asarray([8], jnp.int32),
        ))
        outs.append(programs.prefill_suffix_fn(
            params, jnp.array(pools[0]), jnp.array(pools[1]), None, None,
            jnp.asarray(suffix), jnp.asarray(table), jnp.int32(8), jnp.int32(5),
        ))
    _same_bits(logits[0][:, :5], logits[1][:, :5])
    (pools_a, token_a), (pools_b, token_b) = outs
    assert int(token_a) == int(token_b)
    _same_bits(pools_a[0], pools_b[0])
    _same_bits(pools_a[1], pools_b[1])


def test_decode_step_is_bit_for_bit(both_trees, after_prefill):
    programs, prefilled = after_prefill
    tokens = np.asarray([PROMPT[8], 0], np.int32)
    positions = np.asarray([8, 0], np.int32)
    tables = np.zeros((SLOTS, TABLE), np.int32)
    tables[0, :2] = (1, 2)
    lens = np.asarray([8, 0], np.int32)
    outs, logits = [], []
    for params, (pools, _) in zip(both_trees, prefilled):
        logits.append(_paged_logits(
            programs, params, pools, jnp.asarray(tokens)[:, None],
            jnp.asarray(positions)[:, None], jnp.asarray(tables), jnp.asarray(lens),
        ))
        outs.append(programs.decode_fn(
            params, jnp.array(pools[0]), jnp.array(pools[1]), None, None,
            jnp.asarray(tokens), jnp.asarray(positions), jnp.asarray(tables),
            jnp.asarray(lens),
        ))
    _same_bits(logits[0][0], logits[1][0])
    (pools_a, next_a), (pools_b, next_b) = outs
    np.testing.assert_array_equal(np.asarray(next_a), np.asarray(next_b))
    _same_bits(pools_a[0], pools_b[0])
    _same_bits(pools_a[1], pools_b[1])


# ---------------- (c) the engine, and the counter ----------------


def _engine(cfg, params):
    ecfg = EngineConfig(
        block_size=8, num_blocks=64, max_decode_slots=4, max_blocks_per_seq=8
    )
    return LLMEngine(cfg, ecfg, params=params)


def test_engine_emits_the_same_tokens_from_either_tree_and_counts_half():
    handed = init(BF16, seed=5)
    held = serving_params(BF16, handed)
    rng = np.random.RandomState(1)
    prompts = [list(map(int, rng.randint(0, 128, size=n))) for n in (5, 12, 21)]
    from_handed, from_held = _engine(BF16, handed), _engine(BF16, held)
    assert from_handed.generate(prompts, max_new_tokens=6) == from_held.generate(
        prompts, max_new_tokens=6
    )
    # What the runner took it did not touch, and what it holds is rounded.
    assert handed["params"]["wte"]["embedding"].value.dtype == jnp.float32
    assert from_held.runner.params is held
    # 2 bytes an entry, and 2 more for each entry of a LayerNorm: "half
    # (plus the float32 norms)".
    count = sum(array(leaf).size for leaf in boxed_leaves(handed).values())
    norms = sum(
        array(leaf).size for path, leaf in boxed_leaves(handed).items()
        if path[-2].startswith("ln_")
    )
    assert 0 < norms < count // 50
    for engine in (from_handed, from_held):
        stats = engine.stats()
        assert stats["model_params"] == count
        assert stats["weight_bytes"] == 2 * count + 2 * norms
        assert engine.runner.weight_bytes == stats["weight_bytes"]


def test_engine_at_float32_holds_what_it_was_handed():
    handed = init(F32)
    engine = _engine(F32, handed)
    assert engine.runner.params is handed
    assert engine.stats()["weight_bytes"] == 4 * engine.stats()["model_params"]


def test_a_seeded_engine_holds_the_rounded_tree():
    """No tree handed in: the runner makes float32 and keeps only bf16."""
    engine = LLMEngine(
        BF16,
        EngineConfig(block_size=8, num_blocks=32, max_decode_slots=2, max_blocks_per_seq=4),
        seed=0,
    )
    held = engine.runner.params["params"]
    assert held["wte"]["embedding"].value.dtype == jnp.bfloat16
    assert held["h_0"]["mlp_out"]["kernel"].value.dtype == jnp.bfloat16
    assert held["h_0"]["ln_2"]["scale"].dtype == jnp.float32
    assert 2 * engine.stats()["model_params"] < engine.stats()["weight_bytes"] < (
        2.1 * engine.stats()["model_params"]
    )


def test_gpt2_large_is_held_in_half_the_bytes():
    """Shapes only: what `weight_bytes` reads for the benchmark's model
    (`device_report` read the same two numbers on the chip, PR 33)."""
    cfg = GPTConfig(num_layers=36, num_heads=20, embed_dim=1280)
    handed = jax.eval_shape(lambda: init(cfg))
    held = jax.eval_shape(lambda: serving_params(cfg, init(cfg)))

    def nbytes(tree):
        return sum(
            x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(tree)
        )

    assert nbytes(handed) == 3_096_360_960
    assert nbytes(held) == 1_548_554_240  # half, + 2 B for each of 186,880 norm entries


# ---------------- tensor parallelism: no tree whole on one chip ----------------


def _tp_engine(params, monkeypatch, seed=0):
    """A tp=2 engine, and the leaves `llm_shard_params` was given: what
    existed, and where, just before the placement on the mesh."""
    from ray_tpu.parallel import sharding

    given = []

    def spy(mesh, tree):
        given.append(boxed_leaves(tree))
        return sharding_fn(mesh, tree)

    sharding_fn = sharding.llm_shard_params
    monkeypatch.setattr(sharding, "llm_shard_params", spy)
    engine = LLMEngine(
        BF16,
        EngineConfig(
            block_size=8, num_blocks=64, max_decode_slots=4,
            max_blocks_per_seq=8, tensor_parallel_size=2,
        ),
        params=params,
        seed=seed,
    )
    (leaves,) = given
    return engine, leaves


def _held_on_the_mesh(engine):
    held = boxed_leaves(engine.runner.params)
    mesh = set(engine.runner.mesh.devices.flat)
    assert len(mesh) == 2
    for path, leaf in held.items():
        assert array(leaf).devices() == mesh, path
        assert (array(leaf).dtype == jnp.bfloat16) == (path[-2:] in ROUNDED), path
    # Column-parallel: each chip holds half of the rounded matrix.
    kernel = held[("params", "h_0", "mlp_in", "kernel")].value
    assert kernel.addressable_shards[0].data.nbytes * 2 == kernel.nbytes
    return held


def test_tp_boot_from_a_numpy_checkpoint_never_stages_it_on_a_chip(monkeypatch):
    """Host -> shards, leaf by leaf: what is placed on the mesh is still
    numpy, already rounded, so neither the float32 tree nor its rounded
    copy was ever an argument or a result of a program on one chip."""
    handed = jax.tree_util.tree_map(np.asarray, init(BF16, seed=7))
    before = boxed_leaves(handed)
    engine, given = _tp_engine(handed, monkeypatch)
    for path, leaf in given.items():
        assert isinstance(array(leaf), np.ndarray), path
        want = jnp.bfloat16 if path[-2:] in ROUNDED else jnp.float32
        assert array(leaf).dtype == want, path
    _held_on_the_mesh(engine)
    for path, leaf in before.items():
        assert array(leaf).dtype == np.float32  # the caller's, untouched
    # Same tokens as the same engine over the tree rounded beforehand.
    rng = np.random.RandomState(2)
    prompts = [list(map(int, rng.randint(0, 128, size=n))) for n in (6, 17)]
    held_before, _ = _tp_engine(serving_params(BF16, handed), monkeypatch)
    assert engine.generate(prompts, max_new_tokens=5) == held_before.generate(
        prompts, max_new_tokens=5
    )
    assert engine.stats()["weight_bytes"] == held_before.stats()["weight_bytes"]


def test_tp_seed_init_rounds_on_the_host_cpu(monkeypatch):
    from ray_tpu._private.jax_setup import host_cpu_device

    engine, given = _tp_engine(None, monkeypatch, seed=4)
    host = host_cpu_device("test")
    for path, leaf in given.items():
        assert array(leaf).devices() == {host}, path
        assert (array(leaf).dtype == jnp.bfloat16) == (path[-2:] in ROUNDED), path
    _held_on_the_mesh(engine)
