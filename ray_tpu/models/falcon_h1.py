"""TII Falcon-H1 (`falcon_h1`): a Mamba-2 mixer and grouped-query attention
side by side in every layer, on the same normed input, their outputs added;
then a dense gated MLP; muP-style scalar multipliers on every branch; an
untied head.

Pure functions over a plain tree of parameters (no flax), as
`ray_tpu.models.granite_hybrid`, `ray_tpu.models.laguna` and
`ray_tpu.models.olmo_hybrid`: the serving programs in
`ray_tpu.llm.hybrid_runner` and the full-sequence `forward` below run the
same layer code and differ only in where a mixer's memory comes from (a
state slot and the paged cache, or nothing). With `h` the residual stream,
RMS norms with a learned weight (eps `rms_norm_eps`), no bias but the
convolution's, every layer alike, and the scalars the config's own keys:

    h = embedding_multiplier * wte[ids]
    a layer:
      u = norm1(h)
      [z | x | B | C | dt] = ((ssm_in_multiplier * u) W_in) * mup
          mup: `ssm_multipliers`, one a segment, in that order
      [x | B | C] <- silu(conv(x | B | C) + b_conv)     depthwise, causal
      dt <- softplus(dt + dt_bias);  A = -exp(A_log)
      the recurrence of `ray_tpu.ops.ssd` on a head's state [P, N], head j
          reading the B and C of group j // (H / G), plus the skip D * x
      y <- rms_norm_by_group(y * silu(z), w_norm)       gate first, then a
          norm over each group's channels (`mamba_norm_before_gate` false)
      m = ssm_out_multiplier * (y W_out)
      ua = attention_in_multiplier * u
      q = ua Wq;  k = key_multiplier * (ua Wk);  v = ua Wv;  q, k <- rope
      a = attention_out_multiplier * (softmax(q k^T head_dim^-0.5) v) Wo
      h = h + m + a
      f = norm2(h)
      h = h + mlp_multipliers[1] * ((silu(mlp_multipliers[0] * f Wg) * f Wu) Wd)
    logits = lm_head_multiplier * (norm_f(h) W_head)

`num_attention_heads` query heads over `num_key_value_heads` cached heads of
`head_dim` (which the config names; it is not hidden / heads), rotary over
the whole head at base `rope_theta`, causal.

A layer is of one kind, `parallel`, and holds two mixers, `mamba` and
`full_attention` (`layer_mixers`): the runner keeps a state slot's arrays
and a layer of the K/V pools for every layer, and `run_layers` hands both
mixers the same `u`.

Parameters are held in `param_dtype` (bfloat16), matrix products take
`dtype` operands and accumulate in float32, the recurrent state, the decay
and the running sums are float32 and the convolution's tail is `dtype`.
Every scalar multiplier is applied to a float32 product (or folded into a
float32 constant that is): none is rounded into a bfloat16 weight.

Not imported by `ray_tpu` or `ray_tpu.models`: import this module by name.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.llm.cache import CacheClass, RecurrentKind
from ray_tpu.models import parts
from ray_tpu.models.parts import num_params  # noqa: F401  (the runner's name for it)
from ray_tpu.ops.ssd import ssd_chunked_scan, ssm_decode_update

PARALLEL = "parallel"
MAMBA, FULL = "mamba", "full_attention"
# The parts of a layer a trace's time is split by
# (`ray_tpu.util.device_report.scopes_of`): granite's names for the Mamba-2
# mixer and Laguna's for attention, so that what reads either reads this.
SCOPES = (
    "llm.mixer.mamba.proj", "llm.mixer.mamba.scan", "llm.mixer.mamba.update",
    "llm.mixer.attention.proj", "llm.mixer.attention.full", "llm.mlp",
    "llm.head",
)
ATTENTION_SCOPES = {FULL: ("llm.mixer.attention.proj", "llm.mixer.attention.full")}
# `stats()["attention_shape"]` by cache class name, as a model with several
# classes has it: what reads `llm.mixer.attention.full` reads the `full` class.
ATTENTION_SHAPE_BY_CLASS = True
# What the seeded weights aim for, a kind of matrix: the standard deviation
# of a product's elements, after its multiplier, for an input of unit mean
# square (`init_std`).
INIT_GAIN = {
    "in_proj": 3.0, "out_proj": 0.5, "q": math.sqrt(2.0), "k": math.sqrt(2.0),
    "v": 1.0, "o": 1.0, "mlp_in": 3.0, "mlp_out": 0.6, "lm_head": 1.0,
}


@dataclasses.dataclass(frozen=True)
class FalconH1Config:
    """Keys as the published config.json names them (Falcon-H1-34B's
    values), plus the types."""

    vocab_size: int = 261120
    hidden_size: int = 5120
    intermediate_size: int = 21504
    num_hidden_layers: int = 72
    num_attention_heads: int = 20
    num_key_value_heads: int = 4
    head_dim: int = 128
    mamba_n_heads: int = 32
    mamba_d_head: int = 128
    mamba_d_ssm: int = 4096
    mamba_d_state: int = 256
    mamba_n_groups: int = 2
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    mamba_norm_before_gate: bool = False
    mamba_rms_norm: bool = True
    mamba_conv_bias: bool = True
    embedding_multiplier: float = 5.656854249492381
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    lm_head_multiplier: float = 0.0078125
    mlp_multipliers: Tuple[float, float] = (0.1767766952966369, 0.011160714285714284)
    ssm_in_multiplier: float = 0.25
    ssm_multipliers: Tuple[float, ...] = (
        0.3535533905932738, 0.25, 0.1767766952966369, 0.5, 0.3535533905932738,
    )
    ssm_out_multiplier: float = 0.08838834764831845
    rope_theta: float = 100000000000.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    # What `ray_tpu.llm` reads off a model's configuration: which runner
    # builds its programs from which model module, and that its layers
    # carry a recurrent state beside the paged cache.
    llm_runner = "ray_tpu.llm.hybrid_runner:HybridRunner"
    llm_model = "ray_tpu.models.falcon_h1"
    recurrent_state = True

    def __post_init__(self):
        for name in ("mlp_multipliers", "ssm_multipliers"):
            object.__setattr__(self, name, tuple(float(v) for v in getattr(self, name)))
        object.__setattr__(self, "rope_theta", float(self.rope_theta))
        if len(self.mlp_multipliers) != 2 or len(self.ssm_multipliers) != 5:
            raise ValueError("two mlp_multipliers and five ssm_multipliers (z, x, B, C, dt)")
        if self.mamba_n_heads * self.mamba_d_head != self.mamba_d_ssm:
            raise ValueError("mamba heads x head size must be mamba_d_ssm")
        if self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError("mamba heads must divide into the groups of B and C")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be a multiple of cached heads")
        if self.head_dim % 2:
            raise ValueError("rotary positions take an even head size")
        if self.mamba_norm_before_gate or not self.mamba_rms_norm:
            raise ValueError("the gated norm is the gate, then the norm a group")
        if not self.mamba_conv_bias:
            raise ValueError("a convolution without its bias is not implemented")

    # The names the engine and the runner know a model's geometry by.
    @property
    def layer_types(self) -> Tuple[str, ...]:
        return (PARALLEL,) * self.num_hidden_layers

    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def num_heads(self) -> int:
        return self.num_attention_heads

    @property
    def max_seq_len(self) -> int:
        return self.max_position_embeddings

    @property
    def attention_scale(self) -> float:
        return self.head_dim ** -0.5

    @property
    def cache_classes(self) -> Tuple[CacheClass, ...]:
        """One class over all layers: every layer's attention keeps every
        position."""
        return (CacheClass("full", self.num_hidden_layers, None),)

    def cache_class_of(self, mixer: str) -> int:
        return 0

    def heads_of(self, mixer: str) -> Tuple[int, ...]:
        return (self.num_attention_heads,)

    @property
    def grouped_dim(self) -> int:
        """B (or C) of every group, side by side."""
        return self.mamba_n_groups * self.mamba_d_state

    @property
    def conv_dim(self) -> int:
        """The channels under the convolution: x, B and C side by side."""
        return self.mamba_d_ssm + 2 * self.grouped_dim

    @property
    def in_proj_dim(self) -> int:
        return self.mamba_d_ssm + self.conv_dim + self.mamba_n_heads


def layer_mixers(cfg: FalconH1Config) -> Dict[str, Tuple[str, ...]]:
    """The mixers a layer of each kind holds: both, on the same input."""
    return {PARALLEL: (MAMBA, FULL)}


def recurrent_shape(cfg: FalconH1Config) -> Dict[str, int]:
    """The Mamba-2 mixers' state as `stats()` publishes it: granite's keys
    and the groups of B and C."""
    return {
        "num_layers": cfg.num_hidden_layers,
        "num_heads": cfg.mamba_n_heads,
        "head_dim": cfg.mamba_d_head,
        "state_size": cfg.mamba_d_state,
        "num_groups": cfg.mamba_n_groups,
        "conv_width": cfg.mamba_d_conv,
        "conv_dim": cfg.conv_dim,
        "chunk_size": cfg.mamba_chunk_size,
        "state_itemsize": 4,
        "conv_itemsize": jnp.dtype(cfg.dtype).itemsize,
    }


def recurrent_kinds(cfg: FalconH1Config) -> Dict[str, RecurrentKind]:
    """What a state slot keeps for one layer's Mamba-2 mixer, and the
    mixer's two functions: the runner makes the pools and calls them. The
    tail is kept flat ([taps - 1, channels] would pad its three rows to a
    tile of sixteen on the TPU)."""
    return {
        MAMBA: RecurrentKind(
            arrays=(
                ("conv", ((cfg.mamba_d_conv - 1) * cfg.conv_dim,), cfg.dtype),
                ("ssm", (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state),
                 jnp.float32),
            ),
            prefill=mamba_prefill, decode=mamba_decode,
            scan_scope="llm.mixer.mamba.scan",
        ),
    }


# ---------------- parameters ----------------


def _leaf_shapes(cfg: FalconH1Config) -> Dict[str, Any]:
    d, heads = cfg.hidden_size, cfg.mamba_n_heads
    q = cfg.num_attention_heads * cfg.head_dim
    kv = cfg.num_key_value_heads * cfg.head_dim
    layer = {
        "norm1": (d,), "norm2": (d,),
        MAMBA: {
            "in_proj": (d, cfg.in_proj_dim),
            "conv_w": (cfg.mamba_d_conv, cfg.conv_dim),
            "conv_b": (cfg.conv_dim,),
            "A_log": (heads,), "D": (heads,), "dt_bias": (heads,),
            "norm": (cfg.mamba_d_ssm,),
            "out_proj": (cfg.mamba_d_ssm, d),
        },
        FULL: {"q": (d, q), "k": (d, kv), "v": (d, kv), "o": (q, d)},
        "mlp_in": (d, 2 * cfg.intermediate_size),
        "mlp_out": (cfg.intermediate_size, d),
    }
    return {
        "wte": (cfg.vocab_size, d), "norm_f": (d,), "lm_head": (d, cfg.vocab_size),
        "layers": [layer] * cfg.num_hidden_layers,
    }


def init_std(cfg: FalconH1Config) -> Dict[str, float]:
    """The seeded standard deviation of every kind of matrix: its gain
    (`INIT_GAIN`) over the multiplier its product meets and the root of its
    fan-in, so that at the published multipliers each of the three
    branches of a layer adds to the stream something of the stream's own
    size, whatever the widths. At normal(0.02) throughout, the
    out-multipliers (0.088, 0.0375, 0.011) leave every branch a thousandth
    of the embedding's 5.66 x 0.02 and no comparison notices a wrong mixer.

    The embedding is 1 / embedding_multiplier, so the stream starts at unit
    mean square. in_proj's segments come out at gain x ssm_multipliers (z
    1.06, x 0.75, B 0.53, C 1.5, dt 1.06 at the published values: the dt
    term is what makes the step depend on the token). q and k give scores
    of standard deviation gain_q x gain_k = 2: a query attends a handful
    of positions, not one and not all. mlp_in is one matrix: its up half
    comes out at its gain and its gate half at gain x mlp_multipliers[0]."""
    d = cfg.hidden_size
    q = cfg.num_attention_heads * cfg.head_dim
    a_in = cfg.attention_in_multiplier
    over = {
        "in_proj": (cfg.ssm_in_multiplier, d),
        "out_proj": (cfg.ssm_out_multiplier, cfg.mamba_d_ssm),
        "q": (a_in, d), "k": (a_in * cfg.key_multiplier, d), "v": (a_in, d),
        "o": (cfg.attention_out_multiplier, q),
        "mlp_in": (1.0, d),
        "mlp_out": (cfg.mlp_multipliers[1], cfg.intermediate_size),
        "lm_head": (cfg.lm_head_multiplier, d),
    }
    stds = {
        name: INIT_GAIN[name] / (multiplier * math.sqrt(fan_in))
        for name, (multiplier, fan_in) in over.items()
    }
    stds["wte"] = 1.0 / cfg.embedding_multiplier
    return stds


def init_params(cfg: FalconH1Config, seed: int) -> Dict[str, Any]:
    """Seeded weights, made leaf by leaf in `param_dtype` (a float32 tree
    of the serving size does not fit a chip): normal(`init_std`) matrices,
    ones for the norms and `D`, and for the recurrence granite's draw: `A`
    uniform in (0, 16], `dt` log-uniform in [0.001, 0.1] behind the
    softplus, the convolution and its bias uniform in +-1/sqrt(taps)."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(
        _leaf_shapes(cfg), is_leaf=lambda v: isinstance(v, tuple)
    )
    base = jax.random.PRNGKey(seed)
    bound = 1.0 / math.sqrt(cfg.mamba_d_conv)
    stds = init_std(cfg)
    made = []
    for index, (path, shape) in enumerate(leaves):
        name = path[-1].key
        key = jax.random.fold_in(base, index)
        if name.startswith("norm") or name == "D":
            leaf = jnp.ones(shape, jnp.float32)
        elif name == "A_log":
            leaf = jnp.log(16.0 * (1.0 - jax.random.uniform(key, shape)))
        elif name == "dt_bias":
            lo, hi = math.log(0.001), math.log(0.1)
            leaf = parts.inverse_softplus(
                jnp.exp(jax.random.uniform(key, shape, minval=lo, maxval=hi))
            )
        elif name in ("conv_w", "conv_b"):
            leaf = jax.random.uniform(key, shape, minval=-bound, maxval=bound)
        else:
            leaf = parts.normal(key, shape, cfg.param_dtype, stds[name])
        made.append(leaf.astype(cfg.param_dtype))
    return jax.tree_util.tree_unflatten(tree, made)


# ---------------- the parts of a layer ----------------


def mup_vector(cfg: FalconH1Config) -> np.ndarray:
    """[in_proj_dim] float32: what in_proj's float32 product is multiplied
    by, `ssm_in_multiplier` (which the published layer puts on the input:
    a scalar passes through the product) times `ssm_multipliers`, one a
    segment in the order z, x, B, C, dt."""
    widths = (
        cfg.mamba_d_ssm, cfg.mamba_d_ssm, cfg.grouped_dim, cfg.grouped_dim,
        cfg.mamba_n_heads,
    )
    return np.concatenate([
        np.full((width,), cfg.ssm_in_multiplier * m, np.float32)
        for width, m in zip(widths, cfg.ssm_multipliers)
    ])


def _mamba_split(cfg, p, u):
    """in_proj, the multipliers and the cut into the gate z, the
    convolution's input xBC and the step dt (after its bias and
    softplus)."""
    with jax.named_scope("llm.mixer.mamba.proj"):
        zxbcdt = parts.matmul(u, p["in_proj"], cfg.dtype) * mup_vector(cfg)
    z, xbc, dt = jnp.split(
        zxbcdt, [cfg.mamba_d_ssm, cfg.mamba_d_ssm + cfg.conv_dim], axis=-1
    )
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))
    return z, xbc.astype(cfg.dtype), dt


def _xbc_parts(cfg, xbc):
    return parts.split_xbc(
        xbc, cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_n_groups, cfg.mamba_d_state
    )


def _mamba_finish(cfg, p, y, x, z):
    """The skip, the gate, then the norm a group: y and x [..., H, P], z
    [..., d_ssm] -> out_proj's input, float32."""
    y = y + p["D"].astype(jnp.float32)[:, None] * x.astype(jnp.float32)
    y = y.reshape(y.shape[:-2] + (cfg.mamba_d_ssm,)) * jax.nn.silu(z)
    return parts.rms_norm_by_group(y, p["norm"], cfg.rms_norm_eps, cfg.mamba_n_groups)


def _mamba_out(cfg, p, y):
    with jax.named_scope("llm.mixer.mamba.proj"):
        return parts.matmul(y, p["out_proj"], cfg.dtype) * cfg.ssm_out_multiplier


def mamba_prefill(cfg, p, u, conv_tail, ssm, length):
    """A chunk of one sequence. u [T, D]; conv_tail [(taps - 1) * conv_dim]
    holds xBC of the positions before the chunk (zeros at a sequence's
    start) and ssm [H, P, N] the state there. Returns the mixer's output
    [T, D] and tail and state after token `length` - 1."""
    z, xbc, dt = _mamba_split(cfg, p, u)
    taps = cfg.mamba_d_conv
    with jax.named_scope("llm.mixer.mamba.scan"):
        padded = jnp.concatenate(
            [conv_tail.reshape(taps - 1, cfg.conv_dim).astype(cfg.dtype), xbc], axis=0
        )
        w = p["conv_w"].astype(jnp.float32)
        conv = sum(
            padded[i : i + u.shape[0]].astype(jnp.float32) * w[i]
            for i in range(taps)
        ) + p["conv_b"].astype(jnp.float32)
        new_tail = jax.lax.dynamic_slice_in_dim(padded, length, taps - 1, axis=0)
        x, b, c = _xbc_parts(cfg, jax.nn.silu(conv).astype(cfg.dtype))
        y, new_ssm = ssd_chunked_scan(
            x, dt, -jnp.exp(p["A_log"].astype(jnp.float32)), b, c, ssm,
            chunk=cfg.mamba_chunk_size, length=length, dtype=cfg.dtype,
        )
        y = _mamba_finish(cfg, p, y, x, z)
    return _mamba_out(cfg, p, y), new_tail.reshape(-1), new_ssm


def mamba_decode(cfg, p, u, conv_tail, ssm, live):
    """One token for each of a batch of sequences. u [B, D], conv_tail
    [B, (taps - 1) * conv_dim], ssm [B, H, P, N]; a lane that is not `live`
    [B] keeps its tail and state."""
    z, xbc, dt = _mamba_split(cfg, p, u)
    taps = cfg.mamba_d_conv
    with jax.named_scope("llm.mixer.mamba.update"):
        window = jnp.concatenate(
            [
                conv_tail.reshape(-1, taps - 1, cfg.conv_dim).astype(cfg.dtype),
                xbc[:, None],
            ],
            axis=1,
        )
        conv = jnp.sum(
            window.astype(jnp.float32) * p["conv_w"].astype(jnp.float32), axis=1
        ) + p["conv_b"].astype(jnp.float32)
        x, b, c = _xbc_parts(cfg, jax.nn.silu(conv).astype(cfg.dtype))
        y, new_ssm = ssm_decode_update(
            x, dt, -jnp.exp(p["A_log"].astype(jnp.float32)), b, c, ssm, live
        )
        y = _mamba_finish(cfg, p, y, x, z)
        new_tail = parts.where_live(
            live, window[:, 1:].reshape(u.shape[0], -1), conv_tail
        )
    return _mamba_out(cfg, p, y), new_tail, new_ssm


def attention_qkv(cfg, kind, p, u, positions):
    """u [..., D] at `positions` [...] -> q [..., Hq, d], k and v
    [..., Hkv, d] in `dtype`: `attention_in_multiplier` on all three and
    `key_multiplier` on k, on the float32 products, then q and k rotated
    (in float32) over the whole head."""
    def heads(w, multiplier):
        out = parts.matmul(u, w, cfg.dtype)
        if multiplier != 1.0:
            out = out * multiplier
        return out.reshape(u.shape[:-1] + (-1, cfg.head_dim))

    a_in = cfg.attention_in_multiplier
    cos, sin = parts.rotary_tables(
        {"rope_type": "default", "rope_theta": cfg.rope_theta}, cfg.head_dim, positions
    )
    return (
        parts.rotate(heads(p["q"], a_in), cos, sin).astype(cfg.dtype),
        parts.rotate(heads(p["k"], a_in * cfg.key_multiplier), cos, sin).astype(cfg.dtype),
        heads(p["v"], a_in).astype(cfg.dtype),
    )


def attention_out(cfg, kind, p, u, mixed):
    """The output projection of mixed [..., Hq, d] -> [..., D] float32,
    times `attention_out_multiplier`."""
    out = parts.matmul(mixed.reshape(u.shape[:-1] + (-1,)), p["o"], cfg.dtype)
    return out * cfg.attention_out_multiplier


def causal_attention(cfg, q, k, v):
    """Dense causal grouped-query attention of one sequence: q [T, Hq, d],
    k and v [T, Hkv, d]. The full-sequence forward's, with no cache."""
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    scores = jnp.einsum(
        "qhd,khd->hqk", q, k, preferred_element_type=jnp.float32
    ) * cfg.attention_scale
    t_len = q.shape[0]
    scores = jnp.where(jnp.tril(jnp.ones((t_len, t_len), bool)), scores, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
    return jnp.einsum("hqk,khd->qhd", weights, v, preferred_element_type=jnp.float32)


def embed(cfg, params, ids):
    return parts.embed(params["wte"], ids, cfg.dtype, cfg.embedding_multiplier)


def head(cfg, params, h):
    """Logits (float32) of the residual rows h [..., D]: the head's own
    matrix, times `lm_head_multiplier`."""
    return parts.head(
        h, params["norm_f"], cfg.rms_norm_eps, params["lm_head"], cfg.dtype,
        tied=False, scaling=1.0 / cfg.lm_head_multiplier,
    )


def run_layers(
    cfg: FalconH1Config, params, h, mixers: Dict[str, Callable], *,
    grouped: bool = False, valid=None,
):
    """The layer stack over the residual rows h [T, D]. `mixers[mixer](i,
    p, u)` is that mixer of the i-th layer that holds it: it owns where the
    layer's memory lives, and every mixer of a layer is handed the same u.
    Returns h and None: a dense model routes nothing and has no counts
    (`grouped` and `valid` are the routed models')."""
    held = layer_mixers(cfg)
    seen = dict.fromkeys(mixers, 0)
    gate_m, out_m = cfg.mlp_multipliers
    for kind, p in zip(cfg.layer_types, params["layers"]):
        u = parts.rms_norm(h, p["norm1"], cfg.rms_norm_eps)
        total = h.astype(jnp.float32)
        for mixer in held[kind]:
            total = total + mixers[mixer](seen[mixer], p[mixer], u)
            seen[mixer] += 1
        h = total.astype(cfg.dtype)
        f = parts.rms_norm(h, p["norm2"], cfg.rms_norm_eps)
        with jax.named_scope("llm.mlp"):
            out = parts.gated_mlp(
                f, p["mlp_in"], p["mlp_out"], cfg.dtype, gate_m, out_m
            )
        h = (h.astype(jnp.float32) + out).astype(cfg.dtype)
    return h, None


def forward(cfg: FalconH1Config, params, tokens):
    """Logits [T, vocab] of one whole sequence `tokens` [T] from an empty
    state and no cache: the chunked scan as the prefill programs run it,
    dense causal attention."""
    t_len = tokens.shape[0]
    positions = jnp.arange(t_len)
    arrays = recurrent_kinds(cfg)[MAMBA].arrays

    def mamba(_, p, u):
        empty = [jnp.zeros(shape, dtype) for _, shape, dtype in arrays]
        return mamba_prefill(cfg, p, u, *empty, t_len)[0]

    def attend(_, p, u):
        with jax.named_scope("llm.mixer.attention.proj"):
            q, k, v = attention_qkv(cfg, FULL, p, u, positions)
        with jax.named_scope("llm.mixer.attention.full"):
            mixed = causal_attention(cfg, q, k, v).astype(cfg.dtype)
        with jax.named_scope("llm.mixer.attention.proj"):
            return attention_out(cfg, FULL, p, u, mixed)

    h, _ = run_layers(
        cfg, params, embed(cfg, params, tokens), {MAMBA: mamba, FULL: attend}
    )
    return head(cfg, params, h)
