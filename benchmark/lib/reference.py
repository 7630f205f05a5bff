"""The plain reference and the comparison that decides `correct`.

`gpt2_forward` is the GPT-2 forward pass (Radford et al. 2019: learned
position embeddings, pre-LayerNorm blocks, fused qkv, causal softmax
attention, a 4x tanh-GELU MLP, a head tied to the token embedding) written
out in `jax.numpy`: no kernel, no cache, no batching, no code of the program.
It runs in float32 under `jax.default_matmul_precision("highest")`, because
on a TPU a float32 matmul otherwise runs in lower precision. The one thing it
takes from the program is the weights, by the names `ray_tpu/models/gpt.py`
gives them (`wte`, `wpe`, `h_<i>/{ln_1, attn_qkv, attn_proj, ln_2, mlp_in,
mlp_out}`, `ln_f`; LayerNorm epsilon 1e-6 as flax's default).

Serving is checked teacher-forced: one forward pass over prompt + answer per
sampled request. Every emitted token must be the reference's argmax at its
position or lie within the configuration's tolerance of it: with random
weights the top logits are near ties, and bf16 kernels round differently from
float32, so a near tie may flip (chip_smoke.py's rule, PR 21).

Training is checked on its gradients: the reference takes the same first
steps on the same batches from the same seed's weights, and the optimizer's
running mean of the gradients must agree with the timed loop's. The loss at
initialisation does not tell weights apart (it is ln(vocabulary) for any);
the gradients do, and they pass through the backward kernels and the step
program that the window times.
"""

from __future__ import annotations


def _layer_norm(x, p, dtype):
    import jax.numpy as jnp

    x32 = x.astype(jnp.float32)
    mean = x32.mean(-1, keepdims=True)
    var = ((x32 - mean) ** 2).mean(-1, keepdims=True)
    y = (x32 - mean) / jnp.sqrt(var + 1e-6)
    return (y * p["scale"] + p["bias"]).astype(dtype)


def _int8(x, axis: int):
    """What a symmetric int8 path keeps of `x`, one scale along `axis`."""
    import jax.numpy as jnp

    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.round(x / scale) * scale


def _dense(x, p, dtype, int8: bool = False):
    import jax.numpy as jnp

    kernel = p["kernel"]
    if int8:  # a scale for each token's activations and each output channel
        x = _int8(x.astype(jnp.float32), -1).astype(dtype)
        kernel = _int8(kernel, 0)
    return x @ kernel.astype(dtype) + p["bias"].astype(dtype)


def gpt2_forward(params, tokens, num_layers: int, num_heads: int, dtype=None,
                 int8: bool = False):
    """Logits [batch, seq, vocab] of `tokens` [batch, seq]. `dtype` is the
    type the matmuls run in: float32 for the reference, the serving type to
    see how far its rounding alone moves a logit. `int8` is the control: the
    weights and every dense layer's input pass through int8 (the nearest
    precision below the bfloat16 the configuration states)."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    dtype = dtype or jnp.float32
    p = nn.meta.unbox(params)["params"]
    batch, seq = tokens.shape
    wte = p["wte"]["embedding"]
    wte = (_int8(wte, -1) if int8 else wte).astype(dtype)
    x = wte[tokens] + p["wpe"]["embedding"].astype(dtype)[jnp.arange(seq)][None]
    width = x.shape[-1]
    head = width // num_heads
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    for i in range(num_layers):
        block = p[f"h_{i}"]
        h = _layer_norm(x, block["ln_1"], dtype)
        q, k, v = jnp.split(_dense(h, block["attn_qkv"], dtype, int8), 3, axis=-1)
        q, k, v = (t.reshape(batch, seq, num_heads, head) for t in (q, k, v))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
        scores = jnp.where(causal, scores / head**0.5, -jnp.inf)
        weights = jax.nn.softmax(scores, axis=-1).astype(dtype)
        mixed = jnp.einsum("bhqk,bkhd->bqhd", weights, v).reshape(batch, seq, width)
        x = x + _dense(mixed, block["attn_proj"], dtype, int8)
        h = _layer_norm(x, block["ln_2"], dtype)
        h = jax.nn.gelu(_dense(h, block["mlp_in"], dtype, int8), approximate=True)
        x = x + _dense(h, block["mlp_out"], dtype, int8)
    x = _layer_norm(x, p["ln_f"], dtype)
    return (x @ wte.T).astype(jnp.float32)


class ServingReference:
    """The reference at one padded length, so one program."""

    def __init__(self, model_cfg, params, padded_len: int):
        import jax

        self.padded_len = padded_len
        self._params = params

        def forward(dtype, int8=False):
            def run(params, tokens):
                with jax.default_matmul_precision("highest"):
                    return gpt2_forward(
                        params, tokens, model_cfg.num_layers,
                        model_cfg.num_heads, dtype, int8,
                    )[0]
            return jax.jit(run)

        self._exact = forward(None)
        self._noisy = forward(model_cfg.dtype)
        self._control = forward(model_cfg.dtype, int8=True)

    def _padded(self, tokens):
        import numpy as np

        padded = np.zeros((1, self.padded_len), np.int32)
        padded[0, : len(tokens)] = tokens
        return padded

    def judge(self, prompt, answer, tolerance: float, noise: bool = False) -> dict:
        """One request's emitted tokens against the reference. `worst_gap`
        is the largest reference-logit distance between the reference's
        choice and the emitted token."""
        import numpy as np

        tokens = list(prompt) + list(answer)
        fed = self._padded(tokens[:-1])
        positions = slice(len(prompt) - 1, len(tokens) - 1)
        rows = np.asarray(self._exact(self._params, fed))[positions]
        if not np.isfinite(rows).all():
            return {"ok": False, "why": "reference logits not finite"}
        answer = np.asarray(answer)
        gaps = rows.max(axis=-1) - rows[np.arange(len(answer)), answer]
        verdict = {
            "ok": bool((gaps < tolerance).all()),
            "tokens": int(len(answer)),
            "flipped": int((gaps > 0).sum()),
            "worst_gap": float(gaps.max()),
            "gap_sum": float(gaps.sum()),
        }
        if noise:
            # How far the same dense forward in the serving type moves a
            # logit: what the configuration's tolerance is derived from.
            moved = np.asarray(self._noisy(self._params, fed))[positions]
            verdict["bf16_logit_noise"] = float(np.abs(moved - rows).max())
        return verdict

    def control_gaps(self, prompt, answer) -> dict:
        """The control's reading of `worst_gap` and `gap_sum` on the same
        prompt and tokens: at each position, how far the token that the int8
        forward puts first lies below the reference's best. It need not
        decode."""
        import numpy as np

        tokens = list(prompt) + list(answer)
        fed = self._padded(tokens[:-1])
        positions = slice(len(prompt) - 1, len(tokens) - 1)
        rows = np.asarray(self._exact(self._params, fed))[positions]
        picks = np.asarray(self._control(self._params, fed))[positions].argmax(axis=-1)
        gaps = rows.max(axis=-1) - rows[np.arange(len(picks)), picks]
        return {"tokens": int(len(picks)), "flipped": int((gaps > 0).sum()),
                "worst_gap": float(gaps.max()), "gap_sum": float(gaps.sum())}


def training_reference_step(model_cfg, tx, rows: int, dtype=None):
    """A jitted `step(params, opt_state, tokens) -> (params, opt_state, loss)`:
    one optimizer step of the reference on `tokens` [batch, seq] under the
    optax transformation `tx` the timed loop steps with. The gradient is
    accumulated over slices of `rows` sequences (the mean of equal slices'
    gradients is the batch's), so the dense float32 attention and logits of a
    whole batch never exist at once. `dtype` as in `gpt2_forward`."""
    import jax
    import jax.numpy as jnp
    import optax

    def loss(params, tokens):
        logits = gpt2_forward(
            params, tokens, model_cfg.num_layers, model_cfg.num_heads, dtype
        )[:, :-1]
        picked = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
        return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)

    def step(params, opt_state, tokens):
        slices = tokens.reshape(-1, rows, tokens.shape[-1])

        def add(carry, piece):
            total, grads = carry
            value, grad = jax.value_and_grad(loss)(params, piece)
            return (total + value, jax.tree_util.tree_map(jnp.add, grads, grad)), None

        with jax.default_matmul_precision("highest"):
            zero = jax.tree_util.tree_map(jnp.zeros_like, params)
            (total, grads), _ = jax.lax.scan(add, (jnp.float32(0.0), zero), slices)
        count = slices.shape[0]
        grads = jax.tree_util.tree_map(lambda g: g / count, grads)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, total / count

    return jax.jit(step, donate_argnums=(0, 1))


def adam_momentum(opt_state):
    """The first moment of an optax adam state: a running mean of the
    gradients, linear in them, so it compares the gradients of every step
    taken so far without the step having to return them."""
    return next(part for part in opt_state if hasattr(part, "mu")).mu


def _split_qkv(tree):
    """The fused `attn_qkv` leaves as `gpt2_forward` splits them. At
    initialisation attention is nearly uniform, so the gradients of queries
    and keys are small beside the values': a fault in them would drown in
    the fused leaf and shows in an entry of its own."""
    import jax.numpy as jnp

    if not isinstance(tree, dict):
        return tree
    out = {}
    for name, sub in tree.items():
        if name == "attn_qkv":
            for i, part in enumerate(("attn_q", "attn_k", "attn_v")):
                out[part] = {k: jnp.split(v, 3, axis=-1)[i] for k, v in sub.items()}
        else:
            out[name] = _split_qkv(sub)
    return out


def relative_distance(tree, reference) -> dict:
    """|tree - reference| / |reference| in the 2-norm: `all` over every leaf,
    one entry per kind of leaf (the last two names of its path, as in
    `mlp_in/kernel`), and `worst_matrix`, the largest entry among kernels and
    embeddings. Biases and LayerNorm parameters are left out of that one:
    some of their gradients are zero by construction (a key's bias shifts
    every score of a row alike), and there the quotient is noise over noise."""
    import jax

    @jax.jit
    def squares(a, b):
        import jax.numpy as jnp

        return jax.tree_util.tree_map(
            lambda x, y: jnp.stack([jnp.sum((x - y) ** 2), jnp.sum(y**2)]),
            _split_qkv(dict(a)), _split_qkv(dict(b)),
        )

    sums: dict = {}
    flat = jax.tree_util.tree_flatten_with_path(jax.device_get(squares(tree, reference)))[0]
    for path, (diff, norm) in flat:
        names = [str(getattr(k, "key", getattr(k, "name", k))) for k in path]
        for kind in ("all", "/".join(names[-2:])):
            have = sums.setdefault(kind, [0.0, 0.0])
            have[0] += float(diff)
            have[1] += float(norm)
    out = {
        kind: (diff / norm) ** 0.5 if norm > 0 else float(diff > 0)
        for kind, (diff, norm) in sums.items()
    }
    out["worst_matrix"] = max(
        value for kind, value in out.items() if kind.endswith(("/kernel", "/embedding"))
    )
    return out
