"""Learner — jitted SGD over an RLModule, optionally sharded over a mesh.

Reference: rllib/core/learner/learner.py (:170 build, :482 update, :604
compute_gradients, :1086 apply_gradients) and torch_learner.py:51 (framework
learner). The TPU re-design: instead of a DDP-wrapped torch module, the whole
(loss → grad → optimizer) step is ONE jitted function; data parallelism is a
`dp` mesh axis with the batch sharded and params replicated, so XLA emits the
gradient all-reduce over ICI (no NCCL, no wrapper class — SURVEY.md §2.5).
Subclasses implement `compute_loss(params, batch, rng)` returning
(scalar_loss, metrics_dict); everything else is generic.
"""

from __future__ import annotations

from typing import Any, Callable, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ray_tpu.rllib.core.rl_module import RLModule, RLModuleSpec
from ray_tpu.rllib.policy.sample_batch import SampleBatch

DEVICE_COLUMNS_EXCLUDED = (SampleBatch.INFOS,)


def _to_device_batch(batch: Mapping) -> dict:
    return {
        k: np.asarray(v)
        for k, v in batch.items()
        if k not in DEVICE_COLUMNS_EXCLUDED and isinstance(v, (np.ndarray, jnp.ndarray))
    }


class Learner:
    """Owns module params + optax state; runs the jitted update."""

    # Subclasses whose loss depends on intra-batch row order (V-trace
    # fragments) set this False; minibatches then iterate in input order.
    shuffle_minibatches = True

    def __init__(
        self,
        module_spec: RLModuleSpec,
        config: Optional[Any] = None,
        mesh: Optional[jax.sharding.Mesh] = None,
    ):
        self.config = config
        self.module_spec = module_spec
        self.module: Optional[RLModule] = None
        self.mesh = mesh
        self._opt_state = None
        self._update_fn: Optional[Callable] = None
        self._grad_fn: Optional[Callable] = None
        self._rng = jax.random.PRNGKey(getattr(config, "seed", 0) or 0)
        self._built = False

    # -- construction -----------------------------------------------------

    def build(self) -> None:
        if self._built:
            return
        self.module = self.module_spec.build()
        self.optimizer = self.configure_optimizer()
        self._opt_state = self.optimizer.init(self.module.params)
        # Read-only pytree fed into the jitted loss as a traced input
        # (target networks etc.) — mutated host-side in after_update without
        # forcing a re-trace.
        self.extra_train_state = self.initial_extra_state()
        self._built = True

    def initial_extra_state(self) -> Any:
        return {}

    def configure_optimizer(self) -> optax.GradientTransformation:
        lr = getattr(self.config, "lr", 5e-4) if self.config else 5e-4
        clip = getattr(self.config, "grad_clip", None) if self.config else None
        chain = []
        if clip:
            chain.append(optax.clip_by_global_norm(clip))
        chain.append(optax.adam(lr))
        return optax.chain(*chain)

    # -- algorithm hook ----------------------------------------------------

    def compute_loss(
        self, params, batch: Mapping, rng, extra=None
    ) -> Tuple[jnp.ndarray, dict]:
        raise NotImplementedError

    # -- update path -------------------------------------------------------

    def _make_update_fn(self):
        optimizer = self.optimizer

        def update_step(params, opt_state, extra, batch, rng):
            (loss, metrics), grads = jax.value_and_grad(
                self.compute_loss, has_aux=True
            )(params, batch, rng, extra)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            metrics = dict(metrics)
            metrics["total_loss"] = loss
            metrics["grad_norm"] = optax.global_norm(grads)
            return params, opt_state, metrics

        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            mesh = self.mesh
            data_axes = tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names)
            replicated = NamedSharding(mesh, P())
            batch_sharding = NamedSharding(mesh, P(data_axes))
            jitted = jax.jit(
                update_step,
                in_shardings=(
                    replicated,
                    replicated,
                    replicated,
                    batch_sharding,
                    replicated,
                ),
                out_shardings=(replicated, replicated, replicated),
                donate_argnums=(0, 1),
            )
        else:
            jitted = jax.jit(update_step, donate_argnums=(0, 1))
        return jitted

    def update(self, batch: SampleBatch) -> dict:
        """One pass of minibatch SGD over `batch`; returns averaged metrics
        (reference learner.py:482 update semantics).

        The whole epochs x minibatches loop runs INSIDE one jitted call
        (permutations, dynamic-slice minibatching and the SGD chain as a
        lax.scan): the host uploads the batch once and syncs once — one
        host-device round trip per update where a host-side loop would
        make epochs*minibatches of them."""
        assert self._built, "call build() first"
        cfg = self.config
        minibatch_size = getattr(cfg, "minibatch_size", None) or batch.count
        num_epochs = getattr(cfg, "num_epochs", 1) or 1
        if self.mesh is None:
            out = self._update_scanned(batch, int(minibatch_size), int(num_epochs))
            self.after_update(batch)
            return out
        # Mesh path: per-minibatch jitted steps (the sharded permutation
        # gather is a cross-device shuffle; keep the simple loop here).
        if self._update_fn is None:
            self._update_fn = self._make_update_fn()
        all_metrics = []
        for mb in batch.minibatches(
            minibatch_size, num_epochs=num_epochs, shuffle=self.shuffle_minibatches
        ):
            self._rng, key = jax.random.split(self._rng)
            device_batch = _to_device_batch(mb)
            self.module.params, self._opt_state, metrics = self._update_fn(
                self.module.params,
                self._opt_state,
                self.extra_train_state,
                device_batch,
                key,
            )
            all_metrics.append(metrics)
        out = {
            k: float(np.mean([jax.device_get(m[k]) for m in all_metrics]))
            for k in all_metrics[0]
        }
        self.after_update(batch)
        return out

    def _make_scanned_update_fn(self, n: int, num_minibatches: int,
                                minibatch_size: int, num_epochs: int):
        optimizer = self.optimizer
        shuffle = self.shuffle_minibatches
        n_rows = num_minibatches * minibatch_size

        def full_update(params, opt_state, extra, batch, rng):
            def epoch_body(carry, epoch_key):
                params, opt_state = carry
                # Permute over ALL n rows, then take the first n_rows of the
                # permutation: DIFFERENT remainder rows drop each epoch, so
                # every collected row participates (matching the old
                # shuffle-then-slice minibatch loop).
                perm = (
                    jax.random.permutation(epoch_key, n)[:n_rows]
                    if shuffle
                    else jnp.arange(n_rows)
                )

                def mb_body(carry2, mb_idx):
                    params, opt_state = carry2
                    take = jax.lax.dynamic_slice_in_dim(
                        perm, mb_idx * minibatch_size, minibatch_size
                    )
                    mb = {k: jnp.take(v, take, axis=0) for k, v in batch.items()}
                    mb_key = jax.random.fold_in(epoch_key, mb_idx)
                    (loss, metrics), grads = jax.value_and_grad(
                        self.compute_loss, has_aux=True
                    )(params, mb, mb_key, extra)
                    updates, opt_state = optimizer.update(grads, opt_state, params)
                    params = optax.apply_updates(params, updates)
                    metrics = dict(metrics)
                    metrics["total_loss"] = loss
                    metrics["grad_norm"] = optax.global_norm(grads)
                    return (params, opt_state), metrics

                (params, opt_state), mb_metrics = jax.lax.scan(
                    mb_body, (params, opt_state), jnp.arange(num_minibatches)
                )
                return (params, opt_state), mb_metrics

            epoch_keys = jax.random.split(rng, num_epochs)
            (params, opt_state), metrics = jax.lax.scan(
                epoch_body, (params, opt_state), epoch_keys
            )
            mean_metrics = jax.tree_util.tree_map(jnp.mean, metrics)
            return params, opt_state, mean_metrics

        return jax.jit(full_update, donate_argnums=(0, 1))

    def _update_scanned(self, batch: SampleBatch, minibatch_size: int,
                        num_epochs: int) -> dict:
        device_batch = _to_device_batch(batch)
        n = batch.count
        minibatch_size = min(minibatch_size, n)
        num_minibatches = max(1, n // minibatch_size)
        n_rows = num_minibatches * minibatch_size
        if n_rows != n and not self.shuffle_minibatches:
            # Order-dependent losses (V-trace fragments) can't resample the
            # remainder; drop the partial tail like the old minibatch loop.
            device_batch = {k: v[:n_rows] for k, v in device_batch.items()}
            n = n_rows
        cache_key = (n, num_minibatches, minibatch_size, num_epochs)
        if not hasattr(self, "_scanned_fns"):
            self._scanned_fns = {}
        fn = self._scanned_fns.get(cache_key)
        if fn is None:
            fn = self._make_scanned_update_fn(
                n, num_minibatches, minibatch_size, num_epochs
            )
            self._scanned_fns[cache_key] = fn
        self._rng, key = jax.random.split(self._rng)
        self.module.params, self._opt_state, metrics = fn(
            self.module.params,
            self._opt_state,
            self.extra_train_state,
            device_batch,
            key,
        )
        return {k: float(v) for k, v in jax.device_get(metrics).items()}

    def after_update(self, batch: SampleBatch) -> None:
        """Post-update hook (target-network sync etc.)."""

    # -- gradient-level API (reference learner.py:604,:1086) ---------------

    def compute_gradients(self, batch: SampleBatch) -> Tuple[Any, dict]:
        assert self._built
        if self._grad_fn is None:
            self._grad_fn = jax.jit(
                lambda params, extra, b, rng: jax.value_and_grad(
                    self.compute_loss, has_aux=True
                )(params, b, rng, extra)
            )
        self._rng, key = jax.random.split(self._rng)
        (loss, metrics), grads = self._grad_fn(
            self.module.params, self.extra_train_state, _to_device_batch(batch), key
        )
        metrics = dict(metrics)
        metrics["total_loss"] = loss
        out = {}
        for k, v in metrics.items():
            v = jax.device_get(v)
            # Scalars stay floats; per-sample diagnostics (td errors) pass
            # through as arrays for the LearnerGroup to concatenate.
            out[k] = float(v) if np.ndim(v) == 0 else np.asarray(v)
        return grads, out

    def apply_gradients(self, grads: Any) -> None:
        assert self._built
        updates, self._opt_state = self.optimizer.update(
            grads, self._opt_state, self.module.params
        )
        self.module.params = optax.apply_updates(self.module.params, updates)

    # -- state -------------------------------------------------------------

    def get_weights(self) -> Any:
        return self.module.get_state()

    def set_weights(self, weights: Any) -> None:
        self.module.set_state(weights)

    def get_state(self) -> dict:
        return {
            "weights": jax.device_get(self.module.params),
            "opt_state": jax.device_get(self._opt_state),
            "extra": jax.device_get(self.extra_train_state),
        }

    def set_state(self, state: Mapping) -> None:
        self.module.params = state["weights"]
        self._opt_state = state["opt_state"]
        self.extra_train_state = state.get("extra", self.extra_train_state)


class MultiAgentLearner:
    """Independent per-policy optimization (reference: marl_module.py +
    the per-module update loop in learner.py): one sub-learner per policy,
    each with its OWN parameters and optimizer state. An update routes each
    policy's sub-batch of a MultiAgentBatch to its learner; policies absent
    from a batch are untouched."""

    def __init__(self, learner_builders: Mapping[str, Callable]):
        self._learners = {pid: b() for pid, b in learner_builders.items()}

    def build(self) -> None:
        for learner in self._learners.values():
            learner.build()

    def __getitem__(self, policy_id: str) -> Learner:
        return self._learners[policy_id]

    def keys(self):
        return self._learners.keys()

    def update(self, batch) -> dict:
        out: dict = {}
        for pid, sub in batch.items():
            learner = self._learners.get(pid)
            if learner is None or sub.count == 0:
                continue
            for k, v in learner.update(sub).items():
                out[f"{pid}/{k}"] = v
        return out

    def after_update(self, batch) -> None:
        pass

    def get_weights(self) -> dict:
        return {pid: lr.get_weights() for pid, lr in self._learners.items()}

    def set_weights(self, weights: Mapping) -> None:
        for pid, w in weights.items():
            if pid in self._learners:
                self._learners[pid].set_weights(w)

    def get_state(self) -> dict:
        return {pid: lr.get_state() for pid, lr in self._learners.items()}

    def set_state(self, state: Mapping) -> None:
        for pid, s in state.items():
            if pid in self._learners:
                self._learners[pid].set_state(s)
