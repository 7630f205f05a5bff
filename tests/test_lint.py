"""Tests for `ray-tpu lint` (ray_tpu/tools/lint).

Unit tests exercise every rule family on synthetic snippets (nested and
decorated defs, async generators, partial(jax.jit, ...), lock held across
await, suppression + baseline round-trips), the --json contract, and the
repo gate: `ray-tpu lint ray_tpu/` must be clean against the checked-in
baseline, every baseline entry must carry a written reason, and the full
scan must finish well inside the 10s CI budget.
"""

from __future__ import annotations

import json
import textwrap
from pathlib import Path

import pytest

from ray_tpu.tools.lint import all_rules, lint_paths, lint_source
from ray_tpu.tools.lint.core import lint_sources
from ray_tpu.tools.lint import baseline as baseline_mod
from ray_tpu.tools.lint.cli import main as lint_main

pytestmark = pytest.mark.lint

REPO_ROOT = Path(__file__).resolve().parent.parent


def rules_of(findings):
    return [f.rule for f in findings]


def lint(src, **kwargs):
    return lint_source(textwrap.dedent(src), **kwargs)


def lint_files(files, **kwargs):
    """Multi-module fixture harness: {relpath: source} through one
    project (symbol table / call graph / actor index span the dict)."""
    return lint_sources(
        {p: textwrap.dedent(s) for p, s in files.items()}, **kwargs
    )


# ---------------------------------------------------------------------------
# Family 1: async deadlocks
# ---------------------------------------------------------------------------


def test_blocking_get_in_async_def_flagged():
    findings = lint(
        """
        import ray_tpu

        async def handler(ref):
            return ray_tpu.get(ref)
        """
    )
    assert "RTL101" in rules_of(findings)


def test_blocking_calls_via_alias_and_result():
    findings = lint(
        """
        import time
        from ray_tpu import api as ray

        class A:
            async def poll(self, ref, fut):
                time.sleep(1.0)
                x = ray.get(ref)
                y = fut.result()
                return x, y
        """
    )
    assert rules_of(findings).count("RTL101") == 3


def test_awaited_and_offloaded_calls_not_flagged():
    findings = lint(
        """
        import asyncio, time

        async def ok(loop, pool, ref):
            await asyncio.sleep(0.1)
            # Shipped off-loop: the sanctioned pattern.
            x = await loop.run_in_executor(None, lambda: do_get(ref))
            y = await loop.run_in_executor(pool, time.sleep, 1.0)
            return x, y
        """
    )
    assert "RTL101" not in rules_of(findings)


def test_nested_sync_def_inside_async_not_flagged():
    findings = lint(
        """
        import time

        async def outer(pool):
            def blocking():  # runs wherever it's submitted, not on the loop
                time.sleep(1.0)
            return pool.submit(blocking)
        """
    )
    assert "RTL101" not in rules_of(findings)


def test_threading_event_wait_in_async_def_flagged():
    findings = lint(
        """
        import threading

        class A:
            def __init__(self):
                self._done = threading.Event()

            async def wait_done(self):
                self._done.wait()
        """
    )
    assert "RTL101" in rules_of(findings)


def test_await_while_holding_threading_lock_flagged():
    findings = lint(
        """
        import threading

        class A:
            def __init__(self):
                self._lock = threading.Lock()

            async def bad(self, coro):
                with self._lock:
                    await coro

            async def good(self, coro):
                with self._lock:
                    pass
                await coro
        """
    )
    assert rules_of(findings).count("RTL102") == 1
    assert findings[0].context.endswith("bad")


def test_await_under_local_lock_and_async_gen():
    findings = lint(
        """
        import threading

        async def agen(items):
            lock = threading.Lock()
            for item in items:
                with lock:
                    yield await item
        """
    )
    assert "RTL102" in rules_of(findings)


def test_unawaited_local_coroutine_flagged():
    findings = lint(
        """
        class A:
            async def _push(self):
                pass

            def kick(self):
                self._push()

            async def ok(self):
                await self._push()

        async def helper():
            pass

        def fire():
            helper()
        """
    )
    assert rules_of(findings).count("RTL402") == 2


# ---------------------------------------------------------------------------
# Family 2: lock coverage
# ---------------------------------------------------------------------------

LOCKED_CLASS = """
    import threading

    class Box:
        def __init__(self):
            self._lock = threading.Lock()
            self._items = []
            self._count = 0

        def add(self, x):
            with self._lock:
                self._items.append(x)
                self._count += 1

        def bad_read(self):
            return len(self._items)

        def good_read(self):
            with self._lock:
                return len(self._items)

        def _sum_locked(self):
            return sum(self._items)

        def _helper(self):
            \"\"\"Caller must hold self._lock.\"\"\"
            return list(self._items)
"""


def test_lock_coverage_flags_bare_access_only():
    findings = lint(LOCKED_CLASS)
    assert rules_of(findings) == ["RTL201"]
    assert findings[0].context.endswith("bad_read")
    assert "_items" in findings[0].message


def test_bare_attribute_expression_read_flagged():
    """Regression: a guarded attribute that IS the whole expression
    (`return self._x`, `if self._x:`) was misclassified as nested-def
    and never recorded — the most common bare-read shapes."""
    findings = lint(
        """
        import threading

        class Pool:
            def __init__(self):
                self._lock = threading.Lock()
                self._count = 0
                self._open = True

            def add(self):
                with self._lock:
                    self._count += 1
                    self._open = False

            def peek(self):
                return self._count

            def gate(self):
                if self._open:
                    return "open"
                return "closed"
        """
    )
    assert rules_of(findings) == ["RTL201", "RTL201"]
    assert {f.context.split(".")[-1] for f in findings} == {"peek", "gate"}


def test_condition_alias_counts_as_same_lock():
    findings = lint(
        """
        import threading

        class Q:
            def __init__(self):
                self._lock = threading.Lock()
                self._cv = threading.Condition(self._lock)
                self._queue = []

            def put(self, x):
                with self._cv:
                    self._queue.append(x)
                    self._cv.notify()

            def drain(self):
                with self._lock:
                    out, self._queue = self._queue, []
                    return out
        """
    )
    assert "RTL201" not in rules_of(findings)


def test_module_class_with_acquire_and_release_counts_as_a_lock():
    """A lock wrapped in a class of the module (LLMServer's hand-off lock)
    guards state as the `threading.Lock` inside it does: a Condition over
    it is the same lock, and a read outside it is still a finding."""
    findings = lint(
        """
        import threading

        class Fair:
            def __init__(self):
                self._gate = threading.Lock()
                self._inner = threading.Lock()

            def acquire(self):
                with self._gate:
                    return self._inner.acquire()  # ray-tpu: lint-ignore[RTL202] the lock's own acquire

            def release(self):
                self._inner.release()

            __enter__ = acquire

            def __exit__(self, *exc):
                self._inner.release()

        class Q:
            def __init__(self):
                self._lock = Fair()
                self._cv = threading.Condition(self._lock)
                self._queue = []

            def put(self, x):
                with self._cv:
                    self._queue.append(x)

            def drain(self):
                with self._lock:
                    out, self._queue = self._queue, []
                    return out

            def peek(self):
                return len(self._queue)
        """
    )
    assert rules_of(findings) == ["RTL201"]
    assert findings[0].context.endswith("peek")


def test_unguarded_attrs_and_init_not_flagged():
    findings = lint(
        """
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self._config = {"a": 1}   # never mutated under the lock
                self._state = []

            def read_config(self):
                return self._config["a"]

            def mutate(self):
                with self._lock:
                    self._state.append(1)
        """
    )
    assert "RTL201" not in rules_of(findings)


def test_setup_style_lock_construction_exempt():
    # A method that CREATES the lock is init: nothing contends yet.
    findings = lint(
        """
        import threading

        class Algo:
            def setup(self):
                self._lock = threading.Lock()
                self._updates = 0

            def bump(self):
                with self._lock:
                    self._updates += 1
        """
    )
    assert "RTL201" not in rules_of(findings)


def test_nested_callback_access_not_flagged():
    findings = lint(
        """
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self._n = 0

            def inc(self):
                with self._lock:
                    self._n += 1

            def make_cb(self):
                def cb():
                    return self._n  # runs on another thread; out of scope
                return cb
        """
    )
    assert "RTL201" not in rules_of(findings)


def test_manual_acquire_flagged():
    findings = lint(
        """
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()

            def bad(self):
                self._lock.acquire()
                do_something()
                self._lock.release()
        """
    )
    assert "RTL202" in rules_of(findings)


# ---------------------------------------------------------------------------
# Family 3: JIT trace-safety + clock discipline
# ---------------------------------------------------------------------------


def test_jit_decorator_impurity_flagged():
    findings = lint(
        """
        import time
        import jax

        @jax.jit
        def step(x):
            t = time.time()
            return x + t
        """
    )
    assert "RTL301" in rules_of(findings)


def test_partial_jit_decorator_and_host_random():
    findings = lint(
        """
        from functools import partial
        import jax
        import numpy as np

        @partial(jax.jit, static_argnums=(1,))
        def noisy(x, n):
            return x + np.random.normal(size=n)
        """
    )
    assert "RTL301" in rules_of(findings)


def test_jit_call_form_and_self_method():
    findings = lint(
        """
        import jax

        class Runner:
            def __init__(self):
                self._fn = jax.jit(self._step)

            def _step(self, x):
                print("tracing!")
                return x * 2
        """
    )
    assert "RTL301" in rules_of(findings)


def test_shard_map_and_nested_def():
    findings = lint(
        """
        from ray_tpu._private.jax_compat import shard_map

        def build(mesh, specs, metrics):
            def body(x):
                metrics.observe(1.0)
                return x
            return shard_map(body, mesh=mesh, in_specs=specs, out_specs=specs)
        """
    )
    assert "RTL301" in rules_of(findings)


def test_pallas_call_body_impurity_flagged():
    """RTL301 trace-safety applies inside Pallas kernels too: a kernel body
    is traced exactly once, so host clocks/prints inside it are baked-in
    constants — including kernels handed to pallas_call via
    functools.partial, the idiom every ops/ kernel uses."""
    findings = lint(
        """
        import time
        import functools
        from jax.experimental import pallas as pl

        def kernel(x_ref, o_ref):
            t = time.time()
            o_ref[:] = x_ref[:] * t

        def call(x):
            return pl.pallas_call(
                functools.partial(kernel),
                out_shape=x,
            )(x)
        """
    )
    assert "RTL301" in rules_of(findings)


def test_pallas_call_name_bound_partial_resolved():
    """The partial is often bound to a local name first
    (`kernel = functools.partial(fn, ...)` then `pl.pallas_call(kernel)` —
    paged_flash.py's own shape); the resolver must see through the
    assignment or the repo's real kernels silently go unanalyzed."""
    findings = lint(
        """
        import time
        import functools
        from jax.experimental import pallas as pl

        def _kernel(x_ref, o_ref, scale):
            o_ref[:] = x_ref[:] * scale * time.time()

        def call(x):
            kernel = functools.partial(_kernel, scale=2.0)
            return pl.pallas_call(kernel, out_shape=x)(x)
        """
    )
    assert "RTL301" in rules_of(findings)


def test_pallas_call_local_rebinding_shadows_module_def():
    """Python scoping: a local `kernel = functools.partial(_impure)`
    shadows a clean module-level `def kernel` — the resolver must analyze
    the local binding (the function actually traced), not the shadowed
    def, or the impurity silently escapes."""
    findings = lint(
        """
        import time
        import functools
        from jax.experimental import pallas as pl

        def kernel(x_ref, o_ref):
            o_ref[:] = x_ref[:]

        def _impure(x_ref, o_ref):
            o_ref[:] = x_ref[:] * time.time()

        def call(x):
            kernel = functools.partial(_impure)
            return pl.pallas_call(kernel, out_shape=x)(x)
        """
    )
    assert "RTL301" in rules_of(findings)


def test_pallas_call_same_scope_rebinding_wins():
    """Within one scope the LATEST binding is what runtime traces: a
    `kernel = functools.partial(_impure)` after a clean local def must be
    the one analyzed; an unresolvable local rebinding must stop the walk
    (not fall through to a shadowed outer def)."""
    findings = lint(
        """
        import time
        import functools
        from jax.experimental import pallas as pl

        def _impure(x_ref, o_ref):
            o_ref[:] = x_ref[:] * time.time()

        def call(x):
            def kernel(x_ref, o_ref):
                o_ref[:] = x_ref[:]
            kernel = functools.partial(_impure)
            return pl.pallas_call(kernel, out_shape=x)(x)
        """
    )
    assert "RTL301" in rules_of(findings)

    findings = lint(
        """
        import time
        from jax.experimental import pallas as pl

        def kernel(x_ref, o_ref):
            o_ref[:] = x_ref[:] * time.time()

        def make_kernel():
            return None

        def call(x):
            kernel = make_kernel()  # unresolvable local: shadows the def
            return pl.pallas_call(kernel, out_shape=x)(x)
        """
    )
    assert "RTL301" not in rules_of(findings)


def test_pallas_call_rebinding_after_use_ignored():
    """A rebinding AFTER the pallas_call line has not executed when the
    call runs: the clean def actually traced must be the one analyzed —
    blaming the later impure rebinding is a false positive."""
    findings = lint(
        """
        import time
        import functools
        from jax.experimental import pallas as pl

        def _impure(x_ref, o_ref):
            o_ref[:] = x_ref[:] * time.time()

        def call(x):
            def kernel(x_ref, o_ref):
                o_ref[:] = x_ref[:]
            y = pl.pallas_call(kernel, out_shape=x)(x)
            kernel = functools.partial(_impure)
            return y
        """
    )
    assert "RTL301" not in rules_of(findings)


def test_pallas_call_opaque_local_bindings_stop_walk():
    """Tuple unpacking (and for/with targets) bind the name just as a
    plain assignment does: the resolver must stop at the opaque local
    binding, not blame a shadowed impure module-level def."""
    findings = lint(
        """
        import time
        from jax.experimental import pallas as pl

        def kernel(x_ref, o_ref):
            o_ref[:] = x_ref[:] * time.time()

        def make_kernels():
            return None, None

        def call(x):
            kernel, cfg = make_kernels()
            return pl.pallas_call(kernel, out_shape=x)(x)
        """
    )
    assert "RTL301" not in rules_of(findings)


def test_pallas_call_class_scope_not_in_method_chain():
    """Python skips class scope when resolving names inside methods: a
    sibling impure method named `kernel` must not be blamed when the bare
    name actually resolves to the clean module-level def."""
    findings = lint(
        """
        import time
        from jax.experimental import pallas as pl

        def kernel(x_ref, o_ref):
            o_ref[:] = x_ref[:]

        class Runner:
            def kernel(self, x_ref, o_ref):
                o_ref[:] = x_ref[:] * time.time()

            def call(self, x):
                return pl.pallas_call(kernel, out_shape=x)(x)
        """
    )
    assert "RTL301" not in rules_of(findings)


def test_pallas_call_ann_assign_binding_resolved():
    """An annotated assignment (`kernel: Callable = partial(...)`) binds
    exactly like a plain one: the impure kernel must be analyzed, and an
    AnnAssign shadowing a module def must stop the walk."""
    findings = lint(
        """
        import time
        import functools
        from typing import Callable
        from jax.experimental import pallas as pl

        def _impure(x_ref, o_ref):
            o_ref[:] = x_ref[:] * time.time()

        def call(x):
            kernel: Callable = functools.partial(_impure)
            return pl.pallas_call(kernel, out_shape=x)(x)
        """
    )
    assert "RTL301" in rules_of(findings)


def test_pallas_call_param_shadows_module_def():
    """A parameter named like a module-level def shadows it: the traced
    kernel is whatever the caller passes, so the resolver must stop
    rather than blame the (possibly impure) module def."""
    findings = lint(
        """
        import time
        from jax.experimental import pallas as pl

        def kernel(x_ref, o_ref):
            o_ref[:] = x_ref[:] * time.time()

        def call(x, kernel):
            return pl.pallas_call(kernel, out_shape=x)(x)
        """
    )
    assert "RTL301" not in rules_of(findings)


def test_pallas_call_foreign_scope_binding_not_resolved():
    """A sibling function's LOCAL `kernel = partial(...)` binds that
    function's namespace only: it must not resolve for an outer
    `pallas_call(kernel)` whose name the resolver can't actually see
    (flagging the wrong function would false-positive clean code)."""
    findings = lint(
        """
        import time
        import functools
        from jax.experimental import pallas as pl

        def _impure(x_ref, o_ref):
            o_ref[:] = x_ref[:] * time.time()

        def helper(x):
            kernel = functools.partial(_impure)
            return kernel

        def call(x, kernel):
            return pl.pallas_call(kernel, out_shape=x)(x)
        """
    )
    assert "RTL301" not in rules_of(findings)


def test_pallas_kernel_ref_writes_not_flagged():
    """Ref/scratch writes are writes to kernel ARGUMENTS — the whole point
    of a kernel — and must not trip the closure-mutation rule; closing
    over and mutating host state must."""
    findings = lint(
        """
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def kernel(x_ref, o_ref, acc_scratch):
            acc_scratch[:] = jnp.zeros_like(acc_scratch)
            o_ref[:] = x_ref[:] + acc_scratch[:]

        def call(x):
            return pl.pallas_call(kernel, out_shape=x)(x)
        """
    )
    assert "RTL303" not in rules_of(findings)
    assert "RTL301" not in rules_of(findings)

    findings = lint(
        """
        from jax.experimental import pallas as pl

        stats = {}

        def kernel(x_ref, o_ref):
            stats["traces"] = 1
            o_ref[:] = x_ref[:]

        def call(x):
            return pl.pallas_call(kernel, out_shape=x)(x)
        """
    )
    assert "RTL303" in rules_of(findings)


def test_pure_jax_random_not_flagged():
    findings = lint(
        """
        import jax

        @jax.jit
        def step(x, rng):
            noise = jax.random.normal(rng, x.shape)
            return x + noise
        """
    )
    assert "RTL301" not in rules_of(findings)


def test_jit_closure_mutation_flagged_but_local_ok():
    findings = lint(
        """
        import jax

        log = []

        @jax.jit
        def bad(x):
            log.append(x)
            return x

        @jax.jit
        def good(x):
            acc = []
            acc.append(x)
            return acc[0]
        """
    )
    assert rules_of(findings).count("RTL303") == 1


def test_jit_subscript_and_augassign_mutation_flagged():
    findings = lint(
        """
        import jax
        import functools

        stats = {"n": 0}

        @functools.partial(jax.jit, static_argnums=0)
        def bad(n, x):
            stats["n"] += 1
            return x * n

        class R:
            def build(self):
                self._fn = jax.jit(self._step)

            def _step(self, x):
                self.cache[0] = x
                return x

        @jax.jit
        def good(x):
            acc = {}
            acc["y"] = x
            return acc["y"]
        """
    )
    assert rules_of(findings).count("RTL303") == 2


def test_jit_self_assignment_flagged():
    findings = lint(
        """
        import jax

        class R:
            def build(self):
                self._fn = jax.jit(self._step)

            def _step(self, x):
                self.last = x
                return x
        """
    )
    assert "RTL303" in rules_of(findings)


def test_wallclock_deadline_and_duration_flagged():
    findings = lint(
        """
        import time

        def wait_for(pred, timeout):
            deadline = time.time() + timeout
            while time.time() < deadline:
                if pred():
                    return True
            return False

        def timed(fn):
            t0 = time.time()
            fn()
            return time.time() - t0
        """
    )
    assert rules_of(findings).count("RTL302") == 2


def test_monotonic_deadline_arithmetic_pinned():
    """The overload control plane derives per-request end-to-end
    deadlines as `time.monotonic() + timeout` and enforces them against
    time.monotonic() — this fixture pins the idiom clean while its
    wall-clock twin stays flagged, so deadline arithmetic can never
    drift onto a clock that steps under NTP."""
    findings = lint(
        """
        import time

        def submit_ok(timeout_s):
            deadline_s = time.monotonic() + timeout_s
            return time.monotonic() >= deadline_s

        def submit_bad(timeout_s):
            deadline_s = time.time() + timeout_s
            return time.time() >= deadline_s
        """
    )
    assert rules_of(findings).count("RTL302") == 1


def test_wallclock_identity_not_flagged():
    findings = lint(
        """
        import time

        def stamp(record):
            record["time"] = time.time()
            return record

        def duration_ok():
            t0 = time.perf_counter()
            return time.perf_counter() - t0
        """
    )
    assert "RTL302" not in rules_of(findings)


# ---------------------------------------------------------------------------
# Family 4: resource hygiene
# ---------------------------------------------------------------------------


def test_dropped_object_ref_flagged_and_bound_ok():
    findings = lint(
        """
        def fire(handle):
            handle.ping.remote()

        def keep(handle):
            ref = handle.ping.remote()
            return ref
        """
    )
    assert rules_of(findings) == ["RTL401"]


def test_cleared_before_commit_flagged_and_fixed_form_ok():
    findings = lint(
        """
        class Engine:
            def bad(self, seq):
                src, dst = seq.pending_copy
                seq.pending_copy = None
                self.runner.copy_block(src, dst)
                self.allocator.free([src])

            def good(self, seq):
                src, dst = seq.pending_copy
                self.runner.copy_block(src, dst)
                self.allocator.free([src])
                seq.pending_copy = None
        """
    )
    assert rules_of(findings) == ["RTL403"]
    assert findings[0].context.endswith("bad")


def test_leaky_acquire_flagged_and_try_ok():
    findings = lint(
        """
        class S:
            def bad(self, n):
                blocks = self.allocator.allocate(n)
                self.compute(blocks)
                self.allocator.free(blocks)

            def good(self, n):
                blocks = self.allocator.allocate(n)
                try:
                    self.compute(blocks)
                finally:
                    self.allocator.free(blocks)
        """
    )
    rtl404 = [f for f in findings if f.rule == "RTL404"]
    assert len(rtl404) == 1 and rtl404[0].context.endswith("bad")


def test_leaky_acquire_kv_fabric_restore_path_fixture():
    """KV-fabric restore ordering fixture: restore slots come from an
    allocate() whose failure path frees them, and each slot is committed
    copy-in (restore_block) FIRST, register AFTER — a half-written block
    must never become discoverable. The acquire outside any try (bad) is
    exactly the shape RTL404 exists for: a raise inside the copy-in loop
    skips the free and leaks every slot in the plan."""
    findings = lint(
        """
        class Engine:
            def bad(self, plan):
                tail = self.allocator.allocate(len(plan))
                for block, h in zip(tail, plan):
                    self.runner.restore_block(block, self.fabric.get(h))
                    self.allocator.register(block, h)
                self.allocator.free(tail)

            def good(self, plan):
                tail = self.allocator.allocate(len(plan))
                try:
                    for block, h in zip(tail, plan):
                        self.runner.restore_block(block, self.fabric.get(h))
                        self.allocator.register(block, h)
                except Exception:
                    self.allocator.free(tail)
                    raise
        """
    )
    rtl404 = [f for f in findings if f.rule == "RTL404"]
    assert len(rtl404) == 1 and rtl404[0].context.endswith("bad")


# ---------------------------------------------------------------------------
# Suppressions + baseline round-trip
# ---------------------------------------------------------------------------


def test_suppression_with_reason_suppresses():
    findings = lint(
        """
        def fire(handle):
            # ray-tpu: lint-ignore[RTL401] metrics push is fire-and-forget
            handle.ping.remote()
        """
    )
    assert findings == []


def test_suppression_inline_and_wildcard():
    findings = lint(
        """
        def fire(handle):
            handle.ping.remote()  # ray-tpu: lint-ignore[*] intentional
        """
    )
    assert findings == []


def test_suppression_without_reason_is_reported_not_honored():
    findings = lint(
        """
        def fire(handle):
            # ray-tpu: lint-ignore[RTL401]
            handle.ping.remote()
        """
    )
    assert sorted(rules_of(findings)) == ["RTL002", "RTL401"]


def test_suppression_for_other_rule_does_not_mask():
    findings = lint(
        """
        def fire(handle):
            # ray-tpu: lint-ignore[RTL999] wrong id on purpose
            handle.ping.remote()
        """
    )
    assert "RTL401" in rules_of(findings)


def test_stacked_standalone_suppressions_both_honored():
    """Regression: two standalone lint-ignore comments above one statement
    both resolve to that statement's line; the second used to overwrite
    the first so neither finding stayed suppressed."""
    findings = lint(
        """
        import threading

        class Box:
            def __init__(self):
                self._lock = threading.Lock()
                self._n = 0

            def bump(self):
                with self._lock:
                    self._n += 1

            def fire(self, handle):
                # ray-tpu: lint-ignore[RTL201] snapshot read is fine here
                # ray-tpu: lint-ignore[RTL401] fire-and-forget by design
                handle.ping.remote(self._n)
        """
    )
    assert findings == []


def test_skip_dirs_only_apply_below_scan_root(tmp_path):
    """Regression: a checkout under a hidden/`build` ancestor used to be
    skipped entirely, making the gate vacuously clean on 0 files."""
    root = tmp_path / ".cache" / "build" / "proj"
    pkg = root / "pkg"
    pkg.mkdir(parents=True)
    (pkg / "mod.py").write_text("def fire(h):\n    h.ping.remote()\n")
    (pkg / "__pycache__").mkdir()
    (pkg / "__pycache__" / "mod.py").write_text("def fire(h):\n    h.ping.remote()\n")
    (root / "pyproject.toml").write_text("[project]\nname='x'\n")

    result = lint_paths([pkg], root=root)
    assert result.files_scanned == 1  # __pycache__ below the root still skipped
    assert rules_of(result.findings) == ["RTL401"]


def test_suppression_covers_multiline_statement():
    """Regression: a finding anchored to a continuation line of a
    black-wrapped statement escaped the ignore comment above it (the
    suppression mapped only to the statement's first line)."""
    findings = lint(
        """
        import threading

        class Box:
            def __init__(self):
                self._lock = threading.Lock()
                self._x = 0

            def bump(self):
                with self._lock:
                    self._x += 1

            def peek(self):
                # ray-tpu: lint-ignore[RTL201] racy snapshot is fine here
                return (
                    self._x
                    + 1
                )

            def also_bad(self):
                return self._x
        """
    )
    # The wrapped read is suppressed; the ignore must NOT leak past its
    # statement to `also_bad`.
    assert rules_of(findings) == ["RTL201"]
    assert findings[0].context.endswith("also_bad")


def test_suppression_on_compound_header_does_not_blanket_block():
    findings = lint(
        """
        def fire(h, cond):
            # ray-tpu: lint-ignore[RTL401] header-anchored, body must flag
            if cond(
                h
            ):
                h.ping.remote()
        """
    )
    # The body finding is NOT suppressed — and the header-anchored ignore
    # therefore protects nothing, which RTL003 reports as rot.
    assert rules_of(findings) == ["RTL003", "RTL401"]


def test_scoped_run_does_not_report_out_of_scope_baseline_stale(tmp_path):
    """Regression: a path- or rule-scoped run used to report every
    baseline entry it could not have re-produced as stale, telling users
    to regenerate (and dashboards that the baseline rotted)."""
    pkg = _write_pkg(tmp_path)  # mod.py: RTL302 + RTL401
    full = lint_paths([pkg], root=tmp_path)
    baseline = {
        f.fingerprint: baseline_mod.entry_for(f, "triaged: fixture")
        for f in full.findings
    }

    by_rule = lint_paths(
        [pkg], rule_ids=["RTL302"], root=tmp_path, baseline=baseline
    )
    assert by_rule.stale_baseline == []

    other = tmp_path / "other"
    other.mkdir()
    (other / "clean.py").write_text("x = 1\n")
    by_path = lint_paths([other], root=tmp_path, baseline=baseline)
    assert by_path.stale_baseline == []

    # A genuinely-fixed finding in scope still reports stale.
    (pkg / "mod.py").write_text("x = 1\n")
    fixed = lint_paths([pkg], root=tmp_path, baseline=baseline)
    assert len(fixed.stale_baseline) == 2


def test_baseline_round_trip(tmp_path):
    src = textwrap.dedent(
        """
        def fire(handle):
            handle.ping.remote()
        """
    )
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text(src)
    (tmp_path / "pyproject.toml").write_text("[project]\nname='x'\n")

    result = lint_paths([pkg], root=tmp_path)
    assert rules_of(result.findings) == ["RTL401"]

    # Baseline it with a reason -> clean; entry survives line drift.
    bl = tmp_path / baseline_mod.BASELINE_FILENAME
    baseline_mod.save_baseline(
        bl, [baseline_mod.entry_for(result.findings[0], "known fire-forget")]
    )
    baseline = baseline_mod.load_baseline(bl)
    again = lint_paths([pkg], root=tmp_path, baseline=baseline)
    assert again.findings == [] and len(again.baselined) == 1

    (pkg / "mod.py").write_text("# a new comment line\n" + src)
    drifted = lint_paths([pkg], root=tmp_path, baseline=baseline)
    assert drifted.findings == [] and len(drifted.baselined) == 1

    # Fixing the finding leaves a stale entry, reported as such.
    (pkg / "mod.py").write_text("def fire(h):\n    return h.ping.remote()\n")
    fixed = lint_paths([pkg], root=tmp_path, baseline=baseline)
    assert fixed.findings == [] and fixed.stale_baseline


# ---------------------------------------------------------------------------
# CLI: --json contract, --rule filter, exit codes
# ---------------------------------------------------------------------------


def _write_pkg(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text(
        "import time\n\n"
        "def t(fn):\n"
        "    t0 = time.time()\n"
        "    fn()\n"
        "    return time.time() - t0\n\n"
        "def fire(h):\n"
        "    h.ping.remote()\n"
    )
    (tmp_path / "pyproject.toml").write_text("[project]\nname='x'\n")
    return pkg


def test_cli_json_shape(tmp_path, capsys, monkeypatch):
    pkg = _write_pkg(tmp_path)
    monkeypatch.chdir(tmp_path)
    rc = lint_main([str(pkg), "--json"])
    report = json.loads(capsys.readouterr().out)
    assert rc == 1
    # Schema version 3: the diff-scoped scan added files_checked (new
    # keys never appear under an old version number, so external
    # consumers can gate on report shape).
    assert report["version"] == 3
    assert report["schema"] == "ray-tpu-lint-report/3"
    assert report["files_scanned"] == 1
    assert report["files_checked"] == 1
    assert set(report["counts"]) == {
        "active", "baselined", "suppressed", "parse_errors",
        "stale_baseline", "untriaged_baseline",
    }
    assert report["counts"]["active"] == len(report["findings"]) == 2
    finding = report["findings"][0]
    assert set(finding) == {
        "rule", "name", "family", "path", "line", "col", "context",
        "message", "fingerprint",
    }
    assert {f["rule"] for f in report["findings"]} == {"RTL302", "RTL401"}


def test_cli_rule_filter_and_exit_codes(tmp_path, capsys, monkeypatch):
    pkg = _write_pkg(tmp_path)
    monkeypatch.chdir(tmp_path)
    rc = lint_main([str(pkg), "--rule", "RTL401", "--json"])
    report = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert {f["rule"] for f in report["findings"]} == {"RTL401"}
    # Filtering to a rule with no findings -> exit 0.
    assert lint_main([str(pkg), "--rule", "RTL102"]) == 0
    capsys.readouterr()
    assert lint_main([str(tmp_path / "nope")]) == 2


def test_cli_write_baseline_then_clean(tmp_path, capsys, monkeypatch):
    pkg = _write_pkg(tmp_path)
    monkeypatch.chdir(tmp_path)
    assert lint_main([str(pkg), "--write-baseline"]) == 0
    capsys.readouterr()
    bl_path = tmp_path / baseline_mod.BASELINE_FILENAME
    data = json.loads(bl_path.read_text())
    assert len(data["findings"]) == 2
    # TODO reasons gate: still exit 1 until a human writes reasons.
    assert lint_main([str(pkg)]) == 1
    capsys.readouterr()
    for e in data["findings"]:
        e["reason"] = "triaged: intentional in this fixture"
    bl_path.write_text(json.dumps(data))
    assert lint_main([str(pkg)]) == 0


def test_overlapping_scan_paths_deduplicated(tmp_path):
    """Regression: `lint pkg pkg/sub` used to scan sub's files twice —
    the duplicate findings got occurrence-shifted fingerprints that no
    longer matched the baseline, resurfacing grandfathered entries."""
    pkg = _write_pkg(tmp_path)
    result = lint_paths(
        [tmp_path, pkg, pkg / "mod.py"], root=tmp_path
    )
    assert result.files_scanned == 1
    assert len(result.findings) == 2

    bl = [
        baseline_mod.entry_for(f, "triaged: fixture")
        for f in result.findings
    ]
    baseline = {e["fingerprint"]: e for e in bl}
    again = lint_paths([tmp_path, pkg], root=tmp_path, baseline=baseline)
    assert again.findings == [] and len(again.baselined) == 2


def test_cli_lint_reachable_through_argparse_dispatch(capsys):
    """Regression: `ray-tpu --num-cpus 2 lint ...` bypasses the argv[0]
    fast-path intercept and used to die with KeyError('lint') in the
    handler dict."""
    from ray_tpu.scripts.cli import main as ray_tpu_main

    rc = ray_tpu_main(
        ["--num-cpus", "2", "lint", "--", "--list-rules"]
    )
    out = capsys.readouterr().out
    assert rc == 0
    assert "RTL201" in out
    # And the fast path still owns bare `lint` flags.
    assert ray_tpu_main(["lint", "--list-rules"]) == 0


def test_write_baseline_scoped_run_preserves_out_of_scope(
    tmp_path, capsys, monkeypatch
):
    """Regression: a --write-baseline scoped by path or --rule used to
    treat every entry outside the scan as stale, deleting triaged
    reasons; re-running also used to re-stamp written reasons with TODO."""
    pkg_a = _write_pkg(tmp_path)  # RTL302 + RTL401
    pkg_b = tmp_path / "other"
    pkg_b.mkdir()
    (pkg_b / "mod.py").write_text("def fire(h):\n    h.ping.remote()\n")
    monkeypatch.chdir(tmp_path)
    bl_path = tmp_path / baseline_mod.BASELINE_FILENAME

    assert lint_main([str(pkg_a), str(pkg_b), "--write-baseline"]) == 0
    capsys.readouterr()
    data = json.loads(bl_path.read_text())
    assert len(data["findings"]) == 3
    for e in data["findings"]:
        e["reason"] = "triaged: intentional in this fixture"
    bl_path.write_text(json.dumps(data))

    # Path-scoped rewrite: pkg_b's entry and every written reason survive.
    assert lint_main([str(pkg_a), "--write-baseline"]) == 0
    out = capsys.readouterr().out
    assert "0 new" in out
    data = json.loads(bl_path.read_text())
    assert len(data["findings"]) == 3
    assert all(e["reason"].startswith("triaged") for e in data["findings"])

    # Rule-scoped rewrite after fixing that rule's finding: only the
    # in-scope stale entry drops.
    (pkg_a / "mod.py").write_text(
        "import time\n\ndef t(fn):\n    t0 = time.time()\n    fn()\n"
        "    return time.time() - t0\n"
    )
    assert lint_main(
        [str(pkg_a), str(pkg_b), "--rule", "RTL401", "--write-baseline"]
    ) == 0
    capsys.readouterr()
    data = json.loads(bl_path.read_text())
    assert {e["rule"] for e in data["findings"]} == {"RTL302", "RTL401"}
    assert len(data["findings"]) == 2  # pkg_a RTL401 dropped, RTL302 kept
    assert lint_main([str(pkg_a), str(pkg_b)]) == 0


def test_unused_suppression_flagged_only_on_full_runs():
    """An orphaned reasoned lint-ignore (hazard fixed, or comment drifted
    off the statement) is rot: RTL003 on full runs. A rule-scoped run
    must stay silent — the other rules never had a chance to match it —
    and a docstring SHOWING the idiom is string content, not a comment."""
    src = """
        def fire(h):
            # ray-tpu: lint-ignore[RTL401] nothing below fires this rule
            return h.value
        """
    assert rules_of(lint(src)) == ["RTL003"]

    from ray_tpu.tools.lint.rules_resources import DroppedObjectRefRule

    assert lint(src, rules=[DroppedObjectRefRule()]) == []

    used = lint(
        """
        def fire(h):
            # ray-tpu: lint-ignore[RTL401] fire-and-forget by design
            h.ping.remote()
        """
    )
    assert used == []

    doc = lint(
        '''
        def helper():
            """Suppress false positives like this:

                x()  # ray-tpu: lint-ignore[RTL201] probe reads stale bool
            """
            return 1
        '''
    )
    assert doc == []


def test_cli_json_parse_errors_not_mixed_into_findings(
    tmp_path, capsys, monkeypatch
):
    """Regression: --json used to append RTL001 parse errors into the
    `findings` array while counts.active excluded them, so a consumer
    gating on counts.active == 0 rendered 'clean' beside a non-empty
    findings list."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "broken.py").write_text("def broken(:\n")
    (tmp_path / "pyproject.toml").write_text("[project]\nname='x'\n")
    monkeypatch.chdir(tmp_path)
    rc = lint_main([str(pkg), "--json"])
    report = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert report["counts"]["active"] == len(report["findings"]) == 0
    assert report["counts"]["parse_errors"] == 1
    assert [e["rule"] for e in report["parse_errors"]] == ["RTL001"]


def test_write_baseline_preserves_entries_of_unparseable_file(
    tmp_path, capsys, monkeypatch
):
    """Regression: --write-baseline used to drop the triaged entries (and
    their written reasons) of any file with a transient syntax error —
    the file produced no findings, so its entries looked stale. Once the
    file parsed again its findings came back active and broke the gate."""
    pkg = _write_pkg(tmp_path)  # mod.py: RTL302 + RTL401
    monkeypatch.chdir(tmp_path)
    bl_path = tmp_path / baseline_mod.BASELINE_FILENAME

    assert lint_main([str(pkg), "--write-baseline"]) == 0
    capsys.readouterr()
    data = json.loads(bl_path.read_text())
    assert len(data["findings"]) == 2
    for e in data["findings"]:
        e["reason"] = "triaged: intentional in this fixture"
    bl_path.write_text(json.dumps(data))

    good_source = (pkg / "mod.py").read_text()
    (pkg / "mod.py").write_text(good_source + "def broken(:\n")
    assert lint_main([str(pkg), "--write-baseline"]) == 0
    capsys.readouterr()
    data = json.loads(bl_path.read_text())
    assert len(data["findings"]) == 2
    assert all(e["reason"].startswith("triaged") for e in data["findings"])

    (pkg / "mod.py").write_text(good_source)
    assert lint_main([str(pkg)]) == 0


# ---------------------------------------------------------------------------
# The repo gate
# ---------------------------------------------------------------------------


def test_repo_is_lint_clean():
    """`python -m ray_tpu.tools.lint ray_tpu/` must exit 0: every finding
    on the tree is fixed, suppressed with a reason, or baselined with a
    reason — and the scan, INCLUDING the cross-module project pass the
    RTL5xx/6xx/7xx families ride on, fits the CI budget (<10s; `make
    lint` runs the same gate outside pytest)."""
    # The gate runs the full registry: donation/sharding/actor/shape
    # families must be in it, or a tree full of use-after-donates (or
    # drifted bucket tables) reads as clean.
    families = {r.id[:4] for r in all_rules()}
    assert {"RTL5", "RTL6", "RTL7", "RTL8"} <= families
    baseline = baseline_mod.load_baseline(
        REPO_ROOT / baseline_mod.BASELINE_FILENAME
    )
    result = lint_paths(
        [REPO_ROOT / "ray_tpu"], baseline=baseline, root=REPO_ROOT
    )
    assert result.parse_errors == []
    assert result.findings == [], "\n".join(
        f"{f.path}:{f.line} {f.rule} {f.message}" for f in result.findings
    )
    assert not result.stale_baseline, (
        "stale baseline entries (regenerate with --write-baseline): "
        f"{result.stale_baseline}"
    )
    assert baseline_mod.untriaged(baseline) == []
    assert result.duration_s < 10.0
    assert result.files_scanned > 150  # __pycache__/generated skipped


def test_every_suppression_in_repo_has_reason():
    """The inline-ignore idiom requires a reason everywhere in ray_tpu/."""
    result = lint_paths(
        [REPO_ROOT / "ray_tpu"],
        rule_ids=["RTL002"],
        baseline={},
        root=REPO_ROOT,
    )
    assert result.findings == []


# ---------------------------------------------------------------------------
# Rule examples are executable: every rule's --explain snippets double as
# fixture tests (one firing + one exempt per rule), so the CLI's examples
# can never drift from what the rule actually flags.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "rule", all_rules(), ids=lambda r: r.id
)
def test_rule_example_pair_fires_and_stays_clean(rule):
    assert rule.rationale, f"{rule.id} has no rationale for --explain"
    assert rule.bad_example and rule.good_example
    bad = rules_of(lint(rule.bad_example))
    good = rules_of(lint(rule.good_example))
    assert rule.id in bad, f"{rule.id} does not fire on its own bad example"
    assert rule.id not in good, f"{rule.id} fires on its own good example"


# ---------------------------------------------------------------------------
# Family 5: donation / JAX-perf
# ---------------------------------------------------------------------------


def test_use_after_donate_in_loop_without_rebind():
    """A donating call inside a loop donates the same name every
    iteration: with no rebind, the second iteration reads a dead buffer."""
    findings = lint(
        """
        import jax

        def train(step_fn, params, batches):
            step = jax.jit(step_fn, donate_argnums=(0,))
            losses = []
            for batch in batches:
                out = step(params, batch)
                losses.append(out[1])
            return losses
        """
    )
    assert "RTL501" in rules_of(findings)

    findings = lint(
        """
        import jax

        def train(step_fn, params, batches):
            step = jax.jit(step_fn, donate_argnums=(0,))
            losses = []
            for batch in batches:
                params, loss = step(params, batch)
                losses.append(loss)
            return params
        """
    )
    assert "RTL501" not in rules_of(findings)


def test_use_after_donate_self_attr_binding_and_argnames():
    """Donation through a self-attr binding (`self._fn = jax.jit(...)`),
    with donate_argnames mapped through the wrapped method's params."""
    findings = lint(
        """
        import jax

        class Runner:
            def __init__(self):
                self._fn = jax.jit(self._step, donate_argnames=("cache",))

            def _step(self, cache, x):
                return cache + x, x

            def run(self, x):
                new_cache, y = self._fn(self.cache, x)
                stale = self.cache.sum()  # donated buffer
                self.cache = new_cache
                return y, stale
        """
    )
    assert "RTL501" in rules_of(findings)

    findings = lint(
        """
        import jax

        class Runner:
            def __init__(self):
                self._fn = jax.jit(self._step, donate_argnames=("cache",))

            def _step(self, cache, x):
                return cache + x, x

            def run(self, x):
                self.cache, y = self._fn(self.cache, x)
                total = self.cache.sum()  # the NEW buffer
                return y, total
        """
    )
    assert "RTL501" not in rules_of(findings)


def test_use_after_donate_starred_positions_not_guessed():
    """Positions at/after a *splat are unknowable — the rule must stay
    silent rather than blame the wrong argument (model_runner's own
    `self._decode_fn(self.params, *self._pools, ...)` shape)."""
    findings = lint(
        """
        import jax

        class R:
            def __init__(self):
                self._fn = jax.jit(self._step, donate_argnums=(1, 2))

            def _step(self, a, b, c):
                return a, b, c

            def run(self, x):
                out = self._fn(self.params, *self.pools, x)
                return self.pools  # position unknown: no claim
        """
    )
    assert "RTL501" not in rules_of(findings)


def test_unstable_static_arg_shapes():
    """List literal (unhashable) and a non-frozen dataclass resolved
    ACROSS modules both destroy the jit cache; a frozen dataclass has
    eq+hash and is exempt."""
    findings = lint(
        """
        import jax

        def run(fn, x):
            f = jax.jit(fn, static_argnums=(1,))
            return f(x, [1, 2, 3])
        """
    )
    assert "RTL502" in rules_of(findings)

    cfg = """
        import dataclasses

        @dataclasses.dataclass
        class StepConfig:
            n: int = 1

        @dataclasses.dataclass(frozen=True)
        class FrozenConfig:
            n: int = 1
    """
    findings = lint_files(
        {
            "pkg/cfg.py": cfg,
            "pkg/run.py": """
                import jax
                from pkg.cfg import StepConfig

                def run(fn, x):
                    f = jax.jit(fn, static_argnums=(1,))
                    return f(x, StepConfig(n=2))
            """,
        }
    )
    assert "RTL502" in rules_of(findings)

    findings = lint_files(
        {
            "pkg/cfg.py": cfg,
            "pkg/run.py": """
                import jax
                from pkg.cfg import FrozenConfig

                def run(fn, x):
                    f = jax.jit(fn, static_argnums=(1,))
                    return f(x, FrozenConfig(n=2))
            """,
        }
    )
    assert "RTL502" not in rules_of(findings)


def test_unbucketed_len_shape_flagged_bucket_helper_exempt():
    """A len()-derived array shape fed to a jitted program compiles one
    program per distinct length; routing the size through a bucketing
    helper (model_runner's `bucket_for`) is the sanctioned form."""
    findings = lint(
        """
        import jax
        import numpy as np

        def prefill(fn, token_ids):
            step = jax.jit(fn)
            n = len(token_ids)
            tokens = np.zeros((1, n), np.int32)
            return step(tokens)
        """
    )
    assert "RTL502" in rules_of(findings)

    findings = lint(
        """
        import jax
        import numpy as np

        def prefill(fn, cfg, token_ids):
            step = jax.jit(fn)
            n = len(token_ids)
            bucket = cfg.bucket_for(n)
            tokens = np.zeros((1, bucket), np.int32)
            return step(tokens)
        """
    )
    assert "RTL502" not in rules_of(findings)


def test_host_sync_item_in_while_loop_and_post_loop_exempt():
    findings = lint(
        """
        import jax

        def fit(step_fn, params, n):
            step = jax.jit(step_fn)
            i = 0
            while i < n:
                params, loss = step(params)
                print_loss = loss.item()
                i += 1
            return params
        """
    )
    assert "RTL503" in rules_of(findings)

    findings = lint(
        """
        import jax

        def fit(step_fn, params, n):
            step = jax.jit(step_fn)
            losses = []
            for _ in range(n):
                params, loss = step(params)
                losses.append(loss)
            return params, [x.item() for x in losses]
        """
    )
    assert "RTL503" not in rules_of(findings)


def test_ngram_proposer_host_matching_in_step_loop_not_flagged():
    """Speculative decoding's n-gram proposer is pure host-side token
    matching on python lists — list slicing, comparisons, np.asarray of
    host data — with no jitted result anywhere in its dataflow. Running
    it inside the engine step loop (which also dispatches a jitted verify
    step) must NOT read as a host-device sync: RTL503 is about syncing
    the jitted result, not about the loop doing host work."""
    findings = lint(
        """
        import jax
        import numpy as np

        def match(history, k):
            tail = history[-3:]
            for start in range(len(history) - 4, -1, -1):
                if history[start : start + 3] == tail:
                    return history[start + 3 : start + 3 + k]
            return []

        def serve_loop(step_fn, params, histories, n):
            step = jax.jit(step_fn)
            for _ in range(n):
                proposals = [match(h, 4) for h in histories]
                batch = np.asarray([p + [0] * (4 - len(p)) for p in proposals])
                params, out = step(params, batch)
            return params, out
        """
    )
    assert "RTL503" not in rules_of(findings)
    # Positive control so the negative above can't be a dead rule: the
    # same loop syncing the verify output per iteration IS the defect.
    findings = lint(
        """
        import jax
        import numpy as np

        def serve_loop(step_fn, params, histories, n):
            step = jax.jit(step_fn)
            accepted = []
            for _ in range(n):
                params, out = step(params, histories)
                accepted.append(np.asarray(out))
            return params, accepted
        """
    )
    assert "RTL503" in rules_of(findings)


def test_host_sync_device_get_and_block_until_ready_flagged():
    findings = lint(
        """
        import jax

        def fit(step_fn, params, batches):
            step = jax.jit(step_fn)
            out = []
            for b in batches:
                params, m = step(params, b)
                out.append(jax.device_get(m))
            return params, out
        """
    )
    assert "RTL503" in rules_of(findings)

    findings = lint(
        """
        import jax

        def fit(step_fn, params, batches):
            step = jax.jit(step_fn)
            for b in batches:
                params, m = step(params, b)
                jax.block_until_ready(m)
            return params
        """
    )
    assert "RTL503" in rules_of(findings)


def test_host_sync_prefetched_copy_to_host_async_exempt():
    """The async-engine deferred-commit idiom: dispatch step N+1, start
    `copy_to_host_async()` on its output, then block-read step N's value
    (whose copy has been in flight a whole step). That blocking read is
    a commit, not a stall — RTL503 must stay quiet, including through
    the `prev = out` alias that carries the one-step-behind buffer."""
    findings = lint(
        """
        import jax
        import numpy as np

        def serve_loop(step_fn, params, n):
            step = jax.jit(step_fn)
            prev = None
            committed = []
            for _ in range(n):
                params, out = step(params)
                out.copy_to_host_async()
                if prev is not None:
                    committed.append(np.asarray(prev))
                prev = out
            return params, committed
        """
    )
    assert "RTL503" not in rules_of(findings)
    # Positive control: same loop shape, but the dispatch path reads the
    # fresh result synchronously — no prefetch in flight, device stalls.
    findings = lint(
        """
        import jax
        import numpy as np

        def serve_loop(step_fn, params, n):
            step = jax.jit(step_fn)
            committed = []
            for _ in range(n):
                params, next_tokens = step(params)
                committed.append(np.asarray(next_tokens))
            return params, committed
        """
    )
    assert "RTL503" in rules_of(findings)


# ---------------------------------------------------------------------------
# Family 6: sharding consistency
# ---------------------------------------------------------------------------


def test_spec_axis_resolved_through_cross_module_constant():
    """The mesh's axis tuple lives in another module (the
    parallel/mesh.py AXIS_ORDER shape): a spec axis missing from it is a
    proven mismatch; a spec using those axes is clean."""
    mesh_mod = """
        AXIS_ORDER = ("dp", "tp")

        def build_mesh(devs):
            from jax.sharding import Mesh
            return Mesh(devs, AXIS_ORDER)
    """
    findings = lint_files(
        {
            "pkg/mesh.py": mesh_mod,
            "pkg/run.py": """
                from jax.sharding import PartitionSpec as P
                from ray_tpu._private.jax_compat import shard_map
                from pkg.mesh import build_mesh

                def run(fn, x, devs):
                    mesh = build_mesh(devs)
                    f = shard_map(fn, mesh=mesh, in_specs=(P("model"),),
                                  out_specs=P("dp"))
                    return f(x)
            """,
        }
    )
    assert "RTL601" in rules_of(findings)

    findings = lint_files(
        {
            "pkg/mesh.py": mesh_mod,
            "pkg/run.py": """
                from jax.sharding import PartitionSpec as P
                from ray_tpu._private.jax_compat import shard_map
                from pkg.mesh import build_mesh

                def run(fn, x, devs):
                    mesh = build_mesh(devs)
                    f = shard_map(fn, mesh=mesh, in_specs=(P("tp"),),
                                  out_specs=P("dp"))
                    return f(x)
            """,
        }
    )
    assert "RTL601" not in rules_of(findings)


def test_spec_axis_through_specbuild_method():
    """`Spec(...).build()` resolves through the class's build() returns
    (the MeshSpec.build shape)."""
    findings = lint(
        """
        from jax.sharding import Mesh, PartitionSpec as P
        from ray_tpu._private.jax_compat import shard_map

        AXES = ("pp", "dp")

        class Spec:
            def build(self, devs):
                return Mesh(devs, AXES)

        def run(fn, x, devs):
            mesh = Spec().build(devs)
            f = shard_map(fn, mesh=mesh, in_specs=(P("sp"),),
                          out_specs=P("dp"))
            return f(x)
        """
    )
    assert "RTL601" in rules_of(findings)


def test_unknown_mesh_stays_silent():
    """A mesh that is a bare parameter is not statically known — the
    rule must not guess."""
    findings = lint(
        """
        from jax.sharding import PartitionSpec as P
        from ray_tpu._private.jax_compat import shard_map

        def run(fn, x, mesh):
            f = shard_map(fn, mesh=mesh, in_specs=(P("anything"),),
                          out_specs=P("whatever"))
            return f(x)
        """
    )
    assert "RTL601" not in rules_of(findings)


def test_collective_axis_partial_decorator_and_unknown_mesh_silent():
    """The partial-decorator shard_map form (pipeline.py's shape) with a
    resolvable mesh: a collective over an axis outside the mesh fires.
    With the mesh a bare parameter, shard_map binds ALL of its (unknown)
    axes — the specs are only a subset — so the rule must stay silent
    even for axes the specs never name (psum over an idle mesh axis with
    replicated input is legal and common)."""
    findings = lint(
        """
        import jax
        from functools import partial
        from jax.sharding import Mesh, PartitionSpec as P
        from ray_tpu._private.jax_compat import shard_map

        def build(devs):
            mesh = Mesh(devs, ("pp", "dp"))

            @partial(shard_map, mesh=mesh, in_specs=(P("pp"),),
                     out_specs=P("pp"))
            def run(x):
                stage = jax.lax.axis_index("pp")
                return jax.lax.psum(x, "sp") + stage
            return run
        """
    )
    # "pp"/"dp" are mesh axes; "sp" is not.
    rtl602 = [f for f in findings if f.rule == "RTL602"]
    assert len(rtl602) == 1
    assert "'sp'" in rtl602[0].message

    findings = lint(
        """
        import jax
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from ray_tpu._private.jax_compat import shard_map

        def build(mesh):
            @partial(shard_map, mesh=mesh, in_specs=(P("pp"),),
                     out_specs=P("pp"))
            def run(x):
                return jax.lax.psum(x, "dp")  # may be a real mesh axis
            return run
        """
    )
    assert "RTL602" not in rules_of(findings)


def test_collective_axis_in_pmap_body():
    findings = lint(
        """
        import jax

        def grad_sync(x):
            return jax.lax.pmean(x, "devices")

        def run(x):
            return jax.pmap(grad_sync, axis_name="batch")(x)
        """
    )
    assert "RTL602" in rules_of(findings)

    findings = lint(
        """
        import jax

        def grad_sync(x):
            return jax.lax.pmean(x, "batch")

        def run(x):
            return jax.pmap(grad_sync, axis_name="batch")(x)
        """
    )
    assert "RTL602" not in rules_of(findings)


# ---------------------------------------------------------------------------
# Family 7: actor call-graph deadlocks
# ---------------------------------------------------------------------------


def test_same_actor_blocking_get_via_partial_bound_remote():
    """functools.partial-bound remote methods resolve to the underlying
    handle (the satellite cross-module shape)."""
    findings = lint(
        """
        import functools
        import ray_tpu

        @ray_tpu.remote
        class Coord:
            def __init__(self):
                self._peer = Coord.remote()

            def helper(self, x):
                return x

            def run(self, x):
                fire = functools.partial(self._peer.helper.remote, x)
                ref = fire()
                return ray_tpu.get(ref)
        """
    )
    assert "RTL701" in rules_of(findings)


def test_cross_actor_cycle_with_aliased_import():
    """A -> B -> A across modules, with B's class imported under another
    name (actor-class-aliased-at-import satellite)."""
    findings = lint_files(
        {
            "pkg/beta.py": """
                import ray_tpu
                from pkg import alpha

                @ray_tpu.remote
                class Beta:
                    def __init__(self):
                        self._a = alpha.Alpha.remote()

                    def pong(self, x):
                        return ray_tpu.get(self._a.poke.remote(x))
            """,
            "pkg/alpha.py": """
                import ray_tpu

                @ray_tpu.remote
                class Alpha:
                    def __init__(self):
                        from pkg.beta import Beta as Remote_B
                        self._b = Remote_B.remote()

                    def ping(self, x):
                        return ray_tpu.get(self._b.pong.remote(x))

                    def poke(self, x):
                        return x
            """,
        }
    )
    assert rules_of(findings).count("RTL702") == 2

    # One-way dependency: no cycle, no finding.
    findings = lint_files(
        {
            "pkg/beta.py": """
                import ray_tpu

                @ray_tpu.remote
                class Beta:
                    def pong(self, x):
                        return x + 1
            """,
            "pkg/alpha.py": """
                import ray_tpu
                from pkg.beta import Beta

                @ray_tpu.remote
                class Alpha:
                    def __init__(self):
                        self._b = Beta.remote()

                    def ping(self, x):
                        return ray_tpu.get(self._b.pong.remote(x))
            """,
        }
    )
    assert "RTL702" not in rules_of(findings)


def test_registered_handle_name_resolves_cross_module():
    """`RemoteX = ray_tpu.remote(X)` registrations resolve from another
    module (the rllib RemoteEnvRunner shape)."""
    findings = lint_files(
        {
            "pkg/worker.py": """
                import ray_tpu

                class Worker:
                    def work(self, x):
                        return x

                RemoteWorker = ray_tpu.remote(Worker)
            """,
            "pkg/driver.py": """
                import ray_tpu
                from pkg.worker import RemoteWorker

                @ray_tpu.remote
                class Driver:
                    def __init__(self):
                        self._w = RemoteWorker.options(num_cpus=0).remote()

                    def run(self, x):
                        return ray_tpu.get(self._w.work.remote(x))
            """,
        }
    )
    # One-way blocking call: NOT a deadlock — no findings, but the edge
    # resolving at all is what this test pins (a cycle through the same
    # registration shape must then be detectable).
    assert "RTL702" not in rules_of(findings)
    assert "RTL701" not in rules_of(findings)


# ---------------------------------------------------------------------------
# Cross-module resolution edge cases (tentpole satellite)
# ---------------------------------------------------------------------------


def test_jit_of_imported_function_attributed_to_defining_module():
    """`jax.jit(imported_fn)` analyzes the function in ITS module and
    attributes the finding there."""
    findings = lint_files(
        {
            "pkg/steps.py": """
                import time

                def step(x):
                    return x * time.time()
            """,
            "pkg/run.py": """
                import jax
                from pkg.steps import step

                def run(x):
                    return jax.jit(step)(x)
            """,
        }
    )
    rtl301 = [f for f in findings if f.rule == "RTL301"]
    assert len(rtl301) == 1
    assert rtl301[0].path == "pkg/steps.py"


def test_import_alias_chain_resolves():
    """`from x import y as z` chains terminate at the real definition."""
    findings = lint_files(
        {
            "pkg/a.py": """
                import time

                def impure_step(x):
                    return x * time.time()
            """,
            "pkg/b.py": """
                from pkg.a import impure_step as hop1
            """,
            "pkg/c.py": """
                import jax
                from pkg.b import hop1 as hop2

                def run(x):
                    return jax.jit(hop2)(x)
            """,
        }
    )
    rtl301 = [f for f in findings if f.rule == "RTL301"]
    assert len(rtl301) == 1
    assert rtl301[0].path == "pkg/a.py"


def test_reexport_through_package_init_resolves():
    """Re-exports through __init__.py resolve like the real module path."""
    findings = lint_files(
        {
            "pkg/__init__.py": """
                from pkg.inner import step
            """,
            "pkg/inner.py": """
                import time

                def step(x):
                    return x + time.time()
            """,
            "app.py": """
                import jax
                import pkg

                def run(x):
                    return jax.jit(pkg.step)(x)
            """,
        }
    )
    rtl301 = [f for f in findings if f.rule == "RTL301"]
    assert len(rtl301) == 1
    assert rtl301[0].path == "pkg/inner.py"


def test_cross_module_finding_suppressable_in_defining_module():
    """The inline ignore lives where the finding lands: the DEFINING
    module, even when the jit call is elsewhere."""
    findings = lint_files(
        {
            "pkg/steps.py": """
                import time

                def step(x):
                    # ray-tpu: lint-ignore[RTL301] trace-time stamp is the
                    # documented behavior of this fixture
                    return x * time.time()
            """,
            "pkg/run.py": """
                import jax
                from pkg.steps import step

                def run(x):
                    return jax.jit(step)(x)
            """,
        }
    )
    assert "RTL301" not in rules_of(findings)


# ---------------------------------------------------------------------------
# Family 8: abstract shape/dtype/sharding interpretation (RTL801-805)
# ---------------------------------------------------------------------------


def test_shape_mismatch_with_cross_module_config_constants():
    """RTL801 seeds call-site shapes from statically-resolved config
    constants ACROSS modules (the existing constant-resolver path), so
    a bucket/head-dim mismatch between caller and traced body is caught
    even when the numbers live in a config module."""
    findings = lint_files(
        {
            "cfg.py": "BLOCK = 8\nHEADS = 4\n",
            "eng.py": """
                import jax
                import jax.numpy as jnp
                import cfg

                def step(pool, new):
                    return pool.reshape((cfg.BLOCK, cfg.HEADS))

                def run():
                    f = jax.jit(step)
                    x = jnp.zeros((cfg.BLOCK, cfg.HEADS + 1))
                    return f(x, None)
            """,
        }
    )
    hits = [f for f in findings if f.rule == "RTL801"]
    assert len(hits) == 1
    assert hits[0].path == "eng.py"
    assert "reshape" in hits[0].message


def test_shape_mismatch_symbolic_dims_stay_silent():
    """`B` vs `C` is NOT a provable mismatch (nothing rules out B == C
    at runtime): symbolic-but-different dims must stay silent — the
    no-false-positives-by-construction contract."""
    src = """
        import jax
        import jax.numpy as jnp

        def step(x, w):
            return x @ w

        def run(b, c):
            f = jax.jit(step)
            return f(jnp.zeros((4, b)), jnp.zeros((c, 16)))
    """
    assert "RTL801" not in rules_of(lint(src))


def test_shape_mismatch_unknown_arg_stays_silent():
    """TOP case: an argument whose shape comes from an unresolvable
    helper is unknown — no rule in the family may fire on it."""
    src = """
        import jax
        import jax.numpy as jnp
        from somewhere import load_buffer

        def step(x, w):
            return x @ w

        def run():
            f = jax.jit(step)
            return f(load_buffer(), jnp.zeros((4, 16)))
    """
    assert rules_of(lint(src)) == []


def test_shape_mismatch_symbolic_slice_start_stays_silent():
    """Regression: a slice with a SYMBOLIC start and concrete stop
    (`x[k:5]`) must not be modeled as size 5 — with k == 1 at runtime
    the reshape below is perfectly valid, and one false positive fails
    the whole gate."""
    src = """
        import jax
        import jax.numpy as jnp

        def step(x, k):
            return x[k:5].reshape(4)

        def run(k):
            f = jax.jit(step)
            return f(jnp.zeros((8,)), k)
    """
    assert "RTL801" not in rules_of(lint(src))


def test_shape_mismatch_symbolic_affine_fires():
    """Affine arithmetic over ONE symbol is decidable: `n` rows vs
    `n + 1` rows differ by a nonzero constant whatever n is."""
    src = """
        import jax
        import jax.numpy as jnp

        def step(x, y):
            return jnp.concatenate([x, y], axis=1)

        def run(n):
            f = jax.jit(step)
            return f(jnp.zeros((n, 4)), jnp.zeros((n + 1, 4)))
    """
    assert "RTL801" in rules_of(lint(src))


def test_donation_mismatch_unknown_output_stays_silent():
    """TOP case for RTL802: when any output's geometry is unknown, the
    donated buffer might alias it — silence."""
    src = """
        import jax
        import jax.numpy as jnp
        from somewhere import mystery

        def step(buf, x):
            return mystery(buf + x)

        def run():
            f = jax.jit(step, donate_argnums=(0,))
            return f(jnp.zeros((8, 4), jnp.float32),
                     jnp.zeros((8, 4), jnp.float32))
    """
    assert "RTL802" not in rules_of(lint(src))


def test_donation_through_self_attr_program_symbolic_pools():
    """The runner idiom: pools donated through a self-attr jit binding
    and returned through the step — symbolic shapes flow end to end and
    the donation provably aliases (clean); an astype on the way out
    provably breaks it (fires)."""
    clean = """
        import jax
        import jax.numpy as jnp

        class Runner:
            def __init__(self, layers, blocks, bs, heads, dim):
                shape = (layers, blocks, bs, heads, dim)
                self.pool = jnp.zeros(shape, jnp.float32)
                self._fn = jax.jit(self._step, donate_argnums=(0,))

            def _step(self, pool, new):
                return pool.at[0].set(new), new

            def run(self, new):
                pool, out = self._fn(self.pool, new)
                self.pool = pool
                return out
    """
    assert "RTL802" not in rules_of(lint(clean))
    bad = """
        import jax
        import jax.numpy as jnp

        class Runner:
            def __init__(self, layers, blocks, bs, heads, dim):
                shape = (layers, blocks, bs, heads, dim)
                self.pool = jnp.zeros(shape, jnp.float32)
                self._fn = jax.jit(self._step, donate_argnums=(0,))

            def _step(self, pool, new):
                return pool.astype(jnp.bfloat16)

            def run(self, new):
                return self._fn(self.pool, new)
    """
    assert "RTL802" in rules_of(lint(bad))


def test_sharding_divisibility_symbolic_odd_dim_fires():
    """Symbolic divisibility is decidable for the constant remainder:
    `2*b + 1` is odd whatever b is, so a dp axis of size 2 can never
    divide it."""
    src = """
        import jax
        import jax.numpy as jnp
        from jax.experimental import mesh_utils
        from jax.sharding import Mesh, NamedSharding
        from jax.sharding import PartitionSpec as P

        def place(b):
            mesh = Mesh(
                mesh_utils.create_device_mesh((2, 4)), ("dp", "tp")
            )
            x = jnp.zeros((2 * b + 1, 4))
            return jax.device_put(x, NamedSharding(mesh, P("dp", None)))
    """
    assert "RTL803" in rules_of(lint(src))


def test_sharding_unknown_mesh_stays_silent():
    """TOP case for RTL803: a mesh handed in as a parameter has unknown
    axis sizes — silence, exactly like RTL601's unknown-mesh rule."""
    src = """
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        def place(mesh):
            x = jnp.zeros((9, 4))
            return jax.device_put(x, NamedSharding(mesh, P("dp", None)))
    """
    assert rules_of(lint(src)) == []


def test_shard_map_in_specs_divisibility_checked():
    """shard_map call-site args are checked against in_specs + the mesh
    resolved through the compat shim import (the repo's own spelling)."""
    src = """
        import jax
        import jax.numpy as jnp
        from jax.experimental import mesh_utils
        from jax.sharding import Mesh, PartitionSpec as P
        from ray_tpu._private.jax_compat import shard_map

        def body(x):
            return x

        def run():
            mesh = Mesh(mesh_utils.create_device_mesh((4,)), ("dp",))
            f = shard_map(body, mesh=mesh, in_specs=(P("dp"),),
                          out_specs=P("dp"))
            return f(jnp.zeros((10, 3)))
    """
    assert "RTL803" in rules_of(lint(src))


def test_paired_pool_scale_dtype_and_write_coverage():
    """RTL804's two forms: an int dtype scale pool fires; a pool write
    with no paired scale write fires (the CoW copy_block hazard); a
    None-guarded scale write is the sanctioned pattern and stays clean."""
    bad_dtype = """
        import jax.numpy as jnp

        def build(n, bs, h, d):
            k_cache = jnp.zeros((2, n, bs, h * d), jnp.int8)
            k_scale = jnp.zeros((2, n, bs, h), jnp.int32)
            return k_cache, k_scale
    """
    assert "RTL804" in rules_of(lint(bad_dtype))
    bad_copy = """
        def copy_block(k_cache, k_scale, src, dst):
            k_cache = k_cache.at[:, dst].set(k_cache[:, src])
            return k_cache, k_scale
    """
    assert "RTL804" in rules_of(lint(bad_copy))
    guarded = """
        def copy_block(k_cache, k_scale, src, dst):
            k_cache = k_cache.at[:, dst].set(k_cache[:, src])
            if k_scale is not None:
                k_scale = k_scale.at[:, dst].set(k_scale[:, src])
            return k_cache, k_scale
    """
    assert "RTL804" not in rules_of(lint(guarded))


@pytest.mark.parametrize(
    "scale_shape, fires",
    [
        ("(2, n, bs, h)", False),      # the stored form: same rank
        ("(2, n, bs)", True),          # a rank short
        ("(2, n + 1, bs, h)", True),   # a leading axis that is not the pool's
    ],
)
def test_paired_pool_shape_law_is_the_stored_form(scale_shape, fires):
    """Pools are stored [L, N, bs, H*D] and scales [L, N, bs, H]: the same
    rank, every axis but the minor one shared."""
    src = f"""
        import jax.numpy as jnp

        def build(n, bs, h, d):
            k_cache = jnp.zeros((2, n, bs, h * d), jnp.int8)
            k_scale = jnp.zeros({scale_shape}, jnp.bfloat16)
            return k_cache, k_scale
    """
    assert ("RTL804" in rules_of(lint(src))) is fires


def test_paired_pool_unknown_geometry_stays_silent():
    """TOP case for RTL804: pools built from an opaque helper have
    unknown dtype/shape — silence. A branch-joined scale (None on one
    arm) is TOP too."""
    src = """
        import jax.numpy as jnp
        from somewhere import pool_shape

        def build(quantized):
            k_cache = jnp.zeros(pool_shape(), jnp.int8)
            if quantized:
                k_scale = jnp.zeros(pool_shape())
            else:
                k_scale = None
            return k_cache, k_scale
    """
    assert "RTL804" not in rules_of(lint(src))


def test_bucket_drift_between_two_tables_fires():
    """Two call sites of one program driven by two INCOMPARABLE bucket
    tables: whichever one warmup used, the other demands widths it
    never compiled — provable drift."""
    src = """
        import jax
        import jax.numpy as jnp

        WARM = (8, 16, 24)
        LIVE = (8, 16, 32)

        def step(t):
            return t

        def run(n):
            f = jax.jit(step)
            for b in WARM:
                f(jnp.zeros((1, b), jnp.int32))
            for b in LIVE:
                f(jnp.zeros((1, b), jnp.int32))
    """
    assert "RTL805" in rules_of(lint(src))
    # A strict SUBSET is legal (live uses fewer buckets than warmed).
    subset = src.replace("LIVE = (8, 16, 32)", "LIVE = (8, 16)")
    assert "RTL805" not in rules_of(lint(subset))


def test_chunk_width_table_subset_of_partial_prefill_buckets_is_clean():
    """Chunked prefill's invariant, expressed to RTL805: the chunk-width
    table (the widths the chunked warmup compiles) must stay a subset of
    the partial-prefill bucket table (the widths the live path feeds).
    Both tables resolve statically across modules; a strict subset is
    exactly the legal shape (a budget caps which buckets chunks reach)."""
    findings = lint_files(
        {
            "cfg.py": """
                BUCKETS = (8, 16, 32)
                # Budget 16: chunks only ever reach the first two buckets.
                CHUNK_WIDTHS = (8, 16)

                def bucket_for(n):
                    for b in BUCKETS:
                        if b >= n:
                            return b
                    raise ValueError(n)
            """,
            "runner.py": """
                import jax
                import jax.numpy as jnp
                from cfg import BUCKETS, CHUNK_WIDTHS, bucket_for

                def partial_prefill(t):
                    return t

                def warmup():
                    f = jax.jit(partial_prefill)
                    for w in CHUNK_WIDTHS:
                        f(jnp.zeros((1, w), jnp.int32))

                def serve_chunk(n):
                    f = jax.jit(partial_prefill)
                    f(jnp.zeros((1, bucket_for(n)), jnp.int32))
            """,
        }
    )
    assert "RTL805" not in {f.rule for f in findings}


def test_chunk_width_table_drift_from_bucket_table_fires():
    """Drift between the chunk-width table and the partial-prefill bucket
    table = a guaranteed cold compile (warmup compiles widths the live
    path never feeds, the live path feeds a width warmup never compiled)
    — caught statically, in the module that drifted."""
    findings = lint_files(
        {
            "cfg.py": """
                BUCKETS = (8, 16, 32)
                CHUNK_WIDTHS = (8, 24)  # 24 is not a bucket: drift
            """,
            "runner.py": """
                import jax
                import jax.numpy as jnp
                from cfg import BUCKETS, CHUNK_WIDTHS

                def partial_prefill(t):
                    return t

                def warmup():
                    f = jax.jit(partial_prefill)
                    for w in CHUNK_WIDTHS:
                        f(jnp.zeros((1, w), jnp.int32))

                def serve(n):
                    f = jax.jit(partial_prefill)
                    for b in BUCKETS:
                        f(jnp.zeros((1, b), jnp.int32))
            """,
        }
    )
    hits = [f for f in findings if f.rule == "RTL805"]
    assert hits and hits[0].path == "runner.py"
    assert "drifted" in hits[0].message or "bucket table" in hits[0].message


def test_bucket_coverage_unknown_width_stays_silent():
    """TOP case for RTL805: an unknown width (or an opaque whole shape)
    is never a provable cold compile."""
    src = """
        import jax
        import jax.numpy as jnp

        BUCKETS = (8, 16)

        def step(t):
            return t

        def run(n, shape):
            f = jax.jit(step)
            for b in BUCKETS:
                f(jnp.zeros((1, b), jnp.int32))
            f(jnp.zeros(shape, jnp.int32))
            f(jnp.zeros((1, n), jnp.int32))
    """
    assert rules_of(lint(src)) == []


def test_bucket_lookup_helper_resolves_to_table_membership():
    """A `bucket_for`-style helper (first table entry >= n) abstractly
    returns element-of-table, so padded live-path widths count as
    covered — and a cross-module literal outside the table fires in the
    module that feeds it."""
    findings = lint_files(
        {
            "cfg.py": """
                BUCKETS = (8, 16, 32)

                def bucket_for(n):
                    for b in BUCKETS:
                        if b >= n:
                            return b
                    raise ValueError(n)
            """,
            "run.py": """
                import jax
                import jax.numpy as jnp
                from cfg import BUCKETS, bucket_for

                def step(t):
                    return t

                def serve(n):
                    f = jax.jit(step)
                    for b in BUCKETS:
                        f(jnp.zeros((1, b), jnp.int32))
                    f(jnp.zeros((1, bucket_for(n)), jnp.int32))
                    f(jnp.zeros((1, 24), jnp.int32))
            """,
        }
    )
    hits = [f for f in findings if f.rule == "RTL805"]
    assert len(hits) == 1
    assert hits[0].path == "run.py"
    assert "24" in hits[0].message


# ---------------------------------------------------------------------------
# --changed: diff-scoped scans
# ---------------------------------------------------------------------------


def test_changed_only_scopes_rules_to_reverse_import_closure(tmp_path):
    """lint_paths(changed_only=...) parses everything but runs rules
    only on the changed files plus their importers: an unchanged,
    unrelated module's finding must NOT appear; an importer of the
    changed module IS re-checked."""
    (tmp_path / "pyproject.toml").write_text("[project]\nname='x'\n")
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "base.py").write_text("VALUE = 3\n")
    (pkg / "uses.py").write_text(
        "import time\n\nfrom pkg.base import VALUE\n\n\n"
        "def wait(t):\n"
        "    deadline = time.time() + t\n"
        "    while time.time() < deadline:\n"
        "        pass\n"
    )
    (pkg / "unrelated.py").write_text(
        "def fire(h):\n    h.ping.remote()\n"
    )
    result = lint_paths(
        [pkg], root=tmp_path, changed_only=["pkg/base.py"]
    )
    # Closure: base.py itself + its importer uses.py — not unrelated.py.
    assert result.checked_relpaths == {"pkg/base.py", "pkg/uses.py"}
    assert {f.rule for f in result.findings} == {"RTL302"}
    assert result.files_scanned == 4  # everything still parsed

    # An empty diff checks nothing and is clean.
    result = lint_paths([pkg], root=tmp_path, changed_only=[])
    assert result.checked_relpaths == set()
    assert result.findings == []


def test_changed_cli_flag_against_real_git(tmp_path, capsys, monkeypatch):
    """End to end: `ray-tpu lint --changed` diffs against git HEAD —
    a committed-clean tree reports nothing; touching one file (and
    adding an untracked one) scopes the scan to the diff closure."""
    import shutil
    import subprocess

    if shutil.which("git") is None:
        pytest.skip("git not available")

    def git(*argv):
        subprocess.run(
            ["git", "-C", str(tmp_path), "-c", "user.email=t@t",
             "-c", "user.name=t", *argv],
            check=True, capture_output=True,
        )

    (tmp_path / "pyproject.toml").write_text("[project]\nname='x'\n")
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "mod.py").write_text("VALUE = 3\n")
    git("init", "-q")
    git("add", "-A")
    git("commit", "-qm", "seed")
    monkeypatch.chdir(tmp_path)

    assert lint_main([str(pkg), "--changed", "--json"]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["files_checked"] == 0
    assert report["files_scanned"] == 2

    # Tracked modification + an untracked file both land in the diff.
    (pkg / "mod.py").write_text(
        "import time\n\n\ndef wait(t):\n"
        "    deadline = time.time() + t\n"
        "    while time.time() < deadline:\n"
        "        pass\n"
    )
    (pkg / "fresh.py").write_text(
        "def fire(h):\n    h.ping.remote()\n"
    )
    rc = lint_main([str(pkg), "--changed", "--json"])
    report = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert report["files_checked"] == 2
    assert {f["rule"] for f in report["findings"]} == {
        "RTL302", "RTL401",
    }
    # Outside a work tree (git errors) the flag is a usage error, not
    # a crash — simulated, since tmp_path itself IS a work tree here.
    from ray_tpu.tools.lint import cli as cli_mod

    monkeypatch.setattr(
        cli_mod, "_git_changed_files", lambda root: None
    )
    assert lint_main([str(pkg), "--changed"]) == 2
    capsys.readouterr()


def test_changed_relativizes_to_lint_root_in_monorepo(
    tmp_path, capsys, monkeypatch
):
    """Regression: the lint root (pyproject.toml) can be a SUBDIRECTORY
    of the git toplevel. `git diff --name-only` prints toplevel-relative
    paths, which match no module relpath — without --relative a
    monorepo `lint --changed` silently checked zero files and exited 0
    over real findings."""
    import shutil
    import subprocess

    if shutil.which("git") is None:
        pytest.skip("git not available")

    def git(*argv):
        subprocess.run(
            ["git", "-C", str(tmp_path), "-c", "user.email=t@t",
             "-c", "user.name=t", *argv],
            check=True, capture_output=True,
        )

    sub = tmp_path / "service"
    pkg = sub / "pkg"
    pkg.mkdir(parents=True)
    (sub / "pyproject.toml").write_text("[project]\nname='x'\n")
    (pkg / "__init__.py").write_text("")
    (pkg / "mod.py").write_text("VALUE = 3\n")
    git("init", "-q")
    git("add", "-A")
    git("commit", "-qm", "seed")
    (pkg / "mod.py").write_text(
        "import time\n\n\ndef wait(t):\n"
        "    deadline = time.time() + t\n"
        "    while time.time() < deadline:\n"
        "        pass\n"
    )
    monkeypatch.chdir(sub)
    rc = lint_main([str(pkg), "--changed", "--json"])
    report = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert report["files_checked"] == 1
    assert {f["rule"] for f in report["findings"]} == {"RTL302"}


def test_changed_closure_includes_bare_dotted_importers(tmp_path):
    """`import pkg.base` (no `as`) must register a dependency on
    pkg/base.py, not just pkg/__init__.py, or the importer escapes the
    --changed closure. Same for `from pkg.base import *`, which binds
    no alias at all. And deleting a module entirely must still seed the
    closure with its former importers — a pure deletion re-checks
    everything that resolved symbols through the deleted file."""
    (tmp_path / "pyproject.toml").write_text("[project]\nname='x'\n")
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "base.py").write_text("VALUE = 3\n")
    (pkg / "uses.py").write_text(
        "import pkg.base\n\nX = pkg.base.VALUE\n"
    )
    (pkg / "star.py").write_text("from pkg.base import *\n")
    result = lint_paths(
        [pkg], root=tmp_path, changed_only=["pkg/base.py"]
    )
    assert "pkg/uses.py" in result.checked_relpaths
    assert "pkg/star.py" in result.checked_relpaths
    # Deleted module: the path has no ModuleInfo, but importers of its
    # module name (here via `import pkg.gone`) are still re-checked.
    (pkg / "needs_gone.py").write_text(
        "import pkg.gone\n\nY = pkg.gone.VALUE\n"
    )
    result = lint_paths(
        [pkg], root=tmp_path, changed_only=["pkg/gone.py"]
    )
    assert "pkg/needs_gone.py" in result.checked_relpaths


def test_changed_run_still_sees_cross_module_bucket_tables(tmp_path):
    """The RTL805 site sweep stays PROJECT-wide on diff-scoped runs: a
    checked module's literal width must still be judged against the
    bucket table that warms the program from an UNCHECKED module —
    otherwise a triaged entry would read as stale and --write-baseline
    would drop it."""
    (tmp_path / "pyproject.toml").write_text("[project]\nname='x'\n")
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "warm.py").write_text(textwrap.dedent(
        """
        import jax
        import jax.numpy as jnp

        BUCKETS = (8, 16, 32)

        def step(t):
            return t

        PROG = jax.jit(step)

        def warmup():
            for b in BUCKETS:
                PROG(jnp.zeros((1, b), jnp.int32))
        """
    ))
    (pkg / "live.py").write_text(textwrap.dedent(
        """
        import jax.numpy as jnp
        from pkg.warm import PROG

        def serve():
            PROG(jnp.zeros((1, 24), jnp.int32))
        """
    ))
    full = lint_paths([pkg], root=tmp_path)
    assert "RTL805" in {f.rule for f in full.findings}
    scoped = lint_paths(
        [pkg], root=tmp_path, changed_only=["pkg/live.py"]
    )
    assert "pkg/warm.py" not in scoped.checked_relpaths
    assert "RTL805" in {f.rule for f in scoped.findings}


def test_write_baseline_changed_scope_preserves_unchecked_entries(
    tmp_path, capsys, monkeypatch
):
    """Regression: --write-baseline used to scope stale-dropping by
    scan PATHS, so a diff-scoped run (file parsed but not checked)
    would have treated every unchecked file's triaged entries as stale
    and deleted them. The write must scope to the CHECKED set — the
    files whose rules actually ran."""
    (tmp_path / "pyproject.toml").write_text("[project]\nname='x'\n")
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "a.py").write_text(
        "import time\n\n\ndef wait(t):\n"
        "    deadline = time.time() + t\n"
        "    while time.time() < deadline:\n"
        "        pass\n"
    )
    (pkg / "b.py").write_text("def fire(h):\n    h.ping.remote()\n")
    monkeypatch.chdir(tmp_path)
    bl_path = tmp_path / baseline_mod.BASELINE_FILENAME
    assert lint_main([str(pkg), "--write-baseline"]) == 0
    capsys.readouterr()
    data = json.loads(bl_path.read_text())
    assert len(data["findings"]) == 2
    for e in data["findings"]:
        e["reason"] = "triaged: fixture"
    bl_path.write_text(json.dumps(data))

    # Diff-scoped rewrite touching only a.py: b.py was parsed but NOT
    # checked — its triaged entry (and reason) must survive verbatim.
    from ray_tpu.tools.lint import cli as cli_mod

    monkeypatch.setattr(
        cli_mod, "_git_changed_files", lambda root: {"pkg/a.py"}
    )
    assert lint_main([str(pkg), "--changed", "--write-baseline"]) == 0
    capsys.readouterr()
    data = json.loads(bl_path.read_text())
    assert {e["rule"] for e in data["findings"]} == {"RTL302", "RTL401"}
    assert all(
        e["reason"] == "triaged: fixture" for e in data["findings"]
    )
    # The checked file's entry DOES drop once its finding is fixed.
    (pkg / "a.py").write_text("VALUE = 3\n")
    assert lint_main([str(pkg), "--changed", "--write-baseline"]) == 0
    capsys.readouterr()
    data = json.loads(bl_path.read_text())
    assert {e["rule"] for e in data["findings"]} == {"RTL401"}
    assert lint_main([str(pkg)]) == 0
    capsys.readouterr()


# ---------------------------------------------------------------------------
# CLI: --sarif, --explain
# ---------------------------------------------------------------------------


def test_cli_sarif_shape(tmp_path, capsys, monkeypatch):
    pkg = _write_pkg(tmp_path)  # mod.py: RTL302 + RTL401
    monkeypatch.chdir(tmp_path)
    rc = lint_main([str(pkg), "--sarif"])
    report = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert report["version"] == "2.1.0"
    assert report["$schema"].endswith("sarif-schema-2.1.0.json")
    run = report["runs"][0]
    driver = run["tool"]["driver"]
    assert driver["name"] == "ray-tpu-lint"
    ids = {r["id"] for r in driver["rules"]}
    assert {"RTL501", "RTL601", "RTL701"} <= ids
    # The RTL8xx catalog rides the same driver (make lint-sarif).
    assert {
        "RTL801", "RTL802", "RTL803", "RTL804", "RTL805",
    } <= ids
    results = run["results"]
    assert {r["ruleId"] for r in results} == {"RTL302", "RTL401"}
    for r in results:
        assert r["level"] == "warning"
        loc = r["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"].endswith("mod.py")
        assert loc["region"]["startLine"] >= 1
        assert r["partialFingerprints"]["rayTpuLint/v1"]
    # Clean tree -> empty results, exit 0.
    clean = tmp_path / "clean"
    clean.mkdir()
    (clean / "ok.py").write_text("x = 1\n")
    capsys.readouterr()
    assert lint_main([str(clean), "--sarif"]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["runs"][0]["results"] == []


def test_cli_explain_prints_rationale_and_examples(capsys):
    assert lint_main(["--explain", "RTL501"]) == 0
    out = capsys.readouterr().out
    assert "use-after-donate" in out
    assert "Why:" in out
    assert "Fires on:" in out and "Clean form:" in out
    assert "donate_argnums" in out
    # By name works too; unknown rule is a usage error.
    assert lint_main(["--explain", "cross-actor-call-cycle"]) == 0
    capsys.readouterr()
    assert lint_main(["--explain", "RTL999"]) == 2


def test_actor_cycle_through_reachable_helper():
    """The actor-method reachability index: a blocking get inside a
    plain helper function REACHED from an actor method (through the
    project call graph, across modules) contributes that actor's edge —
    here closing an A→B→A cycle whose first leg lives in a helper."""
    findings = lint_files(
        {
            "pkg/helpers.py": """
                import ray_tpu
                from pkg.beta import Beta

                def fetch_pong(x):
                    h = Beta.remote()
                    return ray_tpu.get(h.pong.remote(x))
            """,
            "pkg/alpha.py": """
                import ray_tpu
                from pkg.helpers import fetch_pong

                @ray_tpu.remote
                class Alpha:
                    def ping(self, x):
                        return fetch_pong(x)

                    def poke(self, x):
                        return x
            """,
            "pkg/beta.py": """
                import ray_tpu

                @ray_tpu.remote
                class Beta:
                    def __init__(self):
                        from pkg.alpha import Alpha
                        self._a = Alpha.remote()

                    def pong(self, x):
                        return ray_tpu.get(self._a.poke.remote(x))
            """,
        }
    )
    rtl702 = [f for f in findings if f.rule == "RTL702"]
    assert len(rtl702) == 2
    assert {f.path for f in rtl702} == {"pkg/helpers.py", "pkg/beta.py"}
    # The helper-side finding names the reaching method.
    helper_f = [f for f in rtl702 if f.path == "pkg/helpers.py"][0]
    assert "via fetch_pong" in helper_f.message


def test_decorated_method_donate_argnums_rebased_on_call_args():
    """A decorated METHOD's donate_argnums count `self`; call sites pass
    args without it. Position 1 of `def step(self, params, batch)` is
    `params` — the rule must flag a later read of params, not batch."""
    findings = lint(
        """
        import functools
        import jax

        class Trainer:
            @functools.partial(jax.jit, donate_argnums=(1,))
            def step(self, params, batch):
                return params, batch

            def fit(self, params, batch):
                new_params, out = self.step(params, batch)
                stale = params.sum()   # donated (argnum 1 == params)
                tail = batch.sum()     # NOT donated
                return new_params, stale, tail
        """
    )
    rtl501 = [f for f in findings if f.rule == "RTL501"]
    assert len(rtl501) == 1
    assert "`params`" in rtl501[0].message


def test_attr_jit_bindings_keyed_per_class_with_inheritance():
    """Review regression: `self._fn` in one class must not resolve to
    another class's jit binding of the same attribute name — but a
    SUBCLASS method must still see a binding its parent's __init__ set
    up (the PerPolicyMultiAgentRunner shape)."""
    findings = lint(
        """
        import jax

        class Donating:
            def __init__(self, f):
                self._fn = jax.jit(f, donate_argnums=(0,))

        class Plain:
            def __init__(self, fn):
                self._fn = fn

            def run(self, params, x):
                y = self._fn(params, x)
                return params.sum(), y  # _fn here never donates
        """
    )
    assert "RTL501" not in rules_of(findings)

    findings = lint(
        """
        import jax

        class Base:
            def __init__(self, f):
                self._fn = jax.jit(f, donate_argnums=(0,))

        class Sub(Base):
            def run(self, params, x):
                y = self._fn(params, x)
                return params.sum(), y  # inherited donating binding
        """
    )
    assert "RTL501" in rules_of(findings)


def test_jnp_asarray_is_a_device_op_not_a_sync():
    """Review regression: jnp.asarray of a device array stays on device;
    only a NUMPY-rooted asarray/array forces the host transfer."""
    findings = lint(
        """
        import jax
        import jax.numpy as jnp

        def fit(step_fn, params, batches):
            step = jax.jit(step_fn)
            out = []
            for b in batches:
                params, m = step(params, b)
                out.append(jnp.asarray(m))  # device op, no host read
            return params, out
        """
    )
    assert "RTL503" not in rules_of(findings)

    findings = lint(
        """
        import jax
        import numpy as np

        def fit(step_fn, params, batches):
            step = jax.jit(step_fn)
            out = []
            for b in batches:
                params, m = step(params, b)
                out.append(np.asarray(m))  # host transfer every step
            return params, out
        """
    )
    assert "RTL503" in rules_of(findings)


def test_function_local_registration_does_not_leak():
    """Review regression: a method-local `h = ray_tpu.remote(Cls)` must
    not register module-wide, and an OPAQUE local binding of the same
    name elsewhere must not fall back to any registration."""
    findings = lint(
        """
        import ray_tpu

        @ray_tpu.remote
        class Driver:
            def spawn(self):
                h = ray_tpu.remote(Driver)
                return h

            def poll(self):
                h = make_handle()  # opaque: class unknown
                return ray_tpu.get(h.work.remote(1))

            def work(self, x):
                return x
        """
    )
    assert "RTL701" not in rules_of(findings)


# ---------------------------------------------------------------------------
# Tensor-parallel LLM engine: head-axis PartitionSpecs vs the engine mesh
# ---------------------------------------------------------------------------


def test_llm_tp_head_spec_against_engine_mesh_clean_and_typo_fires():
    """RTL601 pins the engine's head-axis sharding idiom: the serving mesh
    is built MeshSpec.build-style over the full AXIS_ORDER tuple, and the
    head spec P(None, None, 'tp') (ops.attention.head_sharded_call's
    shape) names an axis that mesh really has — clean. A spec naming an
    axis the mesh lacks (say the LOGICAL axis name 'heads' leaking in
    where the MESH axis 'tp' belongs) must fire: under check_vma=False a
    wrong axis silently means replicated, i.e. every chip would run every
    head and the tp memory win would quietly vanish."""
    engine_mesh = """
        from jax.sharding import Mesh

        AXIS_ORDER = ("pp", "dp", "fsdp", "ep", "sp", "tp")

        class MeshSpec:
            def build(self, devs):
                return Mesh(devs, AXIS_ORDER)
    """
    clean = lint_files(
        {
            "pkg/mesh.py": engine_mesh,
            "pkg/runner.py": """
                from jax.sharding import PartitionSpec as P
                from ray_tpu._private.jax_compat import shard_map
                from pkg.mesh import MeshSpec

                def paged_attention_tp(fn, q, k_cache, devs):
                    mesh = MeshSpec().build(devs)
                    head_spec = P(None, None, "tp")
                    f = shard_map(
                        fn, mesh=mesh,
                        in_specs=(head_spec, head_spec, P()),
                        out_specs=head_spec, check_vma=False,
                    )
                    return f(q, k_cache, None)
            """,
        }
    )
    assert "RTL601" not in rules_of(clean)

    typo = lint_files(
        {
            "pkg/mesh.py": engine_mesh,
            "pkg/runner.py": """
                from jax.sharding import PartitionSpec as P
                from ray_tpu._private.jax_compat import shard_map
                from pkg.mesh import MeshSpec

                def paged_attention_tp(fn, q, k_cache, devs):
                    mesh = MeshSpec().build(devs)
                    f = shard_map(
                        fn, mesh=mesh,
                        in_specs=(P(None, None, "heads"), P()),
                        out_specs=P(None, None, "heads"), check_vma=False,
                    )
                    return f(q, k_cache)
            """,
        }
    )
    assert "RTL601" in rules_of(typo)


def test_llm_tp_pool_head_divisibility_pinned():
    """RTL803 pins the pool-sharding divisibility rule on the engine's
    exact layout: a [L, N, bs, H, D] KV pool head-sharded over a tp axis
    whose size does not divide H fires (the runtime mirror of
    validate_tp_heads' fail-fast config error); a divisible head count is
    clean."""
    bad = """
        import jax
        import jax.numpy as jnp
        from jax.experimental import mesh_utils
        from jax.sharding import Mesh, NamedSharding
        from jax.sharding import PartitionSpec as P

        def build_pool():
            mesh = Mesh(mesh_utils.create_device_mesh((4,)), ("tp",))
            k_cache = jnp.zeros((2, 16, 4, 6, 8))  # H=6, tp=4: indivisible
            return jax.device_put(
                k_cache, NamedSharding(mesh, P(None, None, None, "tp"))
            )
    """
    assert "RTL803" in rules_of(lint(bad))

    good = """
        import jax
        import jax.numpy as jnp
        from jax.experimental import mesh_utils
        from jax.sharding import Mesh, NamedSharding
        from jax.sharding import PartitionSpec as P

        def build_pool():
            mesh = Mesh(mesh_utils.create_device_mesh((4,)), ("tp",))
            k_cache = jnp.zeros((2, 16, 4, 8, 8))  # H=8 divides tp=4
            return jax.device_put(
                k_cache, NamedSharding(mesh, P(None, None, None, "tp"))
            )
    """
    assert "RTL803" not in rules_of(lint(good))
