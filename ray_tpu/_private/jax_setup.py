"""What the processes that own a chip set up around JAX: where compiled
programs are kept, and the host's CPU backend beside the accelerator.
"""

from __future__ import annotations

import os
from typing import Optional

_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def cpu_requested() -> bool:
    """Was this process started with JAX_PLATFORMS=cpu — the CPU asked for,
    as opposed to JAX finding nothing better."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def ensure_compile_cache() -> Optional[str]:
    """Place XLA's persistent compilation cache and return its directory.

    The TPU compiler takes seconds to tens of seconds per program and the
    serving engine warms a dozen, so the entry points that own a chip
    (`chip_smoke.py`, `bench.py`, the LLM runner, the trainer backend)
    keep compiled programs on disk. The directory is part of every cache
    key, so it never moves between runs: with `JAX_COMPILATION_CACHE_DIR`
    set JAX reads it itself and nothing is set in code; unset, it is
    `<checkout>/.jax_cache`. On the CPU backend nothing is cached and None
    is returned — tests and rehearsals compile in seconds, and a chip-less
    compile for a described TPU cannot read its own entries back.

    Beside XLA's executables the directory holds `programs/`: the serving
    step programs' lowered modules (`ray_tpu.llm.program_store`; StableHLO
    through `jax.export`, a few hundred kilobytes to a megabyte a program),
    which a later process reads instead of tracing and lowering each
    program again to learn its executable's key. Whatever keeps or empties
    the one keeps or empties the other; `programs/`, like the rest of the
    directory, is safe to delete at any time."""
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", _DEFAULT_CACHE_DIR)
    return _DEFAULT_CACHE_DIR


def host_cpu_device(purpose: str):
    """The first device of JAX's CPU backend, for host-side work beside an
    accelerator (`purpose` names it in the error). The backend exists only
    when JAX_PLATFORMS lists it — unset, or e.g. "tpu,cpu"; under
    JAX_PLATFORMS=tpu there is no host backend and this raises instead of
    putting the work on the chip."""
    import jax

    try:
        return jax.local_devices(backend="cpu")[0]
    except RuntimeError as exc:
        raise RuntimeError(
            f"{purpose} needs JAX's CPU backend beside "
            f"{jax.default_backend()!r}, and this process has none "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); list cpu "
            'after the accelerator, e.g. JAX_PLATFORMS="tpu,cpu"'
        ) from exc
