"""What every runner sets up around JAX before its first compile, and what
it reads from the device afterwards."""

from __future__ import annotations

import os
import sys
import threading

from lib.manifest import ROOT
from lib.peaks import peaks_for

# The program's own default (`ray_tpu._private.jax_setup`), so harness and
# program agree: inside the checkout, at a path that never moves.
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def prepare_environment(rehearse: bool, chips: int) -> None:
    """Before JAX is imported. The machine's own cache directory is capped
    (PR 21: 192 MiB, one engine's programs fill 185) and lies outside the
    checkout, so its variables are dropped: the program then places the
    cache at CACHE_DIR itself and `place_cache` sets the same."""
    for name in ("JAX_COMPILATION_CACHE_DIR", "JAX_COMPILATION_CACHE_MAX_SIZE"):
        os.environ.pop(name, None)
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}"
        ).strip()


def place_cache() -> str:
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_compilation_cache_max_size", -1)
    # Every program is written, however short its compilation: a program near
    # JAX's default threshold of a second would be written by whichever run
    # happened to compile it slowly, and "a warm run compiles nothing" could
    # not be held exactly.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return CACHE_DIR


def cache_entries() -> int:
    return len(os.listdir(CACHE_DIR)) if os.path.isdir(CACHE_DIR) else 0


def find_devices(chips: int, rehearse: bool) -> dict:
    """The devices this run measures on, or exit 2 with no result line: no
    TPU, fewer chips than the cell asks for, or a kind with no published
    peak. A rehearsal wants the CPU and says so on every line."""
    import jax

    devices = jax.devices()
    found = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if rehearse:
        if found["platform"] != "cpu":
            sys.exit(f"benchmark: a rehearsal runs on the CPU, JAX found {found}")
        return found
    if found["platform"] != "tpu" or found["count"] < chips:
        print(
            f"benchmark: the cell needs {chips} TPU chip(s), JAX found {found}",
            file=sys.stderr,
        )
        sys.exit(2)
    peaks_for(found["kind"])  # an unknown kind is an error, not a default
    found["count"] = chips
    return found


def memory_stats(chips: int) -> list:
    """What the backend says of each chip used, whole, for the info line."""
    import jax

    return [d.memory_stats() for d in jax.devices()[:chips]]


def memory_peak_bytes(chips: int):
    """Peak bytes held on the fullest of the chips used, or None where the
    backend keeps no such statistic (the CPU). The TPU runtime splits a
    chip's memory in two and counts each apart: `peak_bytes_in_use` is the
    arrays (weights, pools, optimizer state) and `peak_bytes_reserved` the
    scratch space of the compiled programs, which is where a step's
    activations and the serving programs' temporaries live (PR 22: a decode
    program with 7.5 GB of temp shows 6.7 GB in use and 9.3 GB reserved; the
    two together never pass `bytes_limit`). The peak held is their sum, or
    `bytes_limit` where the two peaks fell at different times and their sum
    passes it."""
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            held = stats["peak_bytes_in_use"] + stats.get("peak_bytes_reserved", 0)
            peaks.append(min(held, stats.get("bytes_limit", held)))
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts the programs that reach XLA's compile step (a compilation or a
    read from the persistent cache: JAX times both under one event), through
    JAX's own monitoring hook and in whatever thread they happen. The window
    must see none."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"
    CACHE_MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring

        self._lock = threading.Lock()
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0  # programs read from the cache
        self.cache_misses = 0  # programs compiled and written to the cache
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(self._on_cache)

    def _on_cache(self, event: str, **_) -> None:
        if event in (self.CACHE_HIT, self.CACHE_MISS):
            with self._lock:
                if event == self.CACHE_HIT:
                    self.cache_hits += 1
                else:
                    self.cache_misses += 1

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            with self._lock:
                self.count += 1
                self.seconds += duration
