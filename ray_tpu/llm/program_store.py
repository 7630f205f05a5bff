"""The step programs' lowered modules, kept beside the compile cache.

JAX's persistent cache holds executables under a key it computes from a
program's lowered text, so a process whose every executable is on disk
still traces and lowers each program to learn that key: 84 s of Python for
GPT-2 large's seven programs, against 15 s of reading them (PERF.md 6,
PR 55). This store keeps the lowered text itself. The first process to
need a program at a shape traces and lowers it once, through `jax.export`,
and the serialized module goes to `<compile cache dir>/programs/<key>`;
every later process reads the entry and runs it inside a `jax.jit` of the
traced function's name, argument trees and donation, whose body is:
flatten, call the stored module, unflatten. That jit traces to a handful
of equations, lowers by splicing the stored module in (XLA inlines the
call) and compiles as ever: a read of JAX's own cache, which stays the only
place an executable lives. The process that traced runs the same wrapper
around the module it just made, so both compile one and the same text.

Used wherever the compile cache is (`default`): on an accelerator, never on
the CPU backend, and by no option. A key holds everything the lowered text
was traced from (`ProgramStore.environment`, the program table's own
description, the program's name, donation, the argument tree and every
leaf's shape, dtype, weak type and sharding), so an edit to a source file,
another jax or another chip is another entry and never a stale one run. An
entry that cannot be used is logged, counted by reason, removed and traced
again; a directory that cannot be written is a warning: the store never
keeps a replica from starting. Safe to delete at any time.
"""

from __future__ import annotations

import hashlib
import importlib.metadata
import json
import logging
import os
import tempfile
import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
from jax import export as jax_export
from jax.extend.core import Primitive
from jax.interpreters import mlir

from ray_tpu._private.jax_setup import ensure_compile_cache

logger = logging.getLogger(__name__)

# How a stored module is called inside the jit that runs it: by the rules of
# `Exported.call`'s own primitive under another name. JAX commits to its
# device whatever a computation that holds a `call_exported` returns
# (`pxla.jaxpr_transfer_mem_kinds` looks for it by name), where a plain
# jit's results are as uncommitted as its arguments; a decode whose token
# input is now the host's buffer and now the last decode's output would then
# be two signatures, two lowerings and two compile steps, the second one in
# live traffic. Under this name results stay as a plain jit's. The two
# rules are private to jax: where this jax has none, every store is off.
try:
    from jax._src.export import _export as _jax_export_rules

    _stored_p: Optional[Primitive] = Primitive("stored_program")
    _stored_p.multiple_results = True
    _stored_p.def_effectful_abstract_eval(
        _jax_export_rules._call_exported_abstract_eval
    )
    mlir.register_lowering(_stored_p, _jax_export_rules._call_exported_lowering)
    # Outside a trace (`jax.disable_jit`): the public call, results flat.
    _stored_p.def_impl(
        lambda *args, exported: jax.tree_util.tree_leaves(exported.call(*args))
    )
except (ImportError, AttributeError) as _exc:  # pragma: no cover
    logger.warning("program store: off, this jax has no %r", _exc)
    _stored_p = None

_PACKAGE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# What a step program's trace runs through: directories and files under the
# package, by bytes. Wider than any one program reaches, which is the safe
# side: a digest that misses a file serves yesterday's program after an edit.
_TRACED_SOURCES = ("llm", "models", "ops", "parallel", "_private/jax_setup.py")
# The `jax.config` values that change what a function lowers to.
_LOWERING_CONFIG = (
    "jax_enable_x64",
    "jax_default_matmul_precision",
    "jax_numpy_dtype_promotion",
    "jax_use_shardy_partitioner",
)


def source_files(package: str = _PACKAGE) -> List[Tuple[str, str]]:
    """(name, path) of every file of `_TRACED_SOURCES`, in a fixed order."""
    found = []
    for source in _TRACED_SOURCES:
        root = os.path.join(package, *source.split("/"))
        if os.path.isfile(root):
            found.append((source, root))
            continue
        for folder, folders, files in os.walk(root):
            folders.sort()
            for name in sorted(files):
                if name.endswith(".py"):
                    path = os.path.join(folder, name)
                    found.append((os.path.relpath(path, package), path))
    return found


def source_digest(package: str = _PACKAGE) -> str:
    """A hash over the names and bytes of `source_files(package)`."""
    digest = hashlib.sha256()
    for name, path in source_files(package):
        digest.update(name.encode())
        with open(path, "rb") as source:
            digest.update(source.read())
    return digest.hexdigest()


def versions() -> dict:
    """jax, jaxlib and the accelerator's runtime, as installed and as the
    backend itself reports it."""
    installed = {"jax": jax.__version__}
    for package in ("jaxlib", "libtpu"):
        try:
            installed[package] = importlib.metadata.version(package)
        except importlib.metadata.PackageNotFoundError:
            installed[package] = None
    installed["runtime"] = jax.devices()[0].client.platform_version
    return installed


def _platform() -> str:
    """The platform the programs are lowered for."""
    return jax.default_backend()


def _leaf_key(leaf) -> tuple:
    aval = leaf.aval if hasattr(leaf, "aval") else leaf
    return (
        tuple(aval.shape), str(aval.dtype), bool(getattr(aval, "weak_type", False)),
        # Inside a trace a leaf's sharding is its type's: named axes where
        # the mesh is explicit, nothing otherwise (the mesh is the table's).
        str(getattr(aval, "sharding", None)),
    )


class ProgramStore:
    """The entries of one directory, and what this process did with them.

    `directory` None is a store that is off: `stored_jit` then hands out
    plain `jax.jit`s and every count stays 0."""

    def __init__(self, directory: Optional[str]):
        self.directory = directory if _stored_p is not None else None
        self._lock = threading.Lock()
        self._environment: Optional[dict] = None
        self._loaded = 0
        self._traced = 0
        self._misses: Dict[str, int] = {}
        # Entries made while a server boots (`hold`) wait here, and a thread
        # writes them once it serves (`release`): a boot that finds no store
        # does not pay for writing one on its way to traffic.
        self._holds = 0
        self._held: List[tuple] = []
        self._writers: List[threading.Thread] = []

    # ---------------- counts ----------------

    def totals(self) -> dict:
        """Programs this process read from the store, programs it traced
        and lowered (each of those a miss), and the misses by reason."""
        with self._lock:
            return {
                "programs_loaded": self._loaded,
                "programs_traced": self._traced,
                "program_store_misses_by_reason": dict(self._misses),
            }

    def since(self, before: dict) -> dict:
        """The totals' growth since `before` (an earlier `totals()`)."""
        now = self.totals()
        was = before["program_store_misses_by_reason"]
        return {
            "programs_loaded": now["programs_loaded"] - before["programs_loaded"],
            "programs_traced": now["programs_traced"] - before["programs_traced"],
            "program_store_misses_by_reason": {
                reason: count - was.get(reason, 0)
                for reason, count in now["program_store_misses_by_reason"].items()
                if count > was.get(reason, 0)
            },
        }

    def _count(self, reason: Optional[str]) -> None:
        with self._lock:
            if reason is None:
                self._loaded += 1
            else:
                self._traced += 1
                self._misses[reason] = self._misses.get(reason, 0) + 1

    # ---------------- the key ----------------

    def environment(self) -> dict:
        """What every program of this process lowers under: the sources,
        the versions, the devices and the flags. Read once a store."""
        with self._lock:
            if self._environment is None:
                device = jax.devices()[0]
                self._environment = {
                    "source": source_digest(),
                    **versions(),
                    "platform": _platform(),
                    "device_kind": device.device_kind,
                    "device_count": jax.device_count(),
                    "XLA_FLAGS": os.environ.get("XLA_FLAGS", ""),
                    "LIBTPU_INIT_ARGS": os.environ.get("LIBTPU_INIT_ARGS", ""),
                    **{
                        name: str(getattr(jax.config, name, None))
                        for name in _LOWERING_CONFIG
                    },
                }
            return self._environment

    def key(self, table, name: str, donate_argnums, in_tree, leaves) -> str:
        """The entry's name: a hash over everything the lowered text was
        traced from. `table` describes the program table: the model's and
        the engine's configuration, whole, and what else its functions
        close over."""
        described = json.dumps(
            [
                sorted(self.environment().items()), repr(table), name,
                list(donate_argnums), str(in_tree),
                [_leaf_key(leaf) for leaf in leaves],
            ],
            default=str,
        )
        return hashlib.sha256(described.encode()).hexdigest()

    # ---------------- an entry: a header line, then the module ----------------

    @staticmethod
    def write(path: str, key: str, name: str, blob: bytes) -> None:
        """Atomically: two replicas may boot at once, and a reader sees a
        whole entry or none."""
        header = json.dumps(
            {"key": key, "name": name, "sha256": hashlib.sha256(blob).hexdigest()}
        )
        os.makedirs(os.path.dirname(path), exist_ok=True)
        handle, temporary = tempfile.mkstemp(
            dir=os.path.dirname(path), prefix=".writing-"
        )
        try:
            with os.fdopen(handle, "wb") as entry:
                entry.write(header.encode() + b"\n")
                entry.write(blob)
            os.replace(temporary, path)
        except BaseException:
            os.unlink(temporary)
            raise

    @staticmethod
    def _read(path: str, key: str, leaves: Sequence):
        """(the exported module of the entry at `path`, None), or (None,
        why it cannot be used)."""
        try:
            with open(path, "rb") as entry:
                header, _, blob = entry.read().partition(b"\n")
        except FileNotFoundError:
            return None, "absent"
        except OSError as exc:
            return None, f"unreadable: {exc!r}"
        try:
            described = json.loads(header)
            if described["key"] != key:
                raise ValueError(f"entry is of key {described['key']!r}")
            written = described["sha256"]
        except (ValueError, KeyError, TypeError) as exc:
            return None, f"unreadable: {exc!r}"
        if written != hashlib.sha256(blob).hexdigest():
            return None, "corrupt: the module is not the one that was written"
        try:
            exported = jax_export.deserialize(bytearray(blob))
        except Exception as exc:  # another serialization version, a bad buffer
            return None, f"refused: {exc!r}"
        asked = [(tuple(leaf.shape), leaf.dtype) for leaf in leaves]
        kept = [(tuple(aval.shape), aval.dtype) for aval in exported.in_avals]
        if asked != kept:
            return None, f"aval_mismatch: called with {asked}, stored for {kept}"
        return exported, None

    def _store(self, path: str, key: str, name: str, exported) -> None:
        """Serialize and write one entry; a full or read-only disk is a
        warning, and the next process misses too."""
        try:
            self.write(path, key, name, bytes(exported.serialize()))
        except Exception as exc:
            logger.warning("program store: %s is not stored (%r)", name, exc)

    def _store_all(self, entries: List[tuple]) -> None:
        for entry in entries:
            self._store(*entry)

    def hold(self) -> None:
        """From here to `release`, entries are made and not written."""
        with self._lock:
            self._holds += 1

    def release(self) -> None:
        """The last `release` of the `hold`s made starts a thread that
        writes what was held (`join` waits for it)."""
        with self._lock:
            self._holds = max(0, self._holds - 1)
            if self._holds or not self._held:
                return
            held, self._held = self._held, []
            writer = threading.Thread(
                target=self._store_all, args=(held,),
                name="program-store-writer", daemon=True,
            )
            self._writers.append(writer)
        writer.start()

    def join(self, timeout: Optional[float] = None) -> None:
        """Wait for the writes `release` started."""
        with self._lock:
            writers, self._writers = self._writers, []
        for writer in writers:
            writer.join(timeout)

    # ---------------- a program ----------------

    def program(self, fn: Callable, table, donate_argnums, in_tree, leaves):
        """The stored module of `fn` at these arguments: read, or traced,
        lowered and handed on to be written. None where it cannot be
        exported: the caller then traces `fn` in place."""
        name = fn.__name__
        try:
            key = self.key(table, name, donate_argnums, in_tree, leaves)
            path = os.path.join(self.directory, key)
            exported, why = self._read(path, key, leaves)
        except Exception as exc:  # nothing of the store may fail a boot
            self._count(f"store_error:{type(exc).__name__}")
            logger.warning(
                "program store: looking %s up failed; it stays a plain "
                "jax.jit", name, exc_info=True,
            )
            return None
        if exported is not None:
            self._count(None)
            return exported
        reason = why.partition(":")[0]
        if reason != "absent":
            logger.warning(
                "program store: entry %s of %s cannot be used (%s); removed, "
                "traced again", key[:16], name, why,
            )
            try:
                os.unlink(path)
            except OSError:
                pass
        try:
            exported = _export(fn, in_tree, leaves)
        except Exception as exc:
            self._count(f"not_exported:{type(exc).__name__}")
            logger.warning(
                "program store: %s cannot be exported (%r); it stays a plain "
                "jax.jit", name, exc,
            )
            return None
        self._count(reason)
        logger.info("program store: miss (%s) of %s, entry %s", reason, name, key[:16])
        with self._lock:
            if self._holds:
                self._held.append((path, key, name, exported))
                return exported
        self._store(path, key, name, exported)
        return exported


def _export(fn: Callable, in_tree, leaves: Sequence):
    """Trace and lower `fn` once, as a function of flat leaves (a parameter
    tree's nodes are nothing `serialize` knows; the output tree, tuples and
    None, it keeps with the module)."""

    def flat(*flat_leaves):
        return fn(*jax.tree_util.tree_unflatten(in_tree, flat_leaves))

    flat.__name__ = fn.__name__
    specs = [
        jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, weak_type=getattr(leaf.aval, "weak_type", False)
        )
        for leaf in leaves
    ]
    return jax_export.export(jax.jit(flat), platforms=(_platform(),))(*specs)


def stored_jit(
    fn: Callable, *, store: ProgramStore, table, donate_argnums: Tuple[int, ...] = ()
):
    """`jax.jit(fn, donate_argnums=...)`, its lowered module kept in
    `store`: with a store that is off the plain jit itself, else a jit of
    the same name, argument trees and donation that calls the stored module
    of `fn` at the shapes it is traced with, reading or making the entry
    where JAX traces it: once a shape and process, never on a call."""
    if store.directory is None:
        return jax.jit(fn, donate_argnums=donate_argnums)

    def call(*args):
        leaves, in_tree = jax.tree_util.tree_flatten(args)
        exported = store.program(fn, table, donate_argnums, in_tree, leaves)
        if exported is None:
            return fn(*args)
        return jax.tree_util.tree_unflatten(
            exported.out_tree, _stored_p.bind(*leaves, exported=exported)
        )

    # The compiled module takes its name from this function, and profiles
    # and the benchmark's readers find programs by name.
    call.__name__ = fn.__name__
    call.__qualname__ = getattr(fn, "__qualname__", fn.__name__)
    return jax.jit(call, donate_argnums=donate_argnums)


_default: Optional[ProgramStore] = None
_default_lock = threading.Lock()


def default() -> ProgramStore:
    """This process's store: `programs/` in the directory the compile cache
    is placed in (`ensure_compile_cache`), and off on the CPU backend, where
    nothing is cached either."""
    global _default
    with _default_lock:
        if _default is None:
            cache = None if jax.default_backend() == "cpu" else ensure_compile_cache()
            _default = ProgramStore(cache and os.path.join(cache, "programs"))
        return _default
