"""ObjectRef handle — the user-facing future (reference: ObjectRef in _raylet.pyx).

Constructing a handle takes a local reference in the ownership table; GC of the
handle releases it (reference_count.h AddLocalReference/RemoveLocalReference via
core_worker.h:434,442). Because the threaded runtime shares one refcount table,
handles embedded in stored values keep their reference alive through ordinary
Python object liveness — the borrow protocol for the in-process engine.

One kind of handle is not in the table: the ref of a small item of a streaming
generator whose consumer is in this process carries the item's value itself
(`_Carried`, made by `Runtime.report_stream_item`) and enters the store and the
table only when it escapes: see `ObjectRef._promote`.
"""

from __future__ import annotations

import contextlib
import pickle
import threading
from typing import Optional

from ray_tpu._private.ids import ObjectID

_CAPTURE = threading.local()


@contextlib.contextmanager
def capture_serialized_refs(out: list):
    """Collect every ObjectRef serialized while the context is active.

    The store wraps seal-time serialization with this so a ref nested inside a
    stored value is an explicit borrow: the entry holds the captured handles,
    keeping the inner object alive for the outer object's lifetime
    (reference: ReferenceCounter nested-object sets, reference_count.h)."""
    prev = getattr(_CAPTURE, "refs", None)
    _CAPTURE.refs = out
    try:
        yield out
    finally:
        _CAPTURE.refs = prev


def _global_runtime():
    from ray_tpu._private import runtime as runtime_mod

    return runtime_mod._RUNTIME


class _Carried:
    """A small stream item's serialized value, travelling with its ref.

    Every read deserializes a fresh copy, as a read of the store does.
    `nested` holds the ObjectRefs serialized inside the value, as a store
    entry's `nested_refs` does; `lock` makes the promotion happen once."""

    __slots__ = ("data", "nested", "lock")

    def __init__(self, data: bytes, nested: Optional[list]):
        self.data = data
        self.nested = nested
        self.lock = threading.Lock()

    def load(self):
        return pickle.loads(self.data)


def _returned(value):
    """An awaitable's iterator that is done before it starts."""
    return value
    yield


class ObjectRef:
    __slots__ = ("_id", "_owner_hint", "_carried", "__weakref__")

    def __init__(self, object_id: ObjectID, _incref: bool = True):
        self._id = object_id
        self._owner_hint = None
        self._carried: Optional[_Carried] = None
        if _incref:
            rt = _global_runtime()
            if rt is not None:
                rt.refcount.add_local_reference(object_id)

    @classmethod
    def _carrying(cls, object_id: ObjectID, data: bytes, nested: Optional[list]):
        """The ref of an item that is held by the ref and by nothing else:
        neither the reference counter nor the store has heard of its id."""
        ref = cls(object_id, _incref=False)
        ref._carried = _Carried(data, nested)
        return ref

    @property
    def id(self) -> ObjectID:
        """The object's id. Taking the id out of a ref that carries its value
        is an escape (whoever has the id can ask the store and the reference
        counter about it), so it promotes: the runtime's own reads of such a
        ref use `_id`."""
        if self._carried is not None:
            self._promote()
        return self._id

    def binary(self) -> bytes:
        return self.id.binary()

    def _promote(self) -> None:
        """Make a ref that carries its value an ordinary one: the value is
        sealed under its id and the ref entered into the reference counter
        with the counts it would have had (owned by its task, one local
        reference, which `__del__` gives back). Once, whoever asks first."""
        carried = self._carried
        if carried is None:
            return
        with carried.lock:
            if self._carried is None:
                return
            rt = _global_runtime()
            if rt is None or rt.shutting_down:
                return
            rt.refcount.add_owned_object(self._id, owner_task=self._id.task_id)
            rt.refcount.add_local_reference(self._id)
            rt.store.seal_pickled(self._id, carried.data, carried.nested)
            rt.stream_items_promoted += 1
            self._carried = None

    def hex(self) -> str:
        return self._id.hex()

    def task_id(self):
        return self._id.task_id

    def __del__(self):
        try:
            if self._carried is not None:
                return  # never entered: nothing to give back
            rt = _global_runtime()
            if rt is not None and not rt.shutting_down:
                rt.refcount.remove_local_reference(self._id)
        except Exception:
            pass

    def __eq__(self, other):
        return isinstance(other, ObjectRef) and other._id == self._id

    def __hash__(self):
        return hash(self._id)

    def __repr__(self):
        return f"ObjectRef({self._id.hex()})"

    def __reduce__(self):
        # The copy is resolved by id, wherever it lands (`copy.copy` too).
        self._promote()
        refs = getattr(_CAPTURE, "refs", None)
        if refs is not None:
            refs.append(self)
        # Deserialization takes its own local reference (the borrow).
        return (ObjectRef, (self._id,))

    def future(self):
        """Return a concurrent.futures.Future resolving to the object's value."""
        import concurrent.futures

        fut: concurrent.futures.Future = concurrent.futures.Future()
        carried = self._carried
        if carried is not None:
            try:
                fut.set_result(carried.load())
            except BaseException as exc:  # noqa: BLE001
                fut.set_exception(exc)
            return fut
        rt = _global_runtime()

        def _fill():
            try:
                fut.set_result(rt.get([self], timeout=None)[0])
            except BaseException as exc:  # noqa: BLE001
                fut.set_exception(exc)

        rt.store.on_sealed(self._id, lambda: rt.background(_fill))
        return fut

    def __await__(self):
        import asyncio

        carried = self._carried
        if carried is not None:
            return _returned(carried.load())
        loop = asyncio.get_event_loop()
        return _to_asyncio_future(self, loop).__await__()


def _to_asyncio_future(ref: ObjectRef, loop):
    fut = loop.create_future()
    rt = _global_runtime()

    def _fill():
        def _set():
            if fut.cancelled():
                return
            try:
                fut.set_result(rt.get([ref], timeout=None)[0])
            except BaseException as exc:  # noqa: BLE001
                fut.set_exception(exc)

        loop.call_soon_threadsafe(_set)

    rt.store.on_sealed(ref._id, lambda: rt.background(_fill))
    return fut
