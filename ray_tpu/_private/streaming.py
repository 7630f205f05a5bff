"""Streaming generator refs.

Reference: core_worker/task_manager.h ObjectRefStream (:100-151) +
_raylet.pyx:228 StreamingObjectRefGenerator: a generator task's items are
sealed as individual objects as they are yielded; the consumer iterates an
ObjectRefGenerator whose __next__ blocks until the producer reports the next
item (or the stream ends). Errors raised mid-generator are sealed into the
failing item's slot, so the consumer raises exactly at that point. (A small
item read in the producing runtime's process is not sealed until its ref
escapes: `Runtime.report_stream_item`.)
"""

from __future__ import annotations

import logging
import threading
from collections import deque
from typing import Optional

logger = logging.getLogger(__name__)

_SENTINEL = object()


class ObjectRefStream:
    """Owner-side stream state: refs appear in yield order.

    `carries_values` says that the consumer reads the refs in the process
    that offers them, so a small item's ref may carry its value
    (`Runtime.report_stream_item`); a stream handed to a peer process, which
    gets its items by id, does not."""

    def __init__(self, carries_values: bool = False):
        self.carries_values = carries_values
        self._cv = threading.Condition()
        self._items: deque = deque()
        self._done = False
        self._total: Optional[int] = None
        # One-shot callbacks of consumers that wait without a thread
        # (`on_ready`), called by the producer's thread.
        self._waiters: list = []

    def offer(self, ref) -> None:
        with self._cv:
            self._items.append(ref)
            self._cv.notify_all()
            waiters, self._waiters = self._waiters, []
        self._wake(waiters)

    def finish(self, total: int) -> None:
        with self._cv:
            self._done = True
            self._total = total
            self._cv.notify_all()
            waiters, self._waiters = self._waiters, []
        self._wake(waiters)

    @staticmethod
    def _wake(waiters: list) -> None:
        """The callbacks run on the producer's thread, so none may fail the
        producer or cost the waiters behind it their call: a consumer whose
        event loop has closed (`call_soon_threadsafe` raises) is gone, and
        its callback is dropped."""
        for callback in waiters:
            try:
                callback()
            except Exception:
                logger.debug("stream waiter dropped", exc_info=True)

    def on_ready(self, callback) -> None:
        """Call `callback()` once, when `next()` would not block: at once
        where an item is waiting or the stream has ended, else from the
        thread that offers the next item or finishes the stream. What an
        event loop waits on in place of a thread parked in `next()`."""
        with self._cv:
            ready = bool(self._items) or self._done
            if not ready:
                self._waiters.append(callback)
        if ready:
            callback()

    def next(self, timeout: Optional[float] = None):
        """Blocking pop; returns _SENTINEL when the stream is exhausted.
        timeout=None waits indefinitely (the producer task finishing always
        wakes us via finish())."""
        import time as _time

        deadline = None if timeout is None else _time.monotonic() + timeout
        with self._cv:
            while not self._items:
                if self._done:
                    return _SENTINEL
                if deadline is None:
                    self._cv.wait()
                else:
                    remaining = deadline - _time.monotonic()
                    if remaining <= 0 or not self._cv.wait(remaining):
                        raise TimeoutError("ObjectRefStream.next timed out")
            return self._items.popleft()


class ObjectRefGenerator:
    """Iterator of ObjectRefs over a producer task's yielded items
    (reference: StreamingObjectRefGenerator, _raylet.pyx:228)."""

    def __init__(self, stream: ObjectRefStream, task_id):
        self._stream = stream
        self._task_id = task_id

    def __iter__(self):
        return self

    def __next__(self):
        ref = self._stream.next()
        if ref is _SENTINEL:
            raise StopIteration
        return ref

    def __repr__(self):
        return f"ObjectRefGenerator({self._task_id.hex()[:12]})"
