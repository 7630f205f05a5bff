"""Streaming generator refs.

Reference: core_worker/task_manager.h ObjectRefStream (:100-151) +
_raylet.pyx:228 StreamingObjectRefGenerator: a generator task's items are
sealed as individual objects as they are yielded; the consumer iterates an
ObjectRefGenerator whose __next__ blocks until the producer reports the next
item (or the stream ends). Errors raised mid-generator are sealed into the
failing item's slot, so the consumer raises exactly at that point. (A small
item read in the producing runtime's process is not sealed until its ref
escapes: `Runtime.report_stream_item`.)

A stream times each item from the thread that offers it to the thread that
takes it: `offer` keeps one `perf_counter` reading beside the ref and `next`,
the only place an item leaves, charges the wait to the stream's own totals,
both under the stream's own lock, which they hold anyway. Nothing shared by
the process is touched an item; `Runtime.stream_delivery` sums the streams
at a snapshot and a stream hands its totals over once, when it retires.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from typing import Callable, Optional

logger = logging.getLogger(__name__)

_SENTINEL = object()


class ObjectRefStream:
    """Owner-side stream state: refs appear in yield order.

    `carries_values` says that the consumer reads the refs in the process
    that offers them, so a small item's ref may carry its value
    (`Runtime.report_stream_item`); a stream handed to a peer process, which
    gets its items by id, does not.

    `name` is the producing task's (what `Runtime.stream_delivery` groups
    by) and `trace` the (trace id, span id) of that task's span. The
    delivery clock: `items_offered`, `items_taken`, `wait_s` (the sum over
    taken items of offer -> take) and `wait_max_s`. `on_retire(stream)`
    is called once, from whichever thread ends the stream's life: the
    producer has finished and the consumer has either taken every item or
    dropped its generator (`abandon`)."""

    def __init__(
        self,
        carries_values: bool = False,
        name: str = "",
        trace: Optional[tuple] = None,
        on_retire: Optional[Callable[["ObjectRefStream"], None]] = None,
    ):
        self.carries_values = carries_values
        self.name = name
        self.trace = trace
        self.created_s = time.time()
        self.items_offered = 0
        self.items_taken = 0
        self.wait_s = 0.0
        self.wait_max_s = 0.0
        self._on_retire = on_retire
        self._cv = threading.Condition()
        self._items: deque = deque()  # (ref, perf_counter reading at offer)
        self._done = False
        self._finishing = False
        self._abandoned = False
        self._retired = False
        self._total: Optional[int] = None
        # One-shot callbacks of consumers that wait without a thread
        # (`on_ready`), called by the producer's thread.
        self._waiters: list = []

    def offer(self, ref) -> None:
        with self._cv:
            self._items.append((ref, time.perf_counter()))
            self.items_offered += 1
            self._cv.notify_all()
            waiters, self._waiters = self._waiters, []
        self._wake(waiters)

    def claim_finish(self) -> bool:
        """True for the one caller that may finish the stream (every path
        that finalizes the producing task tries)."""
        with self._cv:
            claimed = not self._finishing
            self._finishing = True
        return claimed

    def finish(self, total: int) -> None:
        with self._cv:
            self._done = True
            self._total = total
            self._cv.notify_all()
            waiters, self._waiters = self._waiters, []
            retire = self._ends_here_locked()
        self._wake(waiters)
        if retire:
            self._on_retire(self)

    def abandon(self) -> None:
        """The consumer's generator is gone: what it left is taken by
        nobody."""
        with self._cv:
            self._abandoned = True
            retire = self._ends_here_locked()
        if retire:
            self._on_retire(self)

    def _ends_here_locked(self) -> bool:
        """Whether the caller, who holds the lock, is the one to retire the
        stream."""
        if (
            self._retired
            or self._on_retire is None
            or not self._done
            or (self._items and not self._abandoned)
        ):
            return False
        self._retired = True
        return True

    def delivery(self) -> dict:
        """The delivery clock, read together; `items_dropped` are the items
        no consumer will take."""
        with self._cv:
            return {
                "items_offered": self.items_offered,
                "items_taken": self.items_taken,
                "items_dropped": len(self._items) if self._abandoned else 0,
                "wait_s": self.wait_s,
                "wait_max_s": self.wait_max_s,
            }

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
        wakes us via finish()). The one place an item leaves the stream,
        for a thread parked here and for an event loop that asks with
        timeout=0 after `on_ready` woke it: its wait is charged here."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cv:
            while not self._items:
                if self._done:
                    return _SENTINEL
                if deadline is None:
                    self._cv.wait()
                else:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0 or not self._cv.wait(remaining):
                        raise TimeoutError("ObjectRefStream.next timed out")
            ref, offered = self._items.popleft()
            waited = time.perf_counter() - offered
            self.wait_s += waited
            if waited > self.wait_max_s:
                self.wait_max_s = waited
            self.items_taken += 1
            retire = self._ends_here_locked()
        if retire:
            self._on_retire(self)
        return ref


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
