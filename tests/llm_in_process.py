"""An `LLMServer` built in the test's own process, for a test that runs no
event loop.

The server's calls are coroutines of its actor's event loop (`generate`,
`generate_stream` and every call that takes its lock). `in_process(server)`
gives the same object with each of them run to its end on a loop of the
call's own, so a test reads as it would against a blocking server:
`server.generate(...)` returns the answer, `server.generate_stream(...)` is a
plain generator (closing it closes the async generator, which aborts the
request), `server.metrics()` the dict. Everything else (`_engine`, `_requests`,
`_lock`, `check_health`) is the server's own.
"""

import asyncio
import inspect


def _drive(agen):
    """A plain generator over an async generator, on a loop of its own that
    runs only while the consumer asks for the next item: what the step thread
    hands over meanwhile waits in the loop's queue, as a late consumer's
    tokens do."""
    loop = asyncio.new_event_loop()
    try:
        while True:
            try:
                yield loop.run_until_complete(agen.__anext__())
            except StopAsyncIteration:
                return
    finally:
        loop.run_until_complete(agen.aclose())
        loop.close()


class _InProcess:
    def __init__(self, server):
        self._server = server

    def __getattr__(self, name):
        attr = getattr(self._server, name)
        if inspect.iscoroutinefunction(attr):
            return lambda *args, **kwargs: asyncio.run(attr(*args, **kwargs))
        if inspect.isasyncgenfunction(attr):
            return lambda *args, **kwargs: _drive(attr(*args, **kwargs))
        return attr


def in_process(server):
    return _InProcess(server)
