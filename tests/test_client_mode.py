"""Remote-driver client mode over the TCP control plane (reference:
python/ray/util/client/ — the `ray://` proxy for remote interactive
drivers). The client process holds no runtime: every API call rides the
wire protocol to the head."""

from __future__ import annotations

import subprocess
import sys
import textwrap

import pytest

import ray_tpu


@pytest.fixture
def head():
    runtime = ray_tpu.init(num_cpus=4)
    address = runtime.serve_clients(port=0)
    yield runtime, address
    ray_tpu.shutdown()


CLIENT_SCRIPT = textwrap.dedent(
    """
    import sys
    import ray_tpu

    ray_tpu.init(address=sys.argv[1])

    @ray_tpu.remote
    def square(x):
        return x * x

    assert ray_tpu.get(square.remote(7)) == 49

    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.n = 0

        def add(self, k):
            self.n += k
            return self.n

    c = Counter.remote()
    assert ray_tpu.get([c.add.remote(1), c.add.remote(2)]) == [1, 3]

    ref = ray_tpu.put({"weights": [1.0, 2.0]})
    assert ray_tpu.get(ref)["weights"] == [1.0, 2.0]

    ready, pending = ray_tpu.wait([square.remote(3)], num_returns=1, timeout=10)
    assert len(ready) == 1 and not pending

    # streaming across the TCP boundary
    @ray_tpu.remote
    def gen(n):
        for i in range(n):
            yield i * 10

    items = [ray_tpu.get(r) for r in gen.options(num_returns="streaming").remote(3)]
    assert items == [0, 10, 20]

    # named actor registered by the head-side driver
    h = ray_tpu.get_actor("head_registry")
    assert ray_tpu.get(h.whoami.remote()) == "head"

    ray_tpu.shutdown()
    print("CLIENT_OK")
    """
)


def test_remote_driver_full_api(head):
    runtime, address = head

    @ray_tpu.remote
    class Registry:
        def whoami(self):
            return "head"

    Registry.options(name="head_registry").remote()

    proc = subprocess.run(
        [sys.executable, "-c", CLIENT_SCRIPT, address],
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    assert "CLIENT_OK" in proc.stdout


def test_stream_read_by_a_remote_driver_is_not_inline(head):
    """A remote driver gets a stream's items by id over the wire: the head
    seals them as it always did, none travels with its ref."""
    runtime, address = head
    script = textwrap.dedent(
        """
        import sys
        import ray_tpu

        ray_tpu.init(address=sys.argv[1])

        @ray_tpu.remote
        def gen(n):
            for i in range(n):
                yield {"token_id": i}

        stream = gen.options(num_returns="streaming").remote(5)
        assert [ray_tpu.get(r)["token_id"] for r in stream] == [0, 1, 2, 3, 4]
        ray_tpu.shutdown()
        print("CLIENT_OK")
        """
    )
    proc = subprocess.run(
        [sys.executable, "-c", script, address],
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    assert "CLIENT_OK" in proc.stdout
    assert runtime.stream_items_reported == 5
    assert runtime.stream_items_inline == 0
    assert runtime.stream_items_promoted == 0


def test_wrong_token_refused(head, monkeypatch):
    """The head must refuse an unauthenticated peer before unpickling
    anything it sends (the wire protocol is code execution by design)."""
    from ray_tpu._private import head_server
    from ray_tpu._private.client import ClientCore

    monkeypatch.setattr(head_server, "HANDSHAKE_TIMEOUT_S", 1.0)
    runtime, address = head
    host_port = address.partition("?")[0]
    assert "?token=" in address  # credentials ride in the address
    with pytest.raises(ConnectionError):
        ClientCore(host_port + "?token=" + "0" * 32, timeout=10.0)
    # missing token entirely is also refused (server times the peer out)
    monkeypatch.delenv("RAY_TPU_CLIENT_TOKEN", raising=False)
    with pytest.raises(ConnectionError):
        ClientCore(host_port, timeout=10.0)


def test_client_disconnect_releases_borrows(head):
    runtime, address = head
    script = textwrap.dedent(
        """
        import sys
        import ray_tpu

        ray_tpu.init(address=sys.argv[1])
        ref = ray_tpu.put(list(range(1000)))
        print(ref.hex(), flush=True)
        import os
        os._exit(0)  # die without shutdown: head must drop our borrows
        """
    )
    proc = subprocess.run(
        [sys.executable, "-c", script, address],
        capture_output=True,
        text=True,
        timeout=60,
    )
    assert proc.returncode == 0, proc.stderr
    oid_hex = proc.stdout.strip().splitlines()[-1]
    from ray_tpu._private.ids import ObjectID

    oid = ObjectID.from_hex(oid_hex)
    import time

    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        local, submitted = runtime.refcount.counts(oid)
        if local == 0 and submitted == 0:
            break
        time.sleep(0.1)
    assert runtime.refcount.counts(oid) == (0, 0)
