"""Serve library tests (reference test strategy: serve/tests/)."""

import json
import threading
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture
def serve_instance():
    runtime = ray_tpu.init(num_cpus=8)
    yield runtime
    serve.shutdown()
    ray_tpu.shutdown()


def test_function_deployment(serve_instance):
    @serve.deployment
    def echo(x):
        return {"echo": x}

    handle = serve.run(echo.bind())
    assert handle.remote("hi").result() == {"echo": "hi"}


def test_class_deployment_and_methods(serve_instance):
    @serve.deployment
    class Counter:
        def __init__(self, start):
            self.count = start

        def __call__(self, inc):
            self.count += inc
            return self.count

        def peek(self):
            return self.count

    handle = serve.run(Counter.bind(10))
    assert handle.remote(5).result() == 15
    assert handle.peek.remote().result() == 15


def test_multi_replica_round_robin(serve_instance):
    @serve.deployment(num_replicas=3)
    class WhoAmI:
        def __init__(self):
            self.id = id(self)

        def __call__(self, _):
            return self.id

    handle = serve.run(WhoAmI.bind())
    seen = {handle.remote(None).result() for _ in range(30)}
    assert len(seen) == 3


def test_composed_deployments(serve_instance):
    @serve.deployment
    class Downstream:
        def __call__(self, x):
            return x * 2

    @serve.deployment
    class Ingress:
        def __init__(self, downstream):
            self.downstream = downstream

        def __call__(self, x):
            return self.downstream.remote(x).result() + 1

    handle = serve.run(Ingress.bind(Downstream.bind()))
    assert handle.remote(10).result() == 21


def test_user_config_reconfigure(serve_instance):
    @serve.deployment(user_config={"threshold": 1})
    class Model:
        def __init__(self):
            self.threshold = None

        def reconfigure(self, config):
            self.threshold = config["threshold"]

        def __call__(self, _):
            return self.threshold

    handle = serve.run(Model.bind())
    assert handle.remote(None).result() == 1
    # Redeploy with new user_config — same code version → in-place reconfigure.
    serve.run(Model.options(user_config={"threshold": 7}).bind())
    deadline = time.time() + 10
    while time.time() < deadline:
        if handle.remote(None).result() == 7:
            break
        time.sleep(0.1)
    assert handle.remote(None).result() == 7


def test_autoscaling_scales_up_and_down(serve_instance):
    @serve.deployment(
        autoscaling_config={
            "min_replicas": 1,
            "max_replicas": 3,
            "target_num_ongoing_requests_per_replica": 1,
        },
        max_concurrent_queries=2,
    )
    class Slow:
        def __call__(self, _):
            time.sleep(0.4)
            return "done"

    handle = serve.run(Slow.bind())
    st = serve.status()["default"]["Slow"]
    assert st["num_replicas"] == 1

    results = []

    def fire():
        results.append(handle.remote(None).result(timeout_s=30))

    threads = [threading.Thread(target=fire) for _ in range(12)]
    for t in threads:
        t.start()
    # While load is in flight, replicas should grow past 1.
    grew = False
    deadline = time.time() + 15
    while time.time() < deadline:
        if serve.status()["default"]["Slow"]["num_replicas"] > 1:
            grew = True
            break
        time.sleep(0.05)
    for t in threads:
        t.join()
    assert grew
    assert len(results) == 12
    # After load drains, scale back toward min_replicas.
    deadline = time.time() + 20
    while time.time() < deadline:
        if serve.status()["default"]["Slow"]["num_replicas"] == 1:
            break
        time.sleep(0.1)
    assert serve.status()["default"]["Slow"]["num_replicas"] == 1


def test_batching(serve_instance):
    batch_sizes = []

    @serve.deployment(max_concurrent_queries=32)
    class Batched:
        @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.1)
        def __call__(self, items):
            batch_sizes.append(len(items))
            return [x + 1 for x in items]

    handle = serve.run(Batched.bind())
    results = []

    def fire(i):
        results.append(handle.remote(i).result(timeout_s=30))

    threads = [threading.Thread(target=fire, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert sorted(results) == list(range(1, 9))


def test_batch_pad_to_bucket():
    from ray_tpu.serve.batching import _next_bucket

    assert _next_bucket(3, 8) == 4
    assert _next_bucket(5, 8) == 8
    assert _next_bucket(9, 8) == 8
    calls = []

    @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.05, pad_to_bucket=True)
    def process(items):
        calls.append(len(items))
        return [x * 2 for x in items]

    out = []
    threads = [
        threading.Thread(target=lambda i=i: out.append(process(i)))
        for i in range(3)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert sorted(out) == [0, 2, 4]
    # Batch was padded to a power-of-two bucket.
    assert all(c in (1, 2, 4, 8) for c in calls)


def test_batch_exactly_max_batch_size():
    """A batch that fills max_batch_size flushes immediately, is never
    padded past the cap, and fans every result back out."""
    calls = []

    @serve.batch(max_batch_size=4, batch_wait_timeout_s=5.0, pad_to_bucket=True)
    def process(items):
        calls.append(len(items))
        return [x * 10 for x in items]

    out = []
    threads = [
        threading.Thread(target=lambda i=i: out.append(process(i)))
        for i in range(4)
    ]
    start = time.time()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    # Flushed on the size trigger, not the 5s timer.
    assert time.time() - start < 4.0
    assert sorted(out) == [0, 10, 20, 30]
    assert calls and max(calls) <= 4


def test_batch_of_one_pads_to_bucket_of_one():
    calls = []

    @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.05, pad_to_bucket=True)
    def process(items):
        calls.append(len(items))
        return [x + 100 for x in items]

    assert process(7) == 107
    assert calls == [1]  # bucket for n=1 is 1; no phantom padding items


def test_batch_error_fans_out_to_all_waiters():
    attempts = []

    @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.05)
    def explode(items):
        attempts.append(len(items))
        raise RuntimeError("batch failed")

    errors = []

    def fire(i):
        try:
            explode(i)
        except RuntimeError as e:
            errors.append(str(e))

    threads = [threading.Thread(target=fire, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    # Every waiter in the failed batch got the error, none hung.
    assert errors == ["batch failed"] * 3
    assert sum(attempts) == 3


def test_batch_wrong_result_count_raises_for_all():
    @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.05)
    def short_changed(items):
        return items[:-1]  # one result missing

    errors = []

    def fire(i):
        try:
            short_changed(i)
        except ValueError as e:
            errors.append("results" in str(e))

    threads = [threading.Thread(target=fire, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert errors == [True, True]


def test_status_and_shutdown(serve_instance):
    @serve.deployment
    def f(x):
        return x

    serve.run(f.bind(), name="app1")
    st = serve.status()
    assert st["app1"]["f"]["status"] == "HEALTHY"
    serve.shutdown()
    # A fresh controller comes up empty.
    assert serve.status() == {}


def test_http_proxy(serve_instance):
    from ray_tpu.serve._private.http_proxy import start_proxy, stop_proxy

    @serve.deployment
    def double(x):
        return x * 2

    serve.run(double.bind())
    host, port = start_proxy()
    try:
        req = urllib.request.Request(
            f"http://{host}:{port}/default",
            data=json.dumps(21).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=30) as resp:
            body = json.loads(resp.read())
        assert body["result"] == 42
    finally:
        stop_proxy()


def test_slow_init_replica_not_duplicated(serve_instance):
    """Regression: metrics-poll timeouts on a slow-__init__ replica must not
    drop it and spawn duplicates."""

    @serve.deployment
    class SlowInit:
        def __init__(self):
            time.sleep(3.0)  # longer than the 2s metrics timeout
            self.ready = True

        def __call__(self, _):
            return "ok"

    handle = serve.run(SlowInit.bind(), _blocking_timeout_s=60.0)
    assert handle.remote(None).result(timeout_s=30) == "ok"
    st = serve.status()["default"]["SlowInit"]
    assert st["num_replicas"] == 1


def test_fire_and_forget_does_not_exhaust_slots(serve_instance):
    """Regression: .remote() without .result() must free in-flight slots when
    the reply lands."""

    @serve.deployment(max_concurrent_queries=2)
    class Fast:
        def __call__(self, x):
            return x

    handle = serve.run(Fast.bind())
    for i in range(10):
        handle.remote(i)  # never read
    time.sleep(0.5)
    # Slots freed -> this must not block/timeout.
    assert handle.remote(99).result(timeout_s=10) == 99


def test_graceful_shutdown_hook_runs(serve_instance, tmp_path):
    marker = tmp_path / "shutdown.txt"

    @serve.deployment
    class WithCleanup:
        def __call__(self, _):
            return 1

        def shutdown(self):
            with open(marker, "w") as f:
                f.write("clean")

    serve.run(WithCleanup.bind())
    serve.shutdown()
    def written():  # the file exists before the hook has written into it
        return marker.exists() and marker.read_text() == "clean"

    deadline = time.time() + 10
    while time.time() < deadline and not written():
        time.sleep(0.1)
    assert written()


def test_model_multiplexing(serve_instance):
    from ray_tpu import serve

    @serve.deployment(num_replicas=2)
    class MultiModel:
        @serve.multiplexed(max_num_models_per_replica=2)
        def get_model(self, model_id: str):
            return {"id": model_id, "scale": int(model_id.split("-")[1])}

        def __call__(self, x):
            model = self.get_model()
            return x * model["scale"], serve.get_multiplexed_model_id()

    handle = serve.run(MultiModel.bind(), name="mux")
    for mid, expect in (("m-2", 10), ("m-3", 15), ("m-2", 10), ("m-5", 25)):
        out, seen = handle.options(multiplexed_model_id=mid).remote(5).result(
            timeout_s=30
        )
        assert out == expect and seen == mid


def test_multiplex_lru_eviction():
    from ray_tpu.serve.multiplex import _ModelMultiplexWrapper

    loads = []

    def loader(owner, model_id):
        loads.append(model_id)
        return model_id.upper()

    wrapper = _ModelMultiplexWrapper(loader, None, max_models=2)
    assert wrapper("a") == "A"
    assert wrapper("b") == "B"
    assert wrapper("a") == "A"  # cache hit, no reload
    assert loads == ["a", "b"]
    wrapper("c")  # evicts LRU ("b")
    wrapper("b")
    assert loads == ["a", "b", "c", "b"]


def test_multiplex_async_loader(serve_instance):
    """Async loaders from async deployment methods (documented usage) must
    work on cache misses (regression: nested asyncio.run crashed)."""
    from ray_tpu import serve

    @serve.deployment
    class AsyncMux:
        @serve.multiplexed(max_num_models_per_replica=2)
        async def get_model(self, model_id: str):
            return model_id.upper()

        async def __call__(self):
            return self.get_model()

    handle = serve.run(AsyncMux.bind(), name="asyncmux")
    out = handle.options(multiplexed_model_id="abc").remote().result(timeout_s=30)
    assert out == "ABC"


def test_multiplex_concurrent_load_once():
    import threading
    import time

    from ray_tpu.serve.multiplex import _ModelMultiplexWrapper

    loads = []

    def slow_loader(owner, model_id):
        loads.append(model_id)
        time.sleep(0.2)
        return model_id

    wrapper = _ModelMultiplexWrapper(slow_loader, None, max_models=4)
    threads = [
        threading.Thread(target=lambda: wrapper("same")) for _ in range(4)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert loads == ["same"]  # one load despite 4 concurrent misses


def test_declarative_config_apply(serve_instance, tmp_path):
    """GitOps-style deploy: applications by import path with per-deployment
    overrides (reference deploy_apps/ServeDeploySchema)."""
    import sys
    import textwrap

    from ray_tpu import serve

    mod_dir = tmp_path / "apps"
    mod_dir.mkdir()
    (mod_dir / "my_serve_app.py").write_text(
        textwrap.dedent(
            """
            from ray_tpu import serve

            @serve.deployment
            class Echo:
                def __init__(self, prefix="e"):
                    self.prefix = prefix
                    self.tag = "default"

                def reconfigure(self, user_config):
                    self.tag = user_config.get("tag", "default")

                def __call__(self, x):
                    return f"{self.prefix}:{x}:{self.tag}"

            app = Echo.bind("cfg")

            def build_app(prefix="built"):
                return Echo.bind(prefix)
            """
        )
    )
    sys.path.insert(0, str(mod_dir))
    try:
        config = {
            "applications": [
                {
                    "name": "echo-app",
                    "import_path": "my_serve_app:app",
                    "deployments": [
                        {
                            "name": "Echo",
                            "num_replicas": 2,
                            "user_config": {"tag": "from-config"},
                        }
                    ],
                },
                {
                    "name": "built-app",
                    "import_path": "my_serve_app:build_app",
                    "args": {"prefix": "B"},
                },
            ]
        }
        handles = serve.schema.apply(config)
        out = handles["echo-app"].remote("hi").result(timeout_s=30)
        assert out == "cfg:hi:from-config"
        out2 = handles["built-app"].remote("yo").result(timeout_s=30)
        assert out2 == "B:yo:default"
        # Unknown deployment override fails loudly.
        bad = {"applications": [{"name": "x", "import_path": "my_serve_app:app",
                                 "deployments": [{"name": "Nope", "num_replicas": 1}]}]}
        with pytest.raises(ValueError, match="unknown deployment"):
            serve.schema.apply(bad)
        # args on an already-bound target fails loudly (would be ignored).
        with pytest.raises(ValueError, match="already bound"):
            serve.schema.apply({"applications": [
                {"name": "y", "import_path": "my_serve_app:app",
                 "args": {"prefix": "Z"}}]})
        # Duplicate app names rejected.
        with pytest.raises(ValueError, match="Duplicate"):
            serve.schema.apply({"applications": [
                {"import_path": "my_serve_app:app"},
                {"import_path": "my_serve_app:app"}]})
        # Overrides never leak into the module-level Application.
        import my_serve_app

        assert my_serve_app.app.deployment._config.num_replicas == 1
    finally:
        sys.path.remove(str(mod_dir))
        sys.modules.pop("my_serve_app", None)


def test_replica_health_check_replaces_unhealthy(serve_instance):
    """A replica whose check_health turns False is killed and replaced by
    reconciliation (the health_check_period_s knob is live)."""
    import time

    from ray_tpu import serve

    @serve.deployment
    class Flaky:
        def __init__(self):
            self.healthy = True

        def poison(self):
            self.healthy = False
            return "poisoned"

        def check_health(self):
            return self.healthy

        def __call__(self):
            return "ok"

    handle = serve.run(
        Flaky.options(num_replicas=1, health_check_period_s=0.2).bind(),
        name="flaky",
    )
    assert handle.remote().result(timeout_s=30) == "ok"
    assert handle.poison.remote().result(timeout_s=30) == "poisoned"
    # The poisoned replica fails its next probe; a fresh one replaces it
    # and reports healthy again.
    deadline = time.time() + 30
    while time.time() < deadline:
        st = serve.status()["flaky"]["Flaky"]
        if st["status"] == "HEALTHY" and st["num_replicas"] == 1:
            try:
                # A fresh replica reports healthy again.
                if handle.check_health.remote().result(timeout_s=5) is True:
                    break
            except Exception:
                pass  # raced the replacement
        time.sleep(0.2)
    assert handle.check_health.remote().result(timeout_s=10) is True


def test_http_proxy_streaming(serve_instance):
    """?stream=1 returns a chunked ndjson response, one line per item the
    generator ingress yields (the ASGI-streaming analog)."""
    from ray_tpu.serve._private.http_proxy import start_proxy, stop_proxy

    @serve.deployment
    def counter(n):
        def gen():
            for i in range(int(n)):
                yield {"i": i, "sq": i * i}
        return gen()

    serve.run(counter.bind(), name="streamer")
    host, port = start_proxy()
    try:
        req = urllib.request.Request(
            f"http://{host}:{port}/streamer?stream=1",
            data=json.dumps(5).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=30) as resp:
            assert resp.headers.get("Content-Type") == "application/x-ndjson"
            lines = [
                json.loads(line) for line in resp.read().splitlines() if line
            ]
        assert [row["result"]["i"] for row in lines] == list(range(5))
        assert lines[3]["result"]["sq"] == 9
    finally:
        stop_proxy()


def test_http_proxy_concurrent_inflight(serve_instance):
    """The asyncio proxy keeps many slow requests in flight at once — wall
    time for N concurrent slow calls ~= one call, not N (no
    thread-per-request serialization; replicas run them in parallel)."""
    import threading as _threading
    import time as _time

    from ray_tpu.serve._private.http_proxy import start_proxy, stop_proxy

    @serve.deployment(max_concurrent_queries=16)
    class Slow:
        def __call__(self, x):
            _time.sleep(1.0)
            return x

    serve.run(Slow.options(num_replicas=1).bind(), name="slowapp")
    host, port = start_proxy()
    results = []
    errors = []

    def one(i):
        try:
            req = urllib.request.Request(
                f"http://{host}:{port}/slowapp",
                data=json.dumps(i).encode(),
            )
            with urllib.request.urlopen(req, timeout=30) as resp:
                results.append(json.loads(resp.read())["result"])
        except Exception as exc:  # pragma: no cover
            errors.append(exc)

    try:
        t0 = _time.monotonic()
        threads = [_threading.Thread(target=one, args=(i,)) for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        wall = _time.monotonic() - t0
        assert not errors, errors
        assert sorted(results) == list(range(8))
        # 8 sequential 1s calls would take >= 8s; concurrent ~= 1-3s.
        assert wall < 6.0, f"requests serialized: {wall:.1f}s for 8 calls"
    finally:
        stop_proxy()


def test_http_proxy_request_timeout(serve_instance):
    """Per-request X-Serve-Timeout-S produces a 504 instead of hanging."""
    import time as _time

    from ray_tpu.serve._private.http_proxy import start_proxy, stop_proxy

    @serve.deployment
    def sleepy(x):
        _time.sleep(5.0)
        return x

    serve.run(sleepy.bind(), name="sleepyapp")
    host, port = start_proxy()
    try:
        req = urllib.request.Request(
            f"http://{host}:{port}/sleepyapp",
            data=json.dumps(1).encode(),
            headers={"X-Serve-Timeout-S": "1.0"},
        )
        try:
            with urllib.request.urlopen(req, timeout=30) as resp:
                raise AssertionError(f"expected 504, got {resp.status}")
        except urllib.error.HTTPError as err:
            assert err.code == 504
            assert "timed out" in json.loads(err.read())["error"]
    finally:
        stop_proxy()


def test_streaming_handle_direct(serve_instance):
    """handle.options(stream=True).remote() yields items as they are
    produced (sync iteration path)."""
    @serve.deployment
    def gen_app(n):
        def gen():
            for i in range(int(n)):
                yield i * 10
        return gen()

    handle = serve.run(gen_app.bind(), name="genapp")
    items = list(handle.options(stream=True).remote(4))
    assert items == [0, 10, 20, 30]


def test_idle_streams_do_not_starve_a_stream_with_items(serve_instance):
    """Forty proxy streams that yield nothing yet (requests waiting for a
    decode lane, say) hold no thread while they wait: one more stream still
    gets its 200 items in a fraction of a second. With each wait a 0.2 s
    poll on the loop's default executor the forty parked its threads and
    the live stream got a turn a second (chip run, PR 35)."""
    import threading as _threading
    import time as _time

    from ray_tpu.serve._private.http_proxy import start_proxy, stop_proxy

    release = _threading.Event()

    @serve.deployment(max_concurrent_queries=64)
    class Streams:
        def __call__(self, n):
            def gen():
                if int(n) == 0:
                    release.wait(30.0)  # an idle stream: nothing to yield yet
                for i in range(int(n)):
                    yield i
            return gen()

    serve.run(Streams.bind(), name="streams")
    host, port = start_proxy()
    url = f"http://{host}:{port}/streams?stream=1"

    def read(n, out):
        req = urllib.request.Request(url, data=json.dumps(n).encode())
        with urllib.request.urlopen(req, timeout=60) as resp:
            out.append([json.loads(line)["result"] for line in resp.read().splitlines() if line])

    idle_out, threads = [], []
    try:
        for _ in range(40):
            threads.append(_threading.Thread(target=read, args=(0, idle_out)))
            threads[-1].start()
        _time.sleep(1.0)  # every idle stream is waiting for its first item
        live = []
        t0 = _time.monotonic()
        read(200, live)
        took = _time.monotonic() - t0
        assert live == [list(range(200))]
        assert took < 5.0, took  # 200 turns behind forty 0.2 s polls took 90 s
    finally:
        release.set()
        for thread in threads:
            thread.join(timeout=30)
        stop_proxy()
    assert idle_out == [[]] * 40


def test_stream_on_ready_calls_back_once():
    from ray_tpu._private.streaming import _SENTINEL, ObjectRefStream

    stream, calls = ObjectRefStream(), []
    stream.on_ready(lambda: calls.append("a"))
    assert calls == []  # nothing to read yet: no thread waits, no call
    stream.offer("ref-0")
    assert calls == ["a"] and stream.next(timeout=0) == "ref-0"
    stream.offer("ref-1")
    assert calls == ["a"]  # one-shot
    stream.on_ready(lambda: calls.append("b"))  # an item is waiting: at once
    assert calls == ["a", "b"] and stream.next(timeout=0) == "ref-1"
    stream.on_ready(lambda: calls.append("c"))
    stream.finish(2)
    assert calls == ["a", "b", "c"] and stream.next(timeout=0) is _SENTINEL
    with pytest.raises(TimeoutError):
        ObjectRefStream().next(timeout=0)


@pytest.mark.parametrize("end", ["offer", "finish"])
def test_stream_waiter_of_a_closed_loop_fails_nobody(end):
    """A consumer whose event loop closed while it waited (its callback's
    `call_soon_threadsafe` raises) neither fails the producer's `offer` /
    `finish` nor costs the waiter behind it its call."""
    import asyncio

    from ray_tpu._private.streaming import ObjectRefStream

    loop = asyncio.new_event_loop()
    gone = loop.create_future()
    loop.close()
    stream, calls = ObjectRefStream(), []
    stream.on_ready(lambda: loop.call_soon_threadsafe(gone.set_result, None))
    stream.on_ready(lambda: calls.append("behind"))
    if end == "offer":
        stream.offer("ref-0")  # must not raise RuntimeError("Event loop is closed")
        assert stream.next(timeout=0) == "ref-0"
    else:
        stream.finish(0)
    assert calls == ["behind"]
    # And the stream still serves a waiter that comes later.
    stream.on_ready(lambda: calls.append("later"))
    if end == "offer":
        stream.offer("ref-1")
    assert calls == ["behind", "later"]


def test_per_node_proxies(serve_instance):
    """serve.start(proxy_location="EveryNode") pins one ingress proxy actor
    per alive node; every proxy serves the same applications."""
    from ray_tpu._private.runtime import get_runtime

    runtime = get_runtime()
    runtime.add_node({"CPU": 2})  # a second logical node

    @serve.deployment
    def echo(x):
        return {"v": x}

    serve.run(echo.bind(), name="echoapp")
    addresses = serve.start(proxy_location="EveryNode")
    # head in-process proxy + one actor per node
    assert len(addresses) == 1 + len(runtime.controller.alive_nodes())
    for host, port in addresses:
        req = urllib.request.Request(
            f"http://{host}:{port}/echoapp", data=json.dumps(11).encode()
        )
        with urllib.request.urlopen(req, timeout=30) as resp:
            assert json.loads(resp.read())["result"] == {"v": 11}


def test_stream_cancel_releases_replica_slot(serve_instance):
    """Cancelling an abandoned stream stops the replica-side generator at
    its next yield and frees the max_concurrent_queries slot (the proxy's
    deadline/disconnect path; an infinite generator must not pin the
    replica forever)."""
    import time as _time

    @serve.deployment(max_concurrent_queries=1)
    class Infinite:
        def __call__(self, x):
            def gen():
                i = 0
                while True:
                    yield i
                    i += 1
                    _time.sleep(0.05)

            return gen()

        def ping(self):
            return "pong"

    handle = serve.run(Infinite.bind(), name="cancelapp")
    gen = handle.options(stream=True).remote(0)
    it = iter(gen)
    assert next(it) == 0
    assert next(it) == 1
    gen.cancel()
    # With the only slot pinned by the infinite stream this would time out;
    # the cancel completes the stream, the completion ref seals, and the
    # router releases the slot.
    assert handle.ping.remote().result(timeout_s=20) == "pong"


def test_listen_for_change_timeout_immune_to_wallclock(monkeypatch):
    """Regression (found by `ray-tpu lint` RTL302 wallclock-duration): the
    controller long-poll deadline is monotonic. It used to be computed
    from time.time(), so a frozen/backward-stepping wall clock made
    `deadline - time.time()` never shrink and parked the poller (and the
    actor thread serving it) indefinitely."""
    from ray_tpu.serve._private.controller import ServeControllerActor

    # Bare instance: just the fields listen_for_change touches, no
    # reconcile thread (its wall-clock health probes are not under test).
    ctrl = ServeControllerActor.__new__(ServeControllerActor)
    ctrl._lock = threading.RLock()
    ctrl._cv = threading.Condition(ctrl._lock)
    ctrl._version = 0
    ctrl._shutdown = False

    frozen = time.time()
    monkeypatch.setattr(time, "time", lambda: frozen)
    done = threading.Event()
    result = {}

    def poll():
        result["version"] = ctrl.listen_for_change(
            known_version=5, timeout_s=0.3
        )
        done.set()

    start = time.monotonic()
    threading.Thread(target=poll, daemon=True).start()
    assert done.wait(5.0), (
        "listen_for_change hung on a frozen wall clock (deadline must be "
        "monotonic)"
    )
    assert time.monotonic() - start < 4.0
    assert result["version"] == 0


# ---------------- replica lifecycle: drain + SLO autoscaling ----------------


def _fake_state(autoscaling_config):
    """A bare _DeploymentState for pure policy/window unit tests."""
    from ray_tpu.serve._private.controller import _DeploymentState
    from ray_tpu.serve.config import DeploymentConfig

    return _DeploymentState(
        "app",
        "dep",
        {"config": DeploymentConfig(autoscaling_config=autoscaling_config)},
    )


def test_look_back_window_average_prevents_flap():
    """Satellite: AutoscalingConfig.look_back_period_s is real — the
    controller feeds desired_replicas the window AVERAGE of the
    ongoing-requests metric, so one bursty sample cannot trigger a
    scale-up, and one idle sample amid sustained load cannot trigger a
    scale-down (the oscillation the single-sample policy was prone to)."""
    from ray_tpu.serve.config import AutoscalingConfig

    cfg = AutoscalingConfig(
        min_replicas=1,
        max_replicas=4,
        target_num_ongoing_requests_per_replica=1.0,
        look_back_period_s=1.0,
    )
    st = _fake_state(cfg)
    st.replicas = {"t0": object()}
    # 20 light samples, then ONE 8-request burst sample. The single-sample
    # policy would have jumped straight to 4 replicas on the burst; the
    # window average ((20*0.5 + 8) / 21 ≈ 0.86) stays under target.
    for i in range(20):
        st.observe_metrics_locked(i * 0.05, 0.5, [])
    st.observe_metrics_locked(1.0, 8.0, [])
    assert st.target_replicas(now=1.0) == 1  # no flap on one burst sample

    # Sustained load fills the window: now the same signal scales up.
    for i in range(21, 41):
        st.observe_metrics_locked(i * 0.05, 8.0, [])
    assert st.target_replicas(now=2.05) == 4

    # Scale-down flap guard: one idle sample amid sustained load.
    st2 = _fake_state(cfg)
    st2.replicas = {"t0": object(), "t1": object(), "t2": object(),
                    "t3": object()}
    for i in range(20):
        st2.observe_metrics_locked(i * 0.05, 4.0, [])
    st2.observe_metrics_locked(1.0, 0.0, [])
    assert st2.target_replicas(now=1.0) == 4


def test_llm_autoscaling_policy_decisions():
    """LLMAutoscalingPolicy unit semantics: hot on any exceeded target,
    cold only on a COMPLETE quiet window with no backlog, silence never
    scales up, backlog blocks scale-down, bounds clamp."""
    from ray_tpu.serve import LLMAutoscalingPolicy

    p = LLMAutoscalingPolicy(
        min_replicas=1,
        max_replicas=3,
        target_queue_time_p99_s=0.1,
        target_ttft_p99_s=0.5,
        downscale_margin=0.5,
    )
    hot_q = {"queue_time_p99_s": 0.2, "ttft_p99_s": 0.01,
             "prefill_backlog_tokens": 0, "window_complete": True}
    cold = {"queue_time_p99_s": 0.01, "ttft_p99_s": 0.01,
            "prefill_backlog_tokens": 0, "window_complete": True}
    idle = {"queue_time_p99_s": None, "ttft_p99_s": None,
            "prefill_backlog_tokens": 0, "window_complete": True}
    partial = {"queue_time_p99_s": None, "ttft_p99_s": None,
               "prefill_backlog_tokens": 0, "window_complete": False}
    warm = {"queue_time_p99_s": 0.08, "ttft_p99_s": 0.01,
            "prefill_backlog_tokens": 0, "window_complete": True}
    backlogged = {"queue_time_p99_s": None, "ttft_p99_s": None,
                  "prefill_backlog_tokens": 500, "window_complete": True}
    decode_bound = {"queue_time_p99_s": None, "ttft_p99_s": None,
                    "prefill_backlog_tokens": 0, "window_complete": True,
                    "decode_saturated": True}
    assert p.desired_replicas(hot_q, 1) == 2  # one step up
    assert p.desired_replicas(hot_q, 3) == 3  # clamped at max
    assert p.desired_replicas(cold, 2) == 1  # quiet full window: step down
    assert p.desired_replicas(cold, 1) == 1  # clamped at min
    assert p.desired_replicas(idle, 2) == 1  # idle window counts as cold
    assert p.desired_replicas(partial, 2) == 2  # incomplete window: hold
    # Between margin*target and target: neither hot nor cold (hysteresis
    # band) — hold.
    assert p.desired_replicas(warm, 2) == 2
    # Saturated-but-silent (all slots decoding, backlog queued): the
    # backlog blocks scale-down even though percentiles are silent.
    assert p.desired_replicas(backlogged, 2) == 2
    # Decode-bound silence: long generations produce no admission-time
    # histogram samples and no prefill backlog, but every decode slot
    # busy must block scale-down too — not read as idleness.
    assert p.desired_replicas(decode_bound, 2) == 2

    backlog_policy = LLMAutoscalingPolicy(
        min_replicas=1, max_replicas=4,
        max_prefill_backlog_per_replica=100.0,
    )
    assert backlog_policy.desired_replicas(backlogged, 2) == 3  # 250/replica

    with pytest.raises(ValueError, match="at least one target"):
        serve.LLMAutoscalingPolicy()
    with pytest.raises(ValueError, match="min_replicas"):
        serve.LLMAutoscalingPolicy(
            min_replicas=0, target_ttft_p99_s=1.0
        )


def test_replica_drain_rejects_new_and_interrupts_streams():
    """ReplicaActor drain semantics, no serve stack: after drain(0) new
    unary AND streaming dispatches bounce with the retryable
    ReplicaDrainingError; an in-flight stream is interrupted at the
    deadline with the user generator's cleanup run BEFORE the error
    propagates (the LLM ingress frees engine resources in that finally)."""
    from ray_tpu.exceptions import ReplicaDrainingError
    from ray_tpu.serve._private.replica import ReplicaActor

    cleaned = []

    class Streamy:
        def __call__(self, n):
            try:
                for i in range(n):
                    yield i
            finally:
                cleaned.append(True)

    rep = ReplicaActor("dep", "dep#0", Streamy, (), {})
    # In-flight stream started BEFORE the drain...
    gen = rep.handle_request_streaming("__call__", (100,), {})
    assert next(gen) == 0
    assert rep.drain(0.0) is True  # deadline already passed
    # ...gets interrupted at the next pull, after user-generator cleanup.
    with pytest.raises(ReplicaDrainingError):
        next(gen)
    assert cleaned == [True]
    # New work bounces immediately with the same typed (retryable) error.
    with pytest.raises(ReplicaDrainingError):
        rep.handle_request("__call__", (3,), {})
    with pytest.raises(ReplicaDrainingError):
        list(rep.handle_request_streaming("__call__", (3,), {}))
    m = rep.get_metrics()
    assert m["draining"] is True
    assert m["num_drain_interrupted"] == 1
    assert m["num_ongoing_requests"] == 0  # interrupted stream released


def test_replica_drain_lets_inflight_finish_within_timeout():
    """A drain with a generous deadline does NOT interrupt: the in-flight
    stream runs to completion (zero migrations), only new work bounces."""
    from ray_tpu.exceptions import ReplicaDrainingError
    from ray_tpu.serve._private.replica import ReplicaActor

    class Streamy:
        def __call__(self, n):
            yield from range(n)

    rep = ReplicaActor("dep", "dep#0", Streamy, (), {})
    gen = rep.handle_request_streaming("__call__", (5,), {})
    assert next(gen) == 0
    rep.drain(30.0)
    assert list(gen) == [1, 2, 3, 4]  # finishes gracefully
    with pytest.raises(ReplicaDrainingError):
        rep.handle_request("__call__", (1,), {})
    assert rep.get_metrics()["num_drain_interrupted"] == 0


def test_scale_down_publishes_shrunk_set_before_stop(serve_instance):
    """Satellite: the scale-down ordering fix. The shrunk replica set must
    reach long-pollers BEFORE any stop RPC runs, so routers never
    dispatch to a dying replica in the gap. A delay injected at
    controller.drain_replica holds the stop path open; the snapshot must
    already be shrunk while the victim is still alive and DRAINING."""
    from ray_tpu._private import fault_injection as fi
    from ray_tpu.serve._private.controller import get_or_create_controller

    @serve.deployment(num_replicas=2)
    def echo(x):
        return x

    serve.run(echo.bind(), name="drain-order")
    controller = get_or_create_controller()
    _, before = ray_tpu.get(
        controller.get_replica_snapshot.remote("drain-order", "echo")
    )
    assert len(before) == 2

    spec = fi.inject(
        "controller.drain_replica", action="delay", delay_s=1.5
    )
    try:
        serve.scale_deployment("echo", 1, app_name="drain-order")
        # The bump precedes the (delayed) drain thread: the snapshot
        # shrinks well before the 1.5s stop delay elapses.
        deadline = time.monotonic() + 1.0
        after = before
        while time.monotonic() < deadline:
            _, after = ray_tpu.get(
                controller.get_replica_snapshot.remote("drain-order", "echo")
            )
            if len(after) == 1:
                break
            time.sleep(0.02)
        assert len(after) == 1, "shrunk set not published before the stop"
        assert spec.hits >= 1  # the stop path is really parked in the delay
        (victim_tag,) = set(before) - set(after)
        # The victim is DRAINING — alive and still answering RPCs — not
        # killed: in-flight work on it keeps running.
        obs = ray_tpu.get(controller.get_observability.remote())
        dep = obs["drain-order"]["echo"]
        assert dep["replica_states"].get(victim_tag) == "DRAINING"
        victim = before[victim_tag]
        assert ray_tpu.get(victim.get_metrics.remote(), timeout=5.0)[
            "draining"
        ] in (False, True)  # RPC succeeds: the actor is alive
    finally:
        fi.remove(spec)
    # Eventually the drain completes: victim STOPPED, history records it.
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        hist = ray_tpu.get(
            controller.get_replica_state_history.remote("drain-order", "echo")
        )
        states = [h["state"] for h in hist if h["tag"] == victim_tag]
        if states and states[-1] == "STOPPED":
            break
        time.sleep(0.05)
    assert states[-1] == "STOPPED"
    assert "DRAINING" in states


def test_scale_up_failure_keeps_deployment_healthy(serve_instance):
    """Satellite: controller.start_replica chaos during an autoscale-up
    leaves the deployment HEALTHY at its current count and retrying —
    never wedged in DEPLOY_FAILED while live replicas serve."""
    from ray_tpu._private import fault_injection as fi

    @serve.deployment(
        autoscaling_config={
            "min_replicas": 1,
            "max_replicas": 3,
            "target_num_ongoing_requests_per_replica": 1,
            "look_back_period_s": 0.5,
        },
        max_concurrent_queries=4,
    )
    class Slow:
        def __call__(self, _):
            time.sleep(0.25)
            return "ok"

    handle = serve.run(Slow.bind(), name="upfail")
    spec = fi.inject(
        "controller.start_replica", match="upfail", times=None
    )
    try:
        results = []

        def fire():
            results.append(handle.remote(None).result(timeout_s=30))

        threads = [threading.Thread(target=fire) for _ in range(10)]
        for t in threads:
            t.start()
        # Give the autoscaler time to want more replicas and fail to get
        # them (every start attempt raises InjectedFault).
        deadline = time.monotonic() + 8.0
        saw_attempt = False
        while time.monotonic() < deadline:
            st = serve.status()["upfail"]["Slow"]
            assert st["status"] != "DEPLOY_FAILED", st
            if spec.fires >= 1:
                saw_attempt = True
                if st["status"] == "HEALTHY" and st["num_replicas"] == 1:
                    break
            time.sleep(0.05)
        for t in threads:
            t.join()
        assert saw_attempt, "autoscale-up start was never attempted"
        st = serve.status()["upfail"]["Slow"]
        assert st["status"] == "HEALTHY"
        assert st["num_replicas"] == 1
        assert len(results) == 10  # live replica kept serving throughout
    finally:
        fi.remove(spec)
    # With the fault gone, the deployment can actually grow under load.
    done = []

    def fire2():
        done.append(handle.remote(None).result(timeout_s=30))

    threads = [threading.Thread(target=fire2) for _ in range(10)]
    for t in threads:
        t.start()
    grew = False
    deadline = time.monotonic() + 15.0
    while time.monotonic() < deadline:
        if serve.status()["upfail"]["Slow"]["num_replicas"] > 1:
            grew = True
            break
        time.sleep(0.05)
    for t in threads:
        t.join()
    assert grew
    assert len(done) == 10
