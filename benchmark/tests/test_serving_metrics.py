import pytest

from lib.serving_metrics import reduce_log


def record(rid, due, tokens, status="ok", phase="run", sent_lag=0.001):
    return {
        "id": rid, "phase": phase, "due": due, "sent": due + sent_lag,
        "token_times": tokens, "token_ids": [1] * len(tokens), "status": status,
        "prompt_tokens": 10, "max_new_tokens": len(tokens),
    }


def log(records, loop="open"):
    return {"open": 100.0, "close": 110.0, "loop": loop, "records": records}


def test_latencies_count_from_the_due_time_and_the_rules_for_failed():
    records = [
        record("lead", 99.0, [99.5, 100.5, 101.0]),            # due before the window
        record("a", 100.0, [100.2, 100.3, 100.5]),             # complete
        record("b", 101.0, [101.4, 101.5], status="error: boom"),
        record("c", 105.0, [], status="cancelled"),            # first 4/5, no token: failed
        record("d", 109.0, [], status="cancelled"),            # last fifth: neither
        record("e", 108.0, [108.5, 111.0], status="cancelled"),  # first token, cut at close
        record("p", 90.0, [90.5], phase="prime"),
    ]
    out = reduce_log(log(records))
    assert out["due_in_window"] == 5
    assert (out["attempted"], out["failed"]) == (3, 2)       # a, b, c
    assert out["in_flight_at_close"] == 2
    assert [r["id"] for r in out["complete"]] == ["a"]
    # a: 200 ms, b and c: the window's length, e: 500 ms.
    assert sorted([200.0, 10000.0, 10000.0, 500.0])[1] == pytest.approx(500.0)
    assert out["ttft_samples"] == 4
    assert out["ttft_p50_ms"] == pytest.approx((500.0 + 10000.0) / 2)
    # gaps whose later token arrived inside the window, requests of the
    # lead-in included; e's second token came after the close.
    assert out["itl_samples"] == 5
    assert out["completed_tokens_per_s"] == pytest.approx(8 / 10.0)
    assert out["generator_lag_p99_ms"] == pytest.approx(1.0)


def test_a_closed_loops_queue_is_not_a_failure():
    records = [record("c", 101.0, [], status="cancelled")]
    assert reduce_log(log(records, loop="closed"))["failed"] == 0
    assert reduce_log(log(records, loop="open"))["failed"] == 1


def test_closed_loops_margin_is_the_fewest_requests_a_caller_had_left():
    records = [record("a", 100.0, [100.2, 100.3])]
    closed = dict(log(records, loop="closed"), requests_left_by_caller=[52, 49, 51])
    assert reduce_log(closed)["fewest_requests_left"] == 49
    assert reduce_log(log(records))["fewest_requests_left"] is None  # an open loop has none


def test_gap_percentiles_and_their_per_layer_readers():
    """The median gap is the loaded chat cell's end-to-end metric; p90 and
    p95 of the same gaps are read per layer from the `client` line."""
    from lib import layer_metrics

    # One request, 101 tokens: 80 gaps of 20 ms, 10 of 35 ms, 10 of 52 ms, as
    # the loaded cell's two modes lie (PERF.md section 2).
    gaps = [0.020] * 80 + [0.035] * 10 + [0.052] * 10
    times = [100.0]
    for gap in gaps:
        times.append(times[-1] + gap)
    out = reduce_log(log([record("a", 100.0, times)]))
    assert out["itl_samples"] == 100
    assert out["itl_p50_ms"] == pytest.approx(20.0)
    assert out["itl_p90_ms"] == pytest.approx(35.0 + 0.1 * 17.0)
    assert out["itl_p95_ms"] == pytest.approx(52.0)
    read = layer_metrics.read_all(["token_gap_p90_ms", "token_gap_p95_ms"], {"client": out})
    assert read == {"token_gap_p90_ms": out["itl_p90_ms"], "token_gap_p95_ms": out["itl_p95_ms"]}
    assert layer_metrics.read("token_gap_p95_ms", {"client": {}}) is None
