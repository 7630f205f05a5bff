"""From the load generator's log to what a client saw. Pure arithmetic on
the log's host-clock times; the rules are ISSUE 22's and PERF.md section 2.

- A request belongs to the window when it was due in [open, close).
- Latencies count from the due time, so a stall charges every request
  behind it.
- Failed: it ended in an error, or, in an open loop, it was due in the
  window's first four fifths and has no first token when the window closes
  (a closed loop's callers queue by design, three to a slot, and their wait
  is not a failure). A failed request's time to first token counts as the
  window's length.
- Attempted: failed, or complete by the close. A request still in flight at
  the close was cancelled and is neither.
- A gap is the time between two consecutive tokens of one request; it
  belongs to the window when its later token arrived inside it.
"""

from __future__ import annotations

from lib.stats import percentile


def reduce_log(log: dict) -> dict:
    open_at, close_at = log["open"], log["close"]
    seconds = close_at - open_at
    run = [r for r in log["records"] if r["phase"] == "run"]
    mine = [r for r in run if open_at <= r["due"] < close_at]

    ttft_ms, failed, complete, in_flight = [], [], [], []
    for r in mine:
        first = next((t for t in r["token_times"][:1] if t <= close_at), None)
        if r["status"].startswith("error"):
            failed.append(r)
            ttft_ms.append(seconds * 1000.0)
        elif first is None:
            if log["loop"] == "open" and r["due"] < open_at + 0.8 * seconds:
                failed.append(r)
                ttft_ms.append(seconds * 1000.0)
            else:
                in_flight.append(r)
        else:
            ttft_ms.append((first - r["due"]) * 1000.0)
            if r["status"] == "ok" and r["token_times"][-1] <= close_at:
                complete.append(r)
            else:
                in_flight.append(r)

    gaps_ms, tokens_in_window = [], 0
    for r in run:
        times = r["token_times"]
        tokens_in_window += sum(1 for t in times if open_at <= t <= close_at)
        gaps_ms.extend(
            (b - a) * 1000.0
            for a, b in zip(times, times[1:])
            if open_at <= b <= close_at
        )
    lag_ms = [(r["sent"] - r["due"]) * 1000.0 for r in run if r["sent"] is not None]
    third = seconds / 3.0
    by_third = [
        [
            (r["token_times"][0] - r["due"]) * 1000.0
            for r in mine
            if r["token_times"]
            and open_at + i * third <= r["due"] < open_at + (i + 1) * third
        ]
        for i in range(3)
    ]
    return {
        "attempted": len(complete) + len(failed),
        "failed": len(failed),
        "failed_ids": [r["id"] for r in failed][:10],
        "errors": sorted({r["status"] for r in failed if r["status"].startswith("error")})[:3],
        "due_in_window": len(mine),
        "complete": complete,
        "in_flight_at_close": len(in_flight),
        "ttft_samples": len(ttft_ms),
        "ttft_p50_ms": percentile(ttft_ms, 50.0),
        "ttft_p90_ms": percentile(ttft_ms, 90.0),
        "ttft_p90_ms_by_third": [percentile(t, 90.0) for t in by_third],
        "itl_samples": len(gaps_ms),
        "itl_p50_ms": percentile(gaps_ms, 50.0),
        "itl_p90_ms": percentile(gaps_ms, 90.0),
        "itl_p95_ms": percentile(gaps_ms, 95.0),
        "completed_tokens_per_s": tokens_in_window / seconds,
        "prompt_tokens_sent": sum(r["prompt_tokens"] for r in mine),
        "generator_lag_p99_ms": percentile(lag_ms, 99.0),
        "generator_lag_max_ms": max(lag_ms, default=None),
        "window_s": seconds,
        "callers_that_ran_dry": log.get("callers_that_ran_dry", []),
        # Closed loop: the fewest requests any caller had not yet begun when
        # the window closed, which is how far the mix is from running dry.
        "fewest_requests_left": min(log.get("requests_left_by_caller") or [None]),
    }
