"""The readers PR 37 adds (the step thread's off-CPU share of `prepare`, its
stalls, and a token's three hand-overs on its way out), over a `collected`
made by hand, and over what a program without the fields hands in."""

import json

import pytest

from lib import layer_metrics, manifest

NEW = (
    "host_prepare_offcpu_ms",
    "step_stalls",
    "token_handoff_ms",
    "stream_wait_engine_ms",
    "stream_wait_replica_ms",
    "egress_backlog_tokens",
)
TWINS = tuple("tput_" + name for name in NEW)

# A window of 1,000 dispatching steps and 12,000 tokens, each token an item
# of two streams.
COLLECTED = {
    "engine_window": {
        "dispatch_steps": 1000,
        "step_prepare_s": 7.8,
        "step_prepare_cpu_s": 3.3,
        "step_prepare_offcpu_s": 4.5,
        "stall_steps": 1,
        "egress_handoff_s": 24.0,
        "egress_handoff_tokens": 12000,
        "engine_stream_wait_s": 6.0,
        "engine_stream_items_taken": 12000,
        "stream_wait_s": 42.0,
        "stream_items_taken": 24000,
        # A gauge differenced over the window: not what the reader takes.
        "egress_backlog_tokens": -40,
    },
    "engine_after": {"egress_backlog_tokens": 310},
    "trace": None,
}
EXPECTED = {
    "host_prepare_offcpu_ms": 4.5,
    "step_stalls": 1.0,
    "token_handoff_ms": 2.0,
    "stream_wait_engine_ms": 0.5,
    "stream_wait_replica_ms": 3.0,
    "egress_backlog_tokens": 310.0,
}


def test_each_new_reader_over_a_hand_made_window():
    read = layer_metrics.read_all(NEW + TWINS, COLLECTED)
    for name, value in EXPECTED.items():
        assert read[name] == pytest.approx(value), name
        assert read["tput_" + name] == pytest.approx(value), name
    # The part of prepare off the CPU is never more than prepare.
    prepare = layer_metrics.read("host_prepare_ms", COLLECTED)
    assert read["host_prepare_offcpu_ms"] <= prepare


def test_the_parents_stats_leave_all_twelve_out_and_nothing_raises():
    """What the commit before PR 37 hands in: the phase clock's wall totals
    and the two stream counts of PR 36, none of the new fields."""
    parent = {
        "engine_window": {
            "dispatch_steps": 1000,
            "step_prepare_s": 7.8,
            "stream_items_reported": 24000,
            "stream_items_inline": 24000,
        },
        "engine_after": {"queue_depth": 3, "wedged": False},
        "trace": None,
    }
    read = layer_metrics.read_all(NEW + TWINS, parent)
    assert read == dict.fromkeys(NEW + TWINS)
    # What it did have still reads.
    assert layer_metrics.read("host_prepare_ms", parent) == pytest.approx(7.8)
    assert layer_metrics.read("stream_inline_share", parent) == 100.0
    # A window in which nothing was streamed divides by nothing: left out too.
    idle = json.loads(json.dumps(COLLECTED))
    for key in ("egress_handoff_tokens", "engine_stream_items_taken"):
        idle["engine_window"][key] = 0
    idle["engine_window"]["stream_items_taken"] = 0
    read = layer_metrics.read_all(NEW, idle)
    assert read["token_handoff_ms"] is None
    assert read["stream_wait_engine_ms"] is None
    assert read["stream_wait_replica_ms"] is None


def test_the_new_metrics_are_listed_in_the_four_serving_cells():
    loaded = manifest.load()
    by_name = {m["name"]: m for m in loaded["per_layer"]}
    serving = {
        "gpt2-large.batch-unshared",
        "granite-4.0-h-small-1of2.rag-batch",
        "laguna-s-2.1-1of8.code-gen-mixed",
    }
    for name in NEW:
        assert by_name[name]["moves"] == "itl_p50_ms"
        assert "gpt2-large.chat-sessions-loaded" in by_name[name]["workloads"]
        twin = by_name["tput_" + name]
        assert twin["moves"] == "completed_tokens_per_s"
        assert serving <= set(twin["workloads"])   # later cells may join the list
        for key in ("unit", "better", "source", "layer"):
            assert twin[key] == by_name[name][key]
