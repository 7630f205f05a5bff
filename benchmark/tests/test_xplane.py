import os

import pytest

from lib import xplane

DEV = "/device:TPU:0"
RECORDED = os.path.join(os.path.dirname(__file__), "data", "tiny_chip_trace.xplane.pb")


def op(name, start, dur, plane=DEV):
    name = f"{name} {name.split('.')[0]}"  # as short_name gives it: "fusion.1 fusion"
    return (plane, xplane.OPS_LINE, name, float(start), float(dur))


def module(name, start, dur, plane=DEV):
    return (plane, xplane.MODULES_LINE, name, float(start), float(dur))


def marker(start, dur):
    """The window's own event, as `load_events` hands it on."""
    return ("/host:CPU", xplane.WINDOW_MARKER, xplane.WINDOW_MARKER, float(start), float(dur))


def holds(reduced):
    """What the cut gives by construction, whatever the events."""
    assert 0.0 <= reduced["busy_s"] <= reduced["window_s"]
    assert 0.0 <= reduced["idle_share"] <= 1.0
    assert sum(reduced["op_seconds"].values()) == pytest.approx(reduced["busy_s"], rel=1e-9)
    assert reduced["busy_s"] + reduced["idle_s"] == pytest.approx(reduced["window_s"], rel=1e-9)
    return reduced


def test_short_name_keeps_instruction_and_opcode_and_marks_mosaic_kernels():
    fusion = "%fusion.13 = (f32[50304,768]{1,0:T(8,128)}, f32[8]{0}) fusion(f32[8]{0} %p.1), kind=kOutput, calls=%fused.53"
    kernel = ('%h_3.2 = (bf16[24,1024,768]{2,1,0}, f32[24,12,1024]{2,1,0}) custom-call(bf16[24,1024,2304]{2,1,0} %x), '
              'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
    assert xplane.short_name(fusion) == "fusion.13 fusion"
    assert xplane.short_name(kernel) == "h_3.2 tpu_custom_call"
    assert xplane.short_name("%while = (s32[]{:T(128)}, bf16[512,512]{1,0}) while(%tuple), body=%b") == "while while"
    assert xplane.short_name("$core.py:12 step") == "$core.py:12 step"


def test_interval_arithmetic():
    assert xplane.merge([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == [(0, 3), (5, 8)]
    assert xplane.total([(0, 3), (5, 8)]) == 6
    assert xplane.subtract([(0, 10), (20, 30)], [(5, 22), (25, 26)]) == [
        (0, 5), (22, 25), (26, 30),
    ]


def test_busy_union_idle_share_and_per_name_sums():
    events = [
        marker(0, 1000),
        module("jit_a(11)", 0, 100), module("jit_b(22)", 200, 300),
        op("fusion.1", 0, 60), op("fusion.1", 60, 40),      # back to back
        op("while.3", 200, 300),                             # holds the next two
        op("copy.2", 210, 50), op("fusion.9", 300, 150),
    ]
    reduced = holds(xplane.reduce_events(events, 1003e-9))
    assert reduced["window_from"] == "marker"
    assert reduced["window_s"] == pytest.approx(1000e-9)    # the marker's, not the host's
    assert reduced["host_window_s"] == 1003e-9
    assert reduced["busy_s"] == pytest.approx(400e-9)        # 0-100 and 200-500
    assert reduced["idle_share"] == pytest.approx(0.6)
    seconds = reduced["op_seconds"]
    assert seconds["jit_a/fusion.1 fusion"] == pytest.approx(100e-9)
    assert seconds["jit_b/copy.2 copy"] == pytest.approx(50e-9)
    assert seconds["jit_b/while.3 while"] == pytest.approx(100e-9)  # 300 less its body
    assert reduced["modules"]["jit_b"] == {
        "runs": 1, "median_s": pytest.approx(300e-9), "total_s": pytest.approx(300e-9),
        "window_runs": 1.0, "cut_runs": 0,
    }
    # Busy time and the gaps are the window: the last gap runs to its close.
    assert reduced["breakdown"]["idle_gaps"] == [
        ["jit_b -> window closes", pytest.approx(500e-9)],
        ["jit_a -> jit_b", pytest.approx(100e-9)],
    ]
    assert reduced["breakdown"]["device_ops"][0][0] == "jit_b/fusion.9 fusion"
    assert xplane.op_share(reduced, r"copy") == pytest.approx(50 / 400)
    assert xplane.op_share(reduced, r"no_such_kernel") is None
    assert xplane.module_median_s(reduced, r"jit_") == pytest.approx(200e-9)


def test_a_union_that_passes_the_window_at_both_ends_is_cut_to_it():
    """What cost five checks: the profile holds more than the window."""
    events = [
        marker(1000, 1000),
        module("jit_a(1)", 0, 3000),
        op("fusion.1", 500, 1000),       # 500 of it inside
        op("fusion.2", 1700, 2000),      # 300 of it inside
    ]
    reduced = holds(xplane.reduce_events(events, 1000e-9))
    assert reduced["busy_s"] == pytest.approx(800e-9)
    assert reduced["idle_share"] == pytest.approx(0.2)      # exactly what lies inside
    assert reduced["op_seconds"] == {
        "jit_a/fusion.1 fusion": pytest.approx(500e-9),
        "jit_a/fusion.2 fusion": pytest.approx(300e-9),
    }
    assert reduced["busy_outside_s"] == [pytest.approx(500e-9), pytest.approx(1700e-9)]
    assert reduced["breakdown"]["idle_gaps"] == [["inside jit_a", pytest.approx(200e-9)]]


def test_a_device_busy_all_through_the_window_idles_nought_and_not_less():
    events = [marker(100, 800), module("jit_a(1)", 0, 1000)]
    events += [op("fusion.1", at, 100) for at in range(0, 1000, 100)]
    reduced = holds(xplane.reduce_events(events, 800e-9))
    assert reduced["busy_s"] == reduced["window_s"] == 800e-9
    assert reduced["idle_share"] == 0.0 and reduced["breakdown"]["idle_gaps"] == []


def test_the_figures_of_perf_7_14():
    """PERF.md 7.14, seed 55000102: 5.000464 s of device events in a profile
    whose host window read 5.000147 s; the share came out -0.0063%."""
    span, window = 5.000463983e9, 5.000147257e9
    events = [module("jit__decode_step(1)", 0, span)]
    events += [op("fusion.1", at * 1e6, 1e6) for at in range(5000)]   # a millisecond each
    events += [op("fusion.2", 5000e6, span - 5000e6)]
    before = 1.0 - (span / 1e9) / (window / 1e9)
    assert before == pytest.approx(-0.0063e-2, rel=0.01)                # the parent's reading
    marked = holds(xplane.reduce_events(events + [marker(200e3, window)], window / 1e9))
    assert marked["busy_s"] == marked["window_s"] == window / 1e9 and marked["idle_share"] == 0.0
    unmarked = holds(xplane.reduce_events(events, window / 1e9))
    assert unmarked["window_from"] == "last_device_event"
    assert unmarked["busy_s"] == pytest.approx(window / 1e9) and unmarked["idle_share"] < 1e-12


def test_operations_that_straddle_an_edge_are_charged_their_part_inside():
    events = [
        marker(100, 300),
        module("jit_a(1)", 0, 500),
        op("while.3", 50, 400),            # over both edges, holds the rest
        op("fusion.1", 60, 100),           # 60 of it inside
        op("copy.2", 200, 50),             # whole
        op("fusion.9", 380, 60),           # 20 of it inside
    ]
    reduced = holds(xplane.reduce_events(events, 300e-9))
    assert reduced["busy_s"] == reduced["window_s"] == 300e-9
    assert reduced["op_seconds"] == {
        "jit_a/fusion.1 fusion": pytest.approx(60e-9),
        "jit_a/copy.2 copy": pytest.approx(50e-9),
        "jit_a/fusion.9 fusion": pytest.approx(20e-9),
        "jit_a/while.3 while": pytest.approx(170e-9),    # 300 less its body's 130
    }


def test_a_while_and_its_body_cut_to_one_start_keep_their_order():
    """Both start at the window's edge once cut; the body is still the
    body, whichever of the two the trace lists first."""
    for listed in (0, 1):
        ops = [op("while.3", 0, 1000), op("fusion.1", 0, 1000)]
        events = [marker(400, 200), module("jit_a(1)", 0, 1000)] + ops[::1 - 2 * listed]
        events[2 + listed] = op("while.3", 0, 1001)       # the outer one by a nanosecond
        reduced = holds(xplane.reduce_events(events, 200e-9))
        assert reduced["op_seconds"]["jit_a/fusion.1 fusion"] == pytest.approx(200e-9)
        assert reduced["op_seconds"]["jit_a/while.3 while"] == 0.0


def test_a_program_run_that_an_edge_cuts_is_in_busy_time_and_in_no_median():
    events = [
        marker(1000, 2000),
        module("jit_step(1)", 600, 800), op("fusion.1", 600, 800),     # half inside
        module("jit_step(1)", 1400, 500), op("fusion.1", 1400, 500),
        module("jit_step(1)", 1900, 700), op("fusion.1", 1900, 700),
        module("jit_step(1)", 2600, 1000), op("fusion.1", 2600, 1000),  # 400 inside
        module("jit_other(2)", 0, 900), op("fusion.7", 0, 900),         # outside
        module("jit_edge(3)", 2990, 100), op("fusion.8", 2990, 100),    # only ever cut
    ]
    reduced = holds(xplane.reduce_events(events, 2000e-9))
    step = reduced["modules"]["jit_step"]
    assert (step["runs"], step["cut_runs"]) == (2, 2)
    assert step["median_s"] == pytest.approx(600e-9)          # of 500 and 700 alone
    assert step["total_s"] == pytest.approx(2000e-9)          # 400 + 500 + 700 + 400
    assert step["window_runs"] == pytest.approx(2 + 0.5 + 0.4)
    assert "jit_other" not in reduced["modules"]
    assert reduced["modules"]["jit_edge"]["runs"] == 0
    assert reduced["modules"]["jit_edge"]["median_s"] is None
    assert xplane.window_runs(reduced["modules"]["jit_edge"]) == pytest.approx(0.1)
    assert xplane.module_median_s(reduced, r"jit_") == pytest.approx(600e-9)
    assert xplane.module_median_s(reduced, r"jit_edge") is None
    assert xplane.window_runs({"runs": 3}) == 3               # a reduction made before PR 56
    assert reduced["busy_s"] == reduced["window_s"]


def test_the_gaps_at_the_windows_edges_are_named_by_the_edge_and_the_program():
    events = [
        marker(0, 1000),
        module("jit_a(1)", 100, 200), op("fusion.1", 100, 200),
        module("jit_b(2)", 500, 300), op("fusion.2", 500, 300),
    ]
    reduced = holds(xplane.reduce_events(events, 1000e-9))
    assert dict(map(tuple, reduced["breakdown"]["idle_gaps"])) == {
        "window opens -> jit_a": pytest.approx(100e-9),
        "jit_a -> jit_b": pytest.approx(200e-9),
        "jit_b -> window closes": pytest.approx(200e-9),
    }
    assert reduced["idle_s"] == pytest.approx(500e-9)


def test_exposed_collectives_are_those_no_compute_covers():
    events = [
        marker(0, 1000),
        module("jit_tp(1)", 0, 1000),
        op("all-reduce.1", 100, 100),            # alone: all exposed
        op("fusion.1", 300, 200),
        op("collective-permute.4", 400, 200),    # half under fusion.1
    ]
    reduced = holds(xplane.reduce_events(events, 1000e-9))
    assert reduced["collective_s"] == pytest.approx(300e-9)
    assert reduced["collective_exposed_s"] == pytest.approx(200e-9)
    # Cut like everything else: the window closes inside the second one.
    reduced = holds(xplane.reduce_events([marker(0, 550)] + events[1:], 550e-9))
    assert reduced["collective_s"] == pytest.approx(250e-9)
    assert reduced["collective_exposed_s"] == pytest.approx(150e-9)


def test_two_devices_are_averaged_each_cut_to_the_one_window():
    other = "/device:TPU:1"
    events = [marker(50, 200), op("fusion.1", 0, 100), op("fusion.1", 0, 300, plane=other)]
    reduced = holds(xplane.reduce_events(events, 200e-9))
    assert reduced["devices"] == 2
    assert reduced["busy_s"] == pytest.approx((50e-9 + 200e-9) / 2)
    assert reduced["idle_share"] == pytest.approx(1 - 125 / 200)
    assert reduced["op_seconds"] == {"no program/fusion.1 fusion": pytest.approx(125e-9)}
    assert reduced["busy_outside_s"] == [pytest.approx(50e-9), pytest.approx(25e-9)]


def test_none_means_nothing_ran_in_the_window():
    assert xplane.reduce_events([], 1.0) is None
    assert xplane.reduce_events([module("jit_a(1)", 0, 5)], 1.0) is None
    assert xplane.reduce_events([marker(0, 1.0)], 1.0) is None
    # Operations in the profile, none of them inside what was timed.
    assert xplane.reduce_events([marker(500, 100), op("fusion.1", 0, 100)], 100e-9) is None


def test_a_trace_without_the_marker_is_cut_to_a_window_that_ends_with_its_last_event():
    """Point 3: any trace recorded before PR 56. The window is as long as
    the host said and what lies before it is cut off like anything else."""
    events = [
        module("jit_a(1)", 0, 1200),
        op("fusion.1", 0, 300), op("fusion.2", 400, 500), op("fusion.3", 1000, 200),
    ]
    reduced = holds(xplane.reduce_events(events, 1000e-9))      # 200 to 1200
    assert reduced["window_from"] == "last_device_event"
    assert reduced["window_s"] == reduced["host_window_s"] == 1000e-9
    assert reduced["busy_s"] == pytest.approx(800e-9)           # 100 + 500 + 200
    assert reduced["op_seconds"]["jit_a/fusion.1 fusion"] == pytest.approx(100e-9)
    assert reduced["modules"]["jit_a"]["runs"] == 0 and reduced["modules"]["jit_a"]["cut_runs"] == 1
    # A window longer than the profile reaches back before its first event.
    longer = holds(xplane.reduce_events(events, 2000e-9))
    assert longer["busy_s"] == pytest.approx(1000e-9) and longer["modules"]["jit_a"]["runs"] == 1
    assert longer["breakdown"]["idle_gaps"][0] == ["window opens -> jit_a", pytest.approx(800e-9)]


def test_a_window_opened_and_closed_on_the_cpu_is_found_in_its_own_profile(tmp_path):
    """The marker's way from `TracedWindow` through the profiler to
    `load_events`; the CPU backend has no device plane, so nothing else."""
    import jax.numpy as jnp

    window = xplane.TracedWindow(str(tmp_path / "trace"))
    window.open()
    jnp.ones((64, 64)).sum().block_until_ready()
    host_window_s = window.close()
    events = xplane.load_events(xplane.newest_xplane(window.trace_dir))
    marks = [e for e in events if e[1] == xplane.WINDOW_MARKER]
    assert len(marks) == 1 and {e[1] for e in events} == {xplane.WINDOW_MARKER}
    # Two clocks: the profiler's own and `time.monotonic()`, read just outside it.
    assert marks[0][4] / 1e9 == pytest.approx(host_window_s, abs=2e-3)
    assert xplane.window_of(events + [op("fusion.1", 0, 1)], host_window_s)[2] == "marker"
    assert window.profiled_s >= host_window_s + 2 * xplane.GUARD_S   # the marker lies clear of both ends
    assert xplane.reduce_trace(window.trace_dir, host_window_s, window.profiled_s) is None
    assert not os.path.exists(window.trace_dir)


def test_no_runner_times_a_trace_itself():
    """A second `start_trace()` ... `stop_trace()` with a clock of its own
    around it is how a union came to be divided by an interval it was not
    cut to."""
    runners = os.path.join(os.path.dirname(os.path.dirname(__file__)), "runners")
    tracing = []
    for name in sorted(os.listdir(runners)):
        with open(os.path.join(runners, name)) as f:
            source = f.read()
        assert "start_trace" not in source and "stop_trace" not in source, name
        if "TracedWindow" in source:
            tracing.append(name)
    assert tracing == ["serve.py", "train.py", "train_mellum.py"]


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace beside the tests")
def test_recorded_chip_trace():
    """Three executions of `tests/record_trace.py`'s program on a TPU v5 lite
    with 2 ms pauses between them, recorded before the window was marked."""
    events = xplane.load_events(RECORDED)
    planes = {e[0] for e in events}
    assert planes == {"/device:TPU:0"}
    reduced = holds(xplane.reduce_events(events, 0.05))
    assert reduced["window_from"] == "last_device_event" and reduced["window_s"] == 0.05
    program = reduced["modules"]["jit_tiny_program"]
    assert program["runs"] == 3 and program["cut_runs"] == 0
    assert 0 < reduced["busy_s"] <= program["total_s"] * 1.001
    assert reduced["busy_outside_s"] == [0.0, 0.0]
    assert "jit_tiny_program/while while" in reduced["op_seconds"]
    # The pauses between the executions are the longest gaps between two
    # operations, between two runs of the one program; the window the test
    # hands in is longer than the profile, and the rest of it lies before.
    gaps = dict(map(tuple, reduced["breakdown"]["idle_gaps"]))
    assert gaps["jit_tiny_program -> jit_tiny_program"] > 0.003
    assert set(gaps) == {"jit_tiny_program -> jit_tiny_program", "inside jit_tiny_program",
                         "window opens -> jit_tiny_program", "jit_tiny_program -> window closes"}
    assert gaps["jit_tiny_program -> window closes"] < 1e-6    # the last program's own tail
    # As the parent read it, to the nanosecond.
    assert reduced["busy_s"] == pytest.approx(2.6884e-05, abs=1e-9)
    assert reduced["idle_share"] == pytest.approx(0.99946232, abs=1e-8)
    assert program["median_s"] == pytest.approx(8.905e-06) and program["total_s"] == pytest.approx(2.6923e-05)


def test_the_chip_profiles_of_pr_56_as_both_reductions_read_them():
    """`data/pr56_two_reductions.jsonl`: every profile kept on the chip at PR
    56 as `tests/compare_reductions.py` read it, by the parent's `xplane.py`
    (handed the host's interval about the whole profile, as it used to be)
    and by this one. PERF.md section 6 has the account."""
    import json

    with open(os.path.join(os.path.dirname(__file__), "data", "pr56_two_reductions.jsonl")) as f:
        rows = [json.loads(line) for line in f]
    assert len(rows) >= 32 and len({r["workload"] for r in rows}) == 8
    over = [r for r in rows if r["parent"]["busy_s"] > r["parent"]["window_s"]]
    assert len(over) >= 6 and {r["workload"] for r in over} == {"olmo-hybrid-7b-16l.gen-batch"}
    for r in rows:
        mine = r["pr56"]
        assert 0.0 < mine["busy_s"] <= mine["window_s"] and 0.0 <= mine["idle_share"] <= 1.0
        assert r["edges"]["window_from"] == "marker"
        assert abs(r["edges"]["host_window_s"] - mine["window_s"]) < 1e-4
