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
        module("jit_a(11)", 0, 100), module("jit_b(22)", 200, 300),
        op("fusion.1", 0, 60), op("fusion.1", 60, 40),      # back to back
        op("while.3", 200, 300),                             # holds the next two
        op("copy.2", 210, 50), op("fusion.9", 300, 150),
    ]
    reduced = xplane.reduce_events(events, window_s=1000e-9)
    assert reduced["busy_s"] == pytest.approx(400e-9)        # 0-100 and 200-500
    assert reduced["idle_share"] == pytest.approx(0.6)
    seconds = reduced["op_seconds"]
    assert seconds["jit_a/fusion.1 fusion"] == pytest.approx(100e-9)
    assert seconds["jit_b/copy.2 copy"] == pytest.approx(50e-9)
    assert seconds["jit_b/while.3 while"] == pytest.approx(100e-9)  # 300 less its body
    assert sum(seconds.values()) == pytest.approx(reduced["busy_s"])
    assert reduced["modules"]["jit_b"] == {
        "runs": 1, "median_s": pytest.approx(300e-9), "total_s": pytest.approx(300e-9),
    }
    assert reduced["breakdown"]["idle_gaps"] == [["jit_a -> jit_b", pytest.approx(100e-9)]]
    assert reduced["breakdown"]["device_ops"][0][0] == "jit_b/fusion.9 fusion"
    assert xplane.op_share(reduced, r"copy") == pytest.approx(50 / 400)
    assert xplane.op_share(reduced, r"no_such_kernel") is None
    assert xplane.module_median_s(reduced, r"jit_") == pytest.approx(200e-9)


def test_exposed_collectives_are_those_no_compute_covers():
    events = [
        module("jit_tp(1)", 0, 1000),
        op("all-reduce.1", 100, 100),            # alone: all exposed
        op("fusion.1", 300, 200),
        op("collective-permute.4", 400, 200),    # half under fusion.1
    ]
    reduced = xplane.reduce_events(events, window_s=1000e-9)
    assert reduced["collective_s"] == pytest.approx(300e-9)
    assert reduced["collective_exposed_s"] == pytest.approx(200e-9)


def test_two_devices_are_averaged_and_none_means_nothing_ran():
    other = "/device:TPU:1"
    events = [op("fusion.1", 0, 100), op("fusion.1", 0, 300, plane=other)]
    reduced = xplane.reduce_events(events, window_s=1000e-9)
    assert reduced["devices"] == 2 and reduced["busy_s"] == pytest.approx(200e-9)
    assert xplane.reduce_events([], window_s=1.0) is None
    assert xplane.reduce_events([module("jit_a(1)", 0, 5)], window_s=1.0) is None


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace beside the tests")
def test_recorded_chip_trace():
    """Three executions of `tests/record_trace.py`'s program on a TPU v5 lite
    with 2 ms pauses between them."""
    events = xplane.load_events(RECORDED)
    planes = {e[0] for e in events}
    assert planes == {"/device:TPU:0"}
    reduced = xplane.reduce_events(events, window_s=0.05)
    program = reduced["modules"]["jit_tiny_program"]
    assert program["runs"] == 3
    assert 0 < reduced["busy_s"] <= program["total_s"] * 1.001
    assert sum(reduced["op_seconds"].values()) == pytest.approx(reduced["busy_s"], rel=1e-6)
    assert "jit_tiny_program/while while" in reduced["op_seconds"]
    # The pauses between the executions are the longest gaps, between two
    # runs of the one program.
    name, seconds = reduced["breakdown"]["idle_gaps"][0]
    assert name == "jit_tiny_program -> jit_tiny_program" and seconds > 0.003
    assert 0.0 < reduced["idle_share"] < 1.0
