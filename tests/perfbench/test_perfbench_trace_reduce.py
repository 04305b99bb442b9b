"""trace_reduce.py against one small recorded-shape trace kept as a fixture:
busy and idle, per-operation sums, gap attribution, exposed collective time.
Times in the fixture are nanoseconds, as the profiler gives them."""
import json
import os

import pytest

from benchmarks.harness import trace_reduce as T

HERE = os.path.dirname(os.path.abspath(__file__))
EX = json.load(open(os.path.join(HERE, "fixtures", "small_trace.json")))


def test_busy_idle_and_window_of_one_device():
    r = T.reduce(EX, n_devices=1)
    # device 0: operations cover [1000,3000] [3500,5000] [6000,10000] = 7500 ns
    assert r["devices"] == 1
    assert r["busy_s"] == pytest.approx(7500e-9)
    assert r["window_s"] == pytest.approx(9000e-9)


def test_busy_is_averaged_over_the_devices_used():
    r = T.reduce(EX, n_devices=2)
    # device 1: [1000,4000] [4000,4500]u[4200,7000] [7000,10000] = 9000 ns
    assert r["per_device"][1]["busy_ns"] == pytest.approx(9000)
    assert r["busy_s"] == pytest.approx((7500 + 9000) / 2 * 1e-9)


def test_per_operation_sums_under_stable_names_behind_their_program():
    r = T.reduce(EX, n_devices=1)
    ops = dict(map(tuple, r["device_ops"]))
    # the TPU's events carry the instruction's text: name without its number,
    # first result's type and shape
    assert ops["jit_step/fusion:bf16[16,512]"] == pytest.approx(2000e-9)
    assert ops["jit_step/fusion"] == pytest.approx((1500 + 2000) * 1e-9)
    assert not any("while" in k for k in ops)          # containers are not operations
    assert ops["jit_step/all-reduce"] == pytest.approx(2000e-9)
    assert r["device_op_calls"]["jit_step/fusion"] == 2
    assert r["programs"]["jit_step"] == {"calls": 2, "seconds": pytest.approx(8000e-9)}
    assert T.program_stats(r, r"^jit_step")[1] == 2
    assert T.op_seconds(r, r"all-reduce") == (pytest.approx(2000e-9), 2)


def test_idle_gaps_are_attributed_to_what_the_host_was_doing():
    r = T.reduce(EX, n_devices=1)
    gaps = dict(map(tuple, r["idle_gaps"]))
    # [3000,3500] lies inside one run of the step; [5000,6000] is covered to
    # 90 % by the host's data_wait span
    assert gaps["within_jit_step"] == pytest.approx(500e-9)
    assert gaps["program:data_wait"] == pytest.approx(1000e-9)
    assert sum(gaps.values()) == pytest.approx((9000 - 7500) * 1e-9)


def test_exposed_collective_time_is_the_part_no_other_operation_covers():
    r = T.reduce(EX, n_devices=2)
    d0, d1 = r["per_device"]
    # device 0: a synchronous all-reduce is exposed whole
    assert d0["collective_ns"] == d0["collective_exposed_ns"] == pytest.approx(2000)
    # device 1: start [4000,4500] is covered from 4200 by fusion.9; done
    # [7000,10000] by nothing: 200 + 3000
    assert d1["collective_ns"] == pytest.approx(3500)
    assert d1["collective_exposed_ns"] == pytest.approx(3200)
    assert r["collective_exposed_s"] == pytest.approx(3200e-9)       # worst device


def test_stable_names():
    assert T.stable_name("fusion.123") == "fusion"
    assert T.stable_name("%all-reduce-start.4 = f32[8]{0} all-reduce-start(f32[8]{0} %x)") \
        == "all-reduce-start:f32[8]"
    assert T.opcode("%all-reduce-start.4 = f32[8]{0:T(8)S(1)} all-reduce-start(f32[8]{0} %x)") \
        == "all-reduce-start"
    assert T.opcode("%w.1 = (s32[]{:T(128)}, f32[8]{0}) while((s32[]) %t), body=%b") == "while"
    assert T.is_container("%w.1 = (s32[]{:T(128)}) while((s32[]) %t)") and not T.is_container("fusion.3")
    assert T.stable_name("jit__decode_program(3217)") == "jit__decode_program"
    assert T.stable_name("copy") == "copy"


def test_a_trace_with_no_device_plane_reduces_to_nothing():
    assert T.reduce({"planes": [EX["planes"][-1]]}) is None
