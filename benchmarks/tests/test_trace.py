"""The trace reduction on the small trace recorded on the chip
(`record_small_trace.py`: four 1.26 ms sorts under `tick:0..2` and `sync`,
a 50 ms sleep after each of the first three and 100 ms before the fourth)."""
import json
import os

import pytest

import trace as trace_lib

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "small_trace.json")


@pytest.fixture(scope="module")
def recorded():
    with open(DATA) as f:
        d = json.load(f)
    return d["window_s"], [tuple(r) for r in d["records"]]


def test_reduction_of_the_recorded_trace(recorded):
    window_s, records = recorded
    out = trace_lib.reduce(records)
    assert out["chips_traced"] == 1
    # busy: the union of the operations' intervals; modules: their sum
    assert out["busy_s"] == pytest.approx(0.005068964, abs=1e-9)
    assert out["modules_s"] == pytest.approx(0.005069, abs=1e-9)
    assert out["busy_s"] < window_s
    ops = dict(out["device_ops"])
    assert list(ops)[0] == "%sort.6 sort"
    assert ops["%sort.6 sort"] == pytest.approx(0.005040941, abs=1e-9)
    gaps = dict(out["idle_gaps"])
    # the two sleeps inside tick:0 and tick:1; tick:2's sleep runs on into
    # sync's (one gap, its middle in sync), plus the tail after the last sort
    assert gaps["tick:0"] == pytest.approx(0.051, abs=0.001)
    assert gaps["tick:1"] == pytest.approx(0.051, abs=0.001)
    assert gaps["sync"] == pytest.approx(0.153, abs=0.002)
    assert "tick:2" not in gaps
    # busy and idle make up the window, to the millisecond the clocks differ
    assert out["busy_s"] + sum(gaps.values()) == pytest.approx(window_s,
                                                               abs=0.002)


def test_nested_operations_count_once():
    dev = "/device:TPU:0"
    records = [(dev, "XLA Ops", "%while.1 = (s32[]) while((s32[]) %t)", 0,
                100),
               (dev, "XLA Ops", "%fusion.2 = s32[4]{0} fusion(s32[4] %a)", 10,
                30),
               (dev, "XLA Ops", "%fusion.2 = s32[4]{0} fusion(s32[4] %a)", 50,
                30),
               (dev, "XLA Modules", "jit_step(1)", 0, 100)]
    out = trace_lib.reduce(records)
    assert out["busy_s"] == pytest.approx(100e-9)
    assert dict(out["device_ops"]) == {"%fusion.2 fusion": pytest.approx(60e-9),
                                       "%while.1 while": pytest.approx(40e-9)}


def test_no_device_operation_reduces_to_nothing():
    assert trace_lib.reduce([("/host:CPU", "python", "window", 0, 10)]) is None
