"""The trace reduction on a small event list: busy and idle time, kernel
time by name, gaps named by the host annotation that covered them, and
the step executable's runs.  ``recorded_train.json.gz``, when present, is
a slice of a real one-chip training trace."""
from pathlib import Path

import pytest

import kernels
import trace_reduce as TR

DEV = "/device:TPU:0"


def op(name, t0, dur, target=""):
    return {"kind": "op", "plane": DEV, "name": name, "t0": t0, "dur": dur,
            "long": "", "target": target}


def host(name, t0, dur):
    return {"kind": "host", "plane": "/host:CPU", "name": name, "t0": t0,
            "dur": dur, "long": "", "target": ""}


def module(name, t0, dur):
    return {"kind": "module", "plane": DEV, "name": name, "t0": t0,
            "dur": dur, "long": "", "target": ""}


EVENTS = [
    host(TR.WINDOW, 100, 1000),                  # window [100, 1100]
    host("bench.step", 100, 500), host("bench.data", 120, 60),
    host("bench.step", 600, 500),
    op("fusion.1", 50, 100),                     # clipped to [100, 150]
    op("rdp_matmul_cols.3", 200, 100, kernels.MARK),   # [200, 300]
    op("fusion.2", 250, 100),                    # overlaps: union [200, 350]
    op("rdp_cols_dgrad.4", 700, 200, kernels.MARK),    # [700, 900]
    op("late", 1050, 200),                       # clipped to [1050, 1100]
    module("jit_step", 150, 300), module("jit_step", 650, 300),
    module("jit_other", 500, 10),
]


def test_window_and_busy():
    assert TR.window(EVENTS) == (100, 1100)
    # busy [100,150] + [200,350] + [700,900] + [1050,1100] = 50+150+200+50
    assert TR.busy_ns(EVENTS, DEV, 100, 1100) == 450
    busy, window = TR.busy_s(EVENTS)
    assert busy == pytest.approx(450e-9) and window == pytest.approx(1e-6)


def test_gaps_and_their_host_names():
    assert TR.gaps(EVENTS, DEV) == [(150, 200), (350, 700), (900, 1050)]
    idle = TR.idle_by_host(EVENTS)
    # [150,200] and [350,600] lie in the first step; [600,700] and
    # [900,1050] in the second; bench.data (120-180) overlaps 30 of the
    # first gap, bench.step all 50 of it
    assert idle == pytest.approx({"bench.step": 550e-9})


def test_op_seconds_and_kernel_time():
    by = TR.op_seconds(EVENTS)
    # fusion.2 [250, 350] starts inside the kernel [200, 300]: its self
    # time loses the 50 they share; events are pro-rated to the window
    assert by["rdp_matmul_cols.3"] == pytest.approx(50e-9)
    assert by["rdp_cols_dgrad.4"] == pytest.approx(200e-9)
    assert by["fusion.2"] == pytest.approx(100e-9)
    assert by["fusion.1"] == pytest.approx(50e-9)
    assert by["late"] == pytest.approx(50e-9)
    assert kernels.kernel_seconds(EVENTS) == pytest.approx(250e-9)


@pytest.mark.parametrize("name, counted", [
    ("rdp_matmul_rows.12", True), ("rdp_rows_wgrad", True),
    ("flash_attention.7", False), ("rdp_matmul_colsx.1", False)])
def test_kernels_are_found_by_name(name, counted):
    """Only the pattern kernels' names count; another Pallas kernel is
    named by ``unknown_kernels`` and kept out of their time."""
    ev = [host(TR.WINDOW, 0, 100), op(name, 10, 40, kernels.MARK),
          op("fusion.9", 60, 20)]
    assert kernels.kernel_seconds(ev) == pytest.approx(40e-9 * counted)
    assert kernels.unknown_kernels(ev) == (
        set() if counted else {kernels.base_name(name)})


def test_self_time_of_nested_ops():
    ev = [host(TR.WINDOW, 0, 100), op("while.1", 0, 100),
          op("body.1", 10, 30), op("body.2", 50, 20), op("inner", 55, 5)]
    by = TR.op_seconds(ev)
    assert by == pytest.approx({"while.1": 50e-9, "body.1": 30e-9,
                                "body.2": 15e-9, "inner": 5e-9})


def test_module_runs_and_breakdown():
    runs = TR.module_runs(EVENTS, DEV, "jit_step")
    assert runs == [(150, 450), (650, 950)]
    b = TR.breakdown(EVENTS)
    assert b["device_ops"][0] == ["rdp_cols_dgrad.4", pytest.approx(200e-9)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_no_window_raises():
    with pytest.raises(ValueError):
        TR.window([e for e in EVENTS if e["name"] != TR.WINDOW])


def test_union():
    assert TR.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]


RECORDED = Path(__file__).with_name("recorded_train.json.gz")


def test_recorded_trace():
    """Two training steps of a one-chip v5e trace (train-rdp-p0.5, 8
    layers): the device was busy for 97.5% of them, the compact kernels
    took 0.264 s of it, and the steps ran 11.1 ms apart."""
    ev = TR.load(str(RECORDED))
    busy, window = TR.busy_s(ev)
    assert busy == pytest.approx(0.816846689) and busy < window
    assert kernels.kernel_seconds(ev) == pytest.approx(0.264375381)
    # every Pallas kernel of the step is a pattern kernel, each of the six
    assert kernels.unknown_kernels(ev) == set()
    names = {kernels.base_name(e["name"]) for e in ev
             if e["kind"] == "op" and kernels.is_pattern_kernel(e)}
    assert names == kernels.PATTERN_KERNELS
    (plane,) = TR.device_planes(ev)
    runs = TR.module_runs(ev, plane, "jit_step")
    assert len(runs) == 3            # the third starts at the slice's end
    assert (runs[1][0] - runs[0][1]) / 1e6 == pytest.approx(11.126, abs=1e-3)
    assert sum(TR.op_seconds(ev).values()) == pytest.approx(busy)
