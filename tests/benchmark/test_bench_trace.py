"""The trace-to-metrics reduction of the chip benchmark, on synthetic
traces: busy time as a union of operation intervals clipped to the
window, idle share, the busiest device, and idle time split by host
phase."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "perfbench"))

import trace_reduce as tr  # noqa: E402


def _trace(**kw):
    ops = {"/device:TPU:0": [("fusion.1", 1.0, 2.0), ("fusion.2", 1.5, 3.0),
                             ("copy", 5.0, 6.0), ("fusion.1", 9.0, 12.0)]}
    spans = [("bench.window", 0.0, 10.0), ("bench.params", 0.0, 1.0),
             ("bench.lower", 3.0, 4.0), ("bench.compile", 4.0, 5.0),
             ("bench.device", 5.0, 9.5), ("bench.summarize", 9.5, 10.0)]
    return tr.Trace(device_ops=kw.get("ops", ops),
                    host_spans=kw.get("spans", spans))


def test_union_merges_and_clips():
    assert tr.union([(1, 2), (1.5, 3), (5, 6), (9, 12)], (0, 10)) == \
        [(1, 3), (5, 6), (9, 10)]
    assert tr.union([(11, 12)], (0, 10)) == []


def test_busy_and_idle_share():
    red = tr.reduce(_trace())
    # busy [1,3) + [5,6) + [9,10) inside a 10 s window
    assert red.window_s == pytest.approx(10.0)
    assert red.busy_s["/device:TPU:0"] == pytest.approx(4.0)
    assert red.idle_share("/device:TPU:0") == pytest.approx(0.6)


def test_top_ops_are_clipped_to_the_window():
    ops = dict(tr.reduce(_trace()).device_ops)
    assert ops["fusion.1"] == pytest.approx(2.0)   # 1 s + 1 s in-window
    assert ops["fusion.2"] == pytest.approx(1.5)
    assert ops["copy"] == pytest.approx(1.0)


def test_idle_time_split_by_host_phase():
    idle = dict(tr.reduce(_trace()).idle_gaps)
    assert idle["params"] == pytest.approx(1.0)     # [0,1)
    assert idle["lower"] == pytest.approx(1.0)      # [3,4)
    assert idle["compile"] == pytest.approx(1.0)    # [4,5)
    assert idle["device"] == pytest.approx(3.0)     # [6,9) of [5,9.5)
    assert idle["summarize"] == pytest.approx(0.0)  # busy to 10
    assert idle["host"] == pytest.approx(0.0)
    assert sum(idle.values()) == pytest.approx(6.0)


def test_uncovered_idle_time_is_host():
    spans = [("bench.window", 0.0, 10.0)]
    idle = dict(tr.reduce(_trace(spans=spans)).idle_gaps)
    assert idle == {"host": pytest.approx(6.0)}


def test_busiest_of_several_devices():
    ops = {"/device:TPU:0": [("a", 0.0, 2.0)],
           "/device:TPU:1": [("a", 0.0, 5.0)]}
    red = tr.reduce(_trace(ops=ops))
    assert red.busiest == "/device:TPU:1"
    assert red.mean_busy_s == pytest.approx(3.5)


@pytest.mark.parametrize("kw", [{"spans": []}, {"ops": {}},
                                {"ops": {"/device:TPU:0": []}}])
def test_nothing_to_read_gives_none(kw):
    assert tr.reduce(_trace(**kw)) is None


def test_op_names_keep_name_and_shape_only():
    hlo = "%fusion.617 = f32[8,2]{1,0:T(8,128)} fusion(%a), kind=kLoop"
    assert tr.op_name(hlo) == "%fusion.617 f32[8,2]"
    assert tr.op_name("copy.3") == "copy.3"


@pytest.mark.parametrize("plane,chip", [("/device:TPU:0", True),
                                        ("/device:TPU:3", True),
                                        ("/device:CUSTOM:Megascale Trace",
                                         False),
                                        ("/host:CPU", False)])
def test_only_chip_planes_count_as_devices(plane, chip):
    assert bool(tr.CHIP_PLANE.match(plane)) is chip
