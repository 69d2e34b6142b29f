"""Trace reduction: hand-computed numbers on a made-up trace, and the
same reduction on a short trace recorded on one v5e."""
from pathlib import Path

import pytest

from benchlib import xtrace

# Device 0: a loop op enclosing two kernels, then a matmul; device 1 busy
# 10 ns.  Window 0..100 ns; engine steps 0..60 and 60..100, a pack span
# inside the second, idle gaps 40..50 and 70..100.
T = xtrace.Trace(
    window=(0.0, 100.0),
    ops=[[("while", 0.0, 40.0), ("paged_attention_kernel", 0.0, 20.0),
          ("paged_attention_kernel", 20.0, 40.0), ("fusion.3", 50.0, 70.0)],
         [("fusion.1", 95.0, 105.0)]],
    host={"engine.step": [(0.0, 60.0), (60.0, 100.0)],
          "scheduler.pack": [(80.0, 90.0)]})


def test_union_and_overlap():
    assert xtrace.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert xtrace.overlap([(0, 3), (5, 7)], 2, 6) == 2


def test_busy_averages_devices_inside_window():
    # device 0: 0..40 and 50..70 = 60; device 1: 95..100 = 5
    assert xtrace.busy_ns(T) == pytest.approx(32.5)


def test_op_seconds_counts_leaf_ops_once():
    got = xtrace.op_seconds(T)
    assert got == pytest.approx({"paged_attention_kernel": 40e-9,
                                 "fusion.3": 20e-9})


def test_idle_gaps_named_by_open_span():
    assert xtrace.idle_gaps(T) == [("scheduler.pack", pytest.approx(30e-9)),
                                   ("engine.step", pytest.approx(10e-9))]


# A 1-s traced window of ds7b.decode on one TPU v5 lite, trimmed to the
# planes and lines the reduction reads (device XLA Ops with names cut at
# " = ", the benchmark's host annotations) and stored as a text proto.
FIXTURE = Path(__file__).parent / "data" / "ds7b_decode.xplane.pbtxt"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    from jax.profiler import ProfileData
    raw = ProfileData.text_proto_to_serialized_xspace(FIXTURE.read_text())
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(raw)
    return xtrace.load(str(path))


def test_recorded_chip_trace(recorded):
    """The numbers that the chip run reported from this trace."""
    t = recorded
    assert t.window is not None and len(t.ops) == 1
    lo, hi = t.window
    assert (hi - lo) * 1e-9 == pytest.approx(1.001344463)
    assert xtrace.busy_ns(t) * 1e-9 == pytest.approx(0.980573426)
    ops = xtrace.op_seconds(t)
    assert ops["paged_attention_4d.13"] == pytest.approx(0.732388115)
    assert sum(ops.values()) <= (hi - lo) * 1e-9
    b = xtrace.breakdown(t)
    assert b["device_ops"][0][0] == "paged_attention_4d.13"
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    assert b["idle_gaps"][0] == ["outside engine.step",
                                 pytest.approx(0.00617345)]
