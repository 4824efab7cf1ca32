"""The trace reduction: busy union, idle share and gaps on synthesized
intervals, and the whole reduction on a trace file laid out as JAX writes
one on a TPU, with times worked by hand (`data/synthetic.xplane.pb`)."""
import pathlib

import pytest

from perfbench import xtrace

FIXTURE = pathlib.Path(__file__).with_name("data") / "synthetic.xplane.pb"


def test_busy_union_merges_overlaps_and_clips_to_the_window():
    ivs = [(5.0, 6.0), (0.5, 2.0), (1.0, 1.5), (1.8, 3.0), (9.0, 12.0)]
    m = xtrace.merge(ivs)
    assert m == [(0.5, 3.0), (5.0, 6.0), (9.0, 12.0)]
    c = xtrace.clip(m, 1.0, 10.0)
    assert c == [(1.0, 3.0), (5.0, 6.0), (9.0, 10.0)]
    assert xtrace.covered(c) == pytest.approx(4.0)
    assert xtrace.gaps(c, 1.0, 10.0) == [(3.0, 5.0), (6.0, 9.0)]
    assert xtrace.gaps([], 0.0, 2.0) == [(0.0, 2.0)]
    r = xtrace.Reduction(window_s=9.0, busy_s=4.0, device_ops=[], idle_gaps=[],
                         n_device_events=3)
    assert r.idle_share == pytest.approx(5.0 / 9.0)


def test_gaps_are_labelled_by_the_host_spans_over_them():
    host = [("outer", 0.0, 10.0), ("sample", 1.0, 2.0), ("copy", 6.0, 6.5)]
    labels = xtrace._label_gaps(host, [(1.2, 1.6), (6.1, 6.2), (8.0, 9.0)])
    assert labels == ["outer > sample", "outer > copy", "outer"]
    assert xtrace._label_gaps([], [(0.0, 1.0)]) == ["no host span"]


def test_synthesized_trace_reduces_to_the_hand_worked_numbers():
    """`data/make_synthetic_xplane.py` lays out the times it writes."""
    r = xtrace.reduce(FIXTURE, 1)
    assert r.window_s == pytest.approx(0.010)
    assert r.busy_s == pytest.approx(0.005)
    assert r.idle_share == pytest.approx(0.5)
    assert r.n_device_events == 6
    assert dict(r.device_ops) == pytest.approx(
        {"fusion.1": 0.0025, "scatter.3": 0.0020, "fusion.2": 0.0013})
    assert dict(r.idle_gaps) == pytest.approx(
        {"host.wait": 0.0035, "no host span": 0.0015})
    with pytest.raises(RuntimeError, match="device planes"):
        xtrace.reduce(FIXTURE, 2)



def test_an_op_is_named_by_its_hlo_instruction_name():
    text = ("%dynamic-update-slice.69 = f32[1,10,20857228]{2,1,0:T(8,128)} "
            "dynamic-update-slice(f32[1,10,20857228] %get-tuple-element.478)")
    assert xtrace.op_name(text) == "%dynamic-update-slice.69"
    assert xtrace.op_name("fusion.1") == "fusion.1"
