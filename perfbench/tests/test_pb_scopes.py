"""The scope and host-span reduction (`perfbench/xscopes.py`): the helpers
on synthesized intervals, and the whole reduction on a real v5e trace
(`data/v5e.xplane.pb`, written by `data/make_v5e_xplane.py`) and on the
synthesized one, with times worked by hand from the events."""
import pathlib

import pytest

from perfbench import xscopes, xtrace

DATA = pathlib.Path(__file__).with_name("data")
V5E = DATA / "v5e.xplane.pb"
SYNTHETIC = DATA / "synthetic.xplane.pb"


def test_a_path_splits_into_components_without_its_op_type():
    assert xscopes.components("jit(step)/fixture.scope/jit(sort)/sort:") == {
        "jit(step)", "fixture.scope", "jit(sort)", "sort"}
    assert xscopes.components("jit(f)/while/body/dmf.p_scatter/scatter-add") \
        == {"jit(f)", "while", "body", "dmf.p_scatter", "scatter-add"}
    assert xscopes.components("P") == {"P"}


def test_scopes_are_unions_clipped_to_the_window():
    ops = [
        (0.0, 2.0, "jit(f)/a/x:"),        # clipped to 1-2
        (1.5, 3.0, "jit(f)/a/b/y:"),      # a overlaps itself: a = 1-3
        (2.5, 4.0, "jit(f)/b/z:"),        # b = 1.5-4
        (5.0, 6.0, None),                 # an op with no path
        (5.5, 7.0, "jit(f)/a/x:"),        # overlaps the pathless op
        (9.0, 12.0, "jit(f)/b/z:"),       # clipped to 9-10
        (11.0, 12.0, "jit(f)/c:"),        # outside the window
    ]
    got = xscopes.scope_time(ops, 1.0, 10.0)
    assert got == pytest.approx({
        "jit(f)": 2.0 + 1.0 + 1.5 + 1.0,  # 1-4, (5-6 has no path) 5.5-7, 9-10
        "a": 2.0 + 1.5,                   # 1-3, 5.5-7
        "b": 2.5 + 1.0,                   # 1.5-4, 9-10
        "x": 1.0 + 1.5, "y": 1.5, "z": 1.5 + 1.0,
        xscopes.NO_PATH: 1.0,
    })
    assert "c" not in got


def test_host_spans_are_the_durations_inside_the_window():
    events = [(xtrace.WINDOW, 1.0, 10.0), ("fit.epoch", 1.5, 4.0),
              ("fit.sample", 1.5, 1.75), ("fit.epoch", 4.0, 9.0),
              ("fit.sample", 4.0, 4.5), ("fit.epoch", 9.0, 11.0),
              ("fit.init", 0.5, 1.5)]
    assert xscopes.span_times(events, 1.0, 10.0) == {
        "fit.epoch": [2.5, 5.0], "fit.sample": [0.25, 0.5]}


def test_the_v5e_trace_carries_the_scope_in_tf_op():
    """The stat holds the `op_name` path of both sorts and of the copy
    that ends the second; the compiler's other copies and iotas carry
    none."""
    planes = xscopes.decode_planes(V5E.read_bytes())
    (dev,) = xtrace._device_planes(planes, 1)
    assert dev.name == "/device:TPU:0"
    assert set(dev.paths.values()) == {
        "jit(step)/fixture.scope/jit(sort)/sort:", "jit(step)/jit(sort)/sort:"}
    ops = [ev for ln in dev.lines if ln.name in xtrace.OP_LINES
           for ev in ln.events]
    assert len(ops) == 24
    assert sum(mid in dev.paths for mid, _, _ in ops) == 9


def test_v5e_trace_reduces_to_the_hand_worked_numbers():
    r = xscopes.read(V5E, 1)
    ns = 1e-9
    # the three %sort.7 (53,166 + 52,787 + 52,660 ns) sorts in the scope
    assert r.device_scopes["fixture.scope"] == pytest.approx(158_613 * ns,
                                                             abs=2 * ns)
    # plus the three %sort.13 and %copy.9 outside it
    assert r.device_scopes["jit(step)"] == pytest.approx(
        (158_613 + 159_606 + 6_699) * ns, abs=4 * ns)
    # copy-start, copy-done, copy.7 and both iotas carry no op_name
    assert r.device_scopes[xscopes.NO_PATH] == pytest.approx(8_054 * ns,
                                                             abs=20 * ns)
    busy = xtrace.reduce(V5E, 1).busy_s
    assert (r.device_scopes["jit(step)"] + r.device_scopes[xscopes.NO_PATH]
            == pytest.approx(busy, abs=20 * ns))
    assert r.window_s == pytest.approx(0.04188903)
    assert len(r.host_spans["PjitFunction(step)"]) == 6
    assert xtrace.WINDOW not in r.host_spans


def test_a_trace_without_paths_puts_all_busy_time_under_no_path():
    r = xscopes.read(SYNTHETIC, 1)
    assert r.device_scopes == pytest.approx({xscopes.NO_PATH: 0.005})
    assert list(r.host_spans) == ["host.wait"]
    assert r.host_spans["host.wait"] == pytest.approx([0.002, 0.0015])
    with pytest.raises(RuntimeError, match="device planes"):
        xscopes.read(SYNTHETIC, 2)
