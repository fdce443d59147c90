"""The trace reduction, on a small trace recorded on a TPU v5e chip
(``chipbench/tools/record_trace.py``) and on intervals made by hand."""
from pathlib import Path

import pytest

from chipbench import trace_reduce as tr
from chipbench.trace_reduce import Interval

TINY = Path(__file__).resolve().parents[1] / "testdata" / "tiny.xplane.pb"


@pytest.fixture(scope="module")
def tiny():
    return tr.read_xplane(str(TINY))


def test_union_merges_overlaps_and_gaps_fill_the_rest():
    ops = [Interval("a", 0, 10), Interval("b", 5, 12), Interval("c", 20, 25),
           Interval("d", 21, 22)]
    assert tr.union(ops) == [(0, 12), (20, 25)]
    assert tr.busy_ns(ops, 0, 30) == 17            # not the sum, 20
    assert tr.busy_ns(ops, 6, 21) == 7             # clipped to the window
    assert tr.gaps(ops, -5, 30) == [(-5, 0), (12, 20), (25, 30)]


def test_gap_goes_to_the_span_that_overlaps_it_most():
    spans = [Interval("chipbench.a", 0, 4), Interval("chipbench.b", 3, 10)]
    got = tr.attribute([(2, 9), (20, 21)], spans)
    assert got == {"chipbench.b": 7, tr.NO_SPAN: 1}


def test_program_of_uses_the_module_holding_the_op():
    mods = [Interval("m1", 0, 10), Interval("m2", 20, 30)]
    ops = [Interval("x", 1, 2), Interval("y", 21, 29), Interval("z", 12, 13)]
    assert tr.program_of(ops, mods) == ["m1", "m2", None]


def test_chip_trace_has_the_device_and_the_spans(tiny):
    assert list(tiny.devices) == ["/device:TPU:0"]
    names = {s.name for s in tiny.spans}
    assert {"chipbench.window", "chipbench.f", "chipbench.g",
            "chipbench.sleep"} <= names
    dev = tiny.devices["/device:TPU:0"]
    assert len(dev.modules) == 6 and len(dev.ops) >= 6


def test_chip_trace_busy_is_the_union_of_ops(tiny):
    s = tr.summarize(tiny)
    win = tr.window_of(tiny)
    ops = tr.clip(tiny.devices["/device:TPU:0"].ops, win.start_ns,
                  win.end_ns)
    # ops of one chip run one after another here: union == sum
    by_sweep = 0.0
    end = float("-inf")
    for o in sorted(ops, key=lambda o: o.start_ns):
        by_sweep += max(0.0, o.end_ns - max(o.start_ns, end))
        end = max(end, o.end_ns)
    assert s["busy_s"] == pytest.approx(by_sweep * 1e-9)
    assert 0 < s["busy_s"] < s["window_s"]
    assert s["window_s"] == pytest.approx(win.dur_ns * 1e-9)


def test_chip_trace_time_per_program(tiny):
    s = tr.summarize(tiny)
    dev = tiny.devices["/device:TPU:0"]
    win = tr.window_of(tiny)
    want = {}
    for m in dev.modules:
        inside = [o for o in dev.ops      # timestamps round to the ns
                  if m.start_ns - 10 <= o.start_ns
                  and o.end_ns <= m.end_ns + 10]
        if win.start_ns <= m.start_ns < win.end_ns:
            want[m.name] = want.get(m.name, 0.0) + sum(
                o.dur_ns for o in inside) * 1e-9
    assert s["program_s"] == pytest.approx(want)
    assert sorted(s["program_calls"].values()) == [3, 3]
    assert sum(s["op_s"].values()) == pytest.approx(s["busy_s"])


def test_chip_trace_idle_goes_to_the_sleeps(tiny):
    s = tr.summarize(tiny)
    idle = s["idle_by_span_s"]
    assert sum(idle.values()) == pytest.approx(s["window_s"] - s["busy_s"])
    assert idle["chipbench.sleep"] > 0.9 * sum(idle.values())
    b = tr.breakdown(s)
    assert b["idle_gaps"][0][0] == "chipbench.sleep"
    assert len(b["device_ops"]) <= 10
