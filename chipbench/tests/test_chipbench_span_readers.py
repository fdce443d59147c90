"""The readers of the program's own spans (``repro.core.trace``), on
synthetic root spans against synthetic driver records: pairing in order
with the last roots, the untraced filter, and None where the spans cannot
be paired."""
import pytest

from chipbench import harness
from chipbench.peaks import PEAKS
from chipbench.tests import tiny

MS = 1e-3


def _call(id, dur_s, self_ms=None, counts=None):
    from repro.core.trace import Call, SpanRecord
    span = SpanRecord(id, None, "root", 0, round(dur_s * 1e9), {}, {})
    return Call(span, {k: v * MS for k, v in (self_ms or {}).items()},
                counts or {})


def _group(t0, dur_s, n, traced):
    return {"t0": t0, "t1": t0 + dur_s, "n": n, "traced": traced}


def _step(t0, dur_s, kind, traced):
    return {"t0": t0, "t1": t0 + dur_s, "kind": kind, "traced": traced}


def _read(name, counters, calls, monkeypatch):
    """Reader ``name`` on the driver's ``counters``, the program's roots
    being ``calls`` (span name -> list; None: the program has no
    recorder)."""
    if calls is None:
        import repro.core
        monkeypatch.delattr(repro.core, "trace")
        monkeypatch.setitem(__import__("sys").modules, "repro.core.trace",
                            None)
    else:
        from repro.core import trace
        monkeypatch.setattr(trace, "calls", lambda span: calls[span])
    reading = harness.Reading(counters, None, {}, 1, PEAKS["TPU v5 lite"])
    return harness.load_reader(tiny.CHECKOUT, name)(reading)


#: a warm-up call, then three window calls; the second was traced
GROUPS = [_group(0.0, 0.100, 8, False), _group(0.1, 0.200, 8, True),
          _group(0.3, 0.100, 4, False)]
ROOTS = [_call(1, 0.5, {"stream.pack": 900.0}, {"compile": 7}),
         _call(2, 0.0995, {"stream.pack": 8.0, "stream.stack": 2.0,
                           "stream.place": 4.0, "data.to_host": 16.0},
               {"compile": 1}),
         _call(3, 0.2, {"stream.pack": 500.0, "stream.place": 500.0,
                        "data.to_host": 500.0}, {"compile": 2}),
         _call(4, 0.1001, {"stream.pack": 4.0, "stream.stack": 2.0,
                           "stream.place": 2.0, "data.to_host": 8.0})]


@pytest.mark.parametrize("name,want", [
    ("pack_ms_per_scan.stream", (8 + 2 + 4 + 2) / 12),
    ("upload_ms_per_scan.stream-4chip", (4 + 2) / 12),
    ("readback_ms_per_scan.stream", (16 + 8) / 12),
    ("window_compiles.stream", 1 + 2),
])
def test_stream_readers_pair_the_last_roots_in_order(monkeypatch, name,
                                                     want):
    got = _read(name, {"groups": GROUPS}, {"pipeline.run": ROOTS},
                monkeypatch)
    assert got == pytest.approx(want)


@pytest.mark.parametrize("name", ["pack_ms_per_scan.stream",
                                  "upload_ms_per_scan.stream",
                                  "readback_ms_per_scan.stream-4chip",
                                  "window_compiles.stream"])
@pytest.mark.parametrize("case", ["too_few", "mismatch", "no_recorder"])
def test_stream_readers_give_none_where_roots_do_not_pair(monkeypatch, name,
                                                          case):
    calls = {"pipeline.run": list(ROOTS)}
    if case == "too_few":
        calls["pipeline.run"] = ROOTS[2:]
    elif case == "mismatch":     # 5.5 ms off a 100 ms call: over 2 ms, 5%
        calls["pipeline.run"][3] = _call(4, 0.1 + 5.5 * MS,
                                         {"stream.pack": 4.0})
    else:
        calls = None
    assert _read(name, {"groups": GROUPS}, calls, monkeypatch) is None


def test_a_pair_within_the_slack_still_reads(monkeypatch):
    """1.9 ms off a 10 ms call is within 2 ms, though over 5%."""
    groups = [_group(0.0, 0.010, 2, False)]
    roots = [_call(1, 0.0119, {"stream.pack": 3.0})]
    assert _read("pack_ms_per_scan.stream", {"groups": groups},
                 {"pipeline.run": roots}, monkeypatch) == pytest.approx(1.5)


def test_every_call_traced_reads_none_but_compiles_still_count(monkeypatch):
    groups = [_group(0.0, 0.1, 8, True)]
    roots = [_call(1, 0.1, {"stream.pack": 1.0}, {"compile": 2})]
    counters = {"groups": groups}
    calls = {"pipeline.run": roots}
    assert _read("pack_ms_per_scan.stream", counters, calls,
                 monkeypatch) is None
    assert _read("window_compiles.stream", counters, calls,
                 monkeypatch) == 2


STEPS = [_step(0.0, 0.180, "admit", False), _step(0.18, 0.183, "decode",
                                                  False),
         _step(0.363, 0.183, "decode", True), _step(0.546, 0.190, "release",
                                                    False),
         _step(0.736, 0.184, "decode", False)]
STEP_ROOTS = [_call(9, 1.0, {}, {"compile": 3}),          # warm-up
              _call(10, 0.180, {"lm.token_readback": 100.0}),
              _call(11, 0.183, {"lm.token_readback": 180.0}),
              _call(12, 0.183, {"lm.token_readback": 10.0}, {"compile": 1}),
              _call(13, 0.190, {"lm.token_readback": 150.0}),
              _call(14, 0.184, {"lm.token_readback": 181.0})]


def test_decode_host_ms_reads_untraced_decode_only_steps(monkeypatch):
    got = _read("decode_host_ms.lm", {"steps": STEPS},
                {"lm.step": STEP_ROOTS}, monkeypatch)
    assert got == pytest.approx(((183 - 180) + (184 - 181)) / 2)
    assert _read("window_compiles.lm", {"steps": STEPS},
                 {"lm.step": STEP_ROOTS}, monkeypatch) == 1


@pytest.mark.parametrize("roots", [STEP_ROOTS[3:], STEP_ROOTS[:-1]],
                         ids=["too_few", "misaligned"])
def test_decode_host_ms_gives_none_where_steps_do_not_pair(monkeypatch,
                                                           roots):
    assert _read("decode_host_ms.lm", {"steps": STEPS},
                 {"lm.step": roots}, monkeypatch) is None
