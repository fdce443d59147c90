"""XLA compiles inside the window: the ``compile`` spans the program
records beneath the root spans of the window's calls into it, traced calls
included.  Reads every ``window_compiles.<cell kind>`` metric.

It also holds the pairing the other readers of the program's spans share
(:func:`window_calls`): the driver's records of its calls, in order, with
the program's root spans of the same calls (``repro.core.trace``)."""

#: the program's root span of one call the driver records, by record list
ROOT = {"groups": "pipeline.run", "steps": "lm.step"}
#: a root and the record it is paired with may differ in duration by no
#: more than the larger of these
SLACK_S, SLACK_SHARE = 2e-3, 0.05


def window_calls(r):
    """``[(record, call)]``: the driver's records paired in order with the
    last as many root spans the program recorded, or None where the
    program records none (it lacks the recorder), too few, or a pair
    whose durations disagree."""
    key = "groups" if "groups" in r.counters else "steps"
    records = r.counters.get(key) or []
    try:
        from repro.core import trace
    except ImportError:
        return None
    calls = trace.calls(ROOT[key])
    if not records or len(calls) < len(records):
        return None
    pairs = list(zip(records, calls[len(calls) - len(records):]))
    for rec, call in pairs:
        took = rec["t1"] - rec["t0"]
        if abs(took - call.duration_s) > max(SLACK_S, SLACK_SHARE * took):
            return None
    return pairs


def read(r):
    pairs = window_calls(r)
    if pairs is None:
        return None
    return sum(call.counts.get("compile", 0) for _, call in pairs)
