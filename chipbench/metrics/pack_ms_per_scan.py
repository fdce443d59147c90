"""Host time a scan spent packing items into arena words and stacking
them into batches: the self time of the program's ``stream.pack`` and
``stream.stack`` spans, over the window's untraced ``pipe.run`` calls.
Reads every ``pack_ms_per_scan.<cell kind>`` metric."""
from chipbench.metrics.window_compiles import window_calls

SPANS = ("stream.pack", "stream.stack")


def self_ms_per_scan(r, spans):
    """Milliseconds a scan of self time under ``spans``, over the untraced
    calls; None where the program's spans cannot be paired."""
    pairs = [(g, c) for g, c in window_calls(r) or () if not g["traced"]]
    scans = sum(g["n"] for g, _ in pairs)
    if not scans:
        return None
    return 1e3 * sum(c.self_s.get(s, 0.0) for _, c in pairs
                     for s in spans) / scans


def read(r):
    return self_ms_per_scan(r, SPANS)
