"""The host's part of a decode step: the mean, over the window's untraced
steps that neither admitted nor released, of the program's ``lm.step``
span less the self time of its ``lm.token_readback`` span, where the host
waits for the chip."""
from chipbench.metrics.window_compiles import window_calls


def read(r):
    steps = [c for s, c in window_calls(r) or ()
             if s["kind"] == "decode" and not s["traced"]]
    if not steps:
        return None
    return 1e3 * sum(c.duration_s - c.self_s.get("lm.token_readback", 0.0)
                     for c in steps) / len(steps)
