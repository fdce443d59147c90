"""The held experts' grouped matmuls' share of their roofline, per call
of the decode program: their least time (``costs_moe.held_experts_step``:
the held expert weights read once, each held (token, expert) pair's row
in and out, 6 d f FLOPs a pair; the pairs a decode step computes from the
window's routing counters and the rows of the traced decode steps) over
the device time of the decode program's ``ragged-dot`` ops per call."""
from chipbench import costs, costs_moe


def read(r):
    c = r.counters
    held = costs_moe.held_per_row(c)
    ops = c.get("decode_program")
    steps = [s for s in c.get("steps", ())
             if s["traced"] and s["kind"] != "admit"]
    if held is None or not ops or not ops.get("grouped_s") or not steps:
        return None
    layers = costs_moe.moe_layers(r.config)
    least = sum(costs_moe.held_experts_step(r.config,
                                            s["rows"] * held * layers)
                .least_time_s(r.peaks) for s in steps) / len(steps)
    return costs.share_pct(least, ops["grouped_s"] / ops["decode_calls"])
