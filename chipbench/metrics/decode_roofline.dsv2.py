"""The DeepSeek-V2 decode step's share of its roofline: the mean least
time of the traced decode steps (``costs_moe.decode_step``: every weight
held here read once, each row's live latent cache read and its new entry
written, at the position each step decoded, the held experts' pairs from
the window's routing counters) over the device time of one execution of
the decode program, every op of it.  The decode program is the one whose
name holds ``DecodeStep``, else the one with the most device time while
traced.  Steps that admitted are left out: they also prefill."""
from chipbench import costs, costs_moe


def read(r):
    c = r.counters
    held = costs_moe.held_per_row(c)
    steps = [s for s in c.get("steps", ())
             if s["traced"] and s["kind"] != "admit"]
    t = r.trace
    if t is None or held is None or not steps or not t["program_s"]:
        return None
    named = [p for p in t["program_s"] if "DecodeStep" in p]
    prog = max(named or t["program_s"], key=t["program_s"].get)
    calls = t["program_calls"].get(prog, 0)
    if not calls:
        return None
    least = sum(costs_moe.decode_step(r.config, s["rows"], s["pos"], held)
                .least_time_s(r.peaks) for s in steps) / len(steps)
    return costs.share_pct(least, t["program_s"][prog] / calls)
