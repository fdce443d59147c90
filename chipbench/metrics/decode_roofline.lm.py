"""The decode step's share of its roofline: the mean least time of the
traced steps (``costs.lm_decode_step``: weights read once, the live cache
read and the new entry written, at the position each step decoded) over
the device time of one execution of the decode program, every op of it.
The decode program is the program that took the most device time while
traced: the traced stretch lies inside a wave's decode steps, where it
runs once a step."""
from chipbench import costs


def read(r):
    steps = [s for s in r.counters.get("steps", ()) if s["traced"]]
    t = r.trace
    if t is None or not steps or not t["program_s"]:
        return None
    prog = max(t["program_s"], key=t["program_s"].get)
    calls = t["program_calls"].get(prog, 0)
    if not calls:
        return None
    least = sum(costs.lm_decode_step(r.config, s["rows"], s["pos"])
                .least_time_s(r.peaks) for s in steps) / len(steps)
    return costs.share_pct(least, t["program_s"][prog] / calls)
