"""Share of the window spent in ``LMServer.step`` calls that admitted
requests (prefill and splice of new prompts, then that step's decode)."""


def read(r):
    c = r.counters
    if not c.get("steps"):
        return None
    admit = sum(s["t1"] - s["t0"] for s in c["steps"] if s["kind"] == "admit")
    return 100.0 * admit / c["window_s"]
