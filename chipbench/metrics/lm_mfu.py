"""Model FLOPs utilization of the whole window: 2 N FLOPs for every
prompt token prefilled and every row decoded in the window, over the
window times the chip's bf16 peak."""
from chipbench import costs


def read(r):
    c = r.counters
    if not c.get("steps"):
        return None
    tokens = c["prompt_tokens"] + sum(s["rows"] for s in c["steps"])
    return costs.share_pct(
        costs.lm_token_flops(r.config) * tokens / r.peaks.flops,
        c["window_s"] * r.chips)
