"""Model FLOPs utilization of the whole window for a DeepSeek-V2 share:
2 N_active FLOPs (``costs_moe.token_flops``, the held experts a row
computes per expert layer from the window's routing counters) for every
prompt token prefilled and every row decoded in the window, over the
window times the chip's bf16 peak."""
from chipbench import costs, costs_moe


def read(r):
    c = r.counters
    held = costs_moe.held_per_row(c)
    if not c.get("steps") or held is None:
        return None
    tokens = c["prompt_tokens"] + sum(s["rows"] for s in c["steps"])
    return costs.share_pct(
        costs_moe.token_flops(r.config, held) * tokens / r.peaks.flops,
        c["window_s"] * r.chips)
