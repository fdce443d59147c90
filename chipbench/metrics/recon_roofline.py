"""The reconstruction's share of its roofline: the least time of the
scans reconstructed while traced (``costs.mri_recon_scan``) over the
device time of every op that ran while traced, summed over chips.  The
traced stretch holds whole ``pipe.run`` calls, so every op in it is work
of those scans: the recon program and the per-scan slicing of its
output.  Reads every ``recon_roofline.<cell kind>`` metric."""
from chipbench import costs


def read(r):
    scans = sum(g["n"] for g in r.counters.get("groups", ()) if g["traced"])
    if r.trace is None or not scans:
        return None
    c = r.config
    least = costs.mri_recon_scan(c["frames"], c["coils"], c["height"],
                                 c["width"]).least_time_s(r.peaks)
    return costs.share_pct(least * scans, sum(r.trace["op_s"].values()))
