"""Host CPU time of the whole process (all threads, ``process_time``) per
scan over the window's untraced ``pipe.run`` calls: the stream executor's
packing, placement and readback on the host.  Reads every
``host_cpu_ms_per_scan.<cell kind>`` metric."""


def read(r):
    groups = [g for g in r.counters.get("groups", ()) if not g["traced"]]
    scans = sum(g["n"] for g in groups)
    if not scans:
        return None
    return 1e3 * sum(g["cpu_s"] for g in groups) / scans
