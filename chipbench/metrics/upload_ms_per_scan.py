"""Host time a scan spent dispatching its uploads: the self time of the
program's ``stream.place`` spans (one per ``device_put``), over the
window's untraced ``pipe.run`` calls.  Reads every
``upload_ms_per_scan.<cell kind>`` metric."""
from chipbench.metrics.pack_ms_per_scan import self_ms_per_scan


def read(r):
    return self_ms_per_scan(r, ("stream.place",))
