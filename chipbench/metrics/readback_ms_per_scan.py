"""Host time a scan spent getting its image back: the self time of the
program's ``data.to_host`` spans (the wait for the device, the copy to
the host and the unpacking), over the window's untraced ``pipe.run``
calls.  Reads every ``readback_ms_per_scan.<cell kind>`` metric."""
from chipbench.metrics.pack_ms_per_scan import self_ms_per_scan


def read(r):
    return self_ms_per_scan(r, ("data.to_host",))
