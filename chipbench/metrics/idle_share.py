"""Share of the traced window in which no op ran on the chip, the mean
over the chips used.  Reads every ``idle_share.<cell kind>`` metric."""


def read(r):
    t = r.trace
    if t is None:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
