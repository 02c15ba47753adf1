"""device_idle_pct.sweep: the share of the traced window in which no
operation runs on a chip, averaged over the cell's four chips (profiler
trace, the union of the "XLA Ops" intervals)."""


def read(t):
    if not t.devices or t.seconds <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s() / t.seconds)
