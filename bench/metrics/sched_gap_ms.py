"""Sweep scheduler: mean over the traced sweeps of the device's idle time
between the sweep's first and last device operation (segment round trips,
dispatch), in ms."""


def read(t):
    if not t.phases:
        return None
    return 1e3 * sum(p.gap_s for p in t.phases) / len(t.phases)
