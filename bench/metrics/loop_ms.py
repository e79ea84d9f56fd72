"""Device loop: mean over the traced sweeps of the union of the sweep's
device operation intervals, in ms."""


def read(t):
    if not t.phases:
        return None
    return 1e3 * sum(p.loop_s for p in t.phases) / len(t.phases)
