"""Host cell prep: mean over the traced sweeps of the time from the
``run_sweep`` call to the sweep's first device operation, in ms."""


def read(t):
    if not t.phases:
        return None
    return 1e3 * sum(p.prep_s for p in t.phases) / len(t.phases)
