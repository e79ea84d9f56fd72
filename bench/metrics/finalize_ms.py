"""Host finalize: mean over the traced sweeps of the time from the
sweep's last device operation to the return of ``run_sweep`` (device to
host copies, host finalizers), in ms."""


def read(t):
    if not t.phases:
        return None
    return 1e3 * sum(p.finalize_s for p in t.phases) / len(t.phases)
