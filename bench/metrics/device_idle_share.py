"""Device: the share of the traced window in which no operation ran on a
chip, averaged over the chips (1 - busy / window)."""


def read(t):
    if t.window_s <= 0 or t.busy_s <= 0:
        return None
    return 1.0 - t.busy_s / t.window_s
