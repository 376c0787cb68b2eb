"""Share of the traced window in which no operation ran on the chip,
averaged over the chips used: 100 * (1 - busy / window)."""


def read(run):
    s = run.trace
    if s is None or s.window_s <= 0:
        return None
    busy = sum(s.busy_s) / len(s.busy_s)
    return 100.0 * (1.0 - busy / s.window_s)
