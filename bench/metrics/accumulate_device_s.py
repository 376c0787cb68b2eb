"""Device seconds per fit of the accumulate programs (the count kernel and
its block handling) that ``AccumulateLog`` names, on the slowest chip."""


def read(run):
    s = run.trace
    if s is None or s.fits < 1 or max(s.accumulate_s) <= 0:
        return None
    return max(s.accumulate_s) / s.fits
