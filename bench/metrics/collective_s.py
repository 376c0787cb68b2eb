"""Device seconds per fit of the all-reduce operations (the per-block psum
of counts across chips), on the slowest chip."""


def read(run):
    s = run.trace
    if s is None or s.fits < 1 or s.chips < 2 or max(s.collective_s) <= 0:
        return None
    return max(s.collective_s) / s.fits
