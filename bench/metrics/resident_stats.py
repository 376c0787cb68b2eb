"""Whether one fit sized its default MI score from the blocks it keeps on
the device (``MRMRResult.io["resident_stats"]``): 1, or 0 where it scanned
the source for the category counts, found them memoised, or was given its
score.  None for a program without the counter."""


def read(run):
    if not run.io or "resident_stats" not in run.io:
        return None
    return float(run.io["resident_stats"])
