"""Passes of one fit counted from device-resident blocks
(``MRMRResult.io["resident_passes"]``): every pass after the first where
the placed dataset fits the device budget, 0 where the fit streams."""


def read(run):
    if not run.io or "resident_passes" not in run.io:
        return None
    return float(run.io["resident_passes"])
