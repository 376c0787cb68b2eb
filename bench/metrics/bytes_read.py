"""Bytes the fit read from its source (``MRMRResult.io``), one fit."""


def read(run):
    if not run.io or "bytes_read" not in run.io:
        return None
    return float(run.io["bytes_read"])
