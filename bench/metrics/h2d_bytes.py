"""Bytes of host arrays the engine placed on the device in one fit
(``MRMRResult.io["h2d_bytes"]``): every block triple and the vectors the
greedy loop folds."""


def read(run):
    if not run.io or "h2d_bytes" not in run.io:
        return None
    return float(run.io["h2d_bytes"])
