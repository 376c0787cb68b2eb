"""Device-to-host copies the engine made in one fit
(``MRMRResult.io["host_syncs"]``)."""


def read(run):
    if not run.io or "host_syncs" not in run.io:
        return None
    return float(run.io["host_syncs"])
