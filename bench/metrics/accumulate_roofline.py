"""Share of the count layer's HBM roofline: the least time the chips need
to read the work a fit requires (``mrmrbench.work``, from the
configuration and the fit's pass count) at the published HBM bandwidth,
over the accumulate programs' device time per fit on the slowest chip."""

from mrmrbench import work


def read(run):
    s = run.trace
    if s is None or s.fits < 1 or max(s.accumulate_s) <= 0:
        return None
    seconds = max(s.accumulate_s) / s.fits
    need = work.count_bytes_per_fit(
        run.config["rows"], run.config["features"], run.io["passes"]
    )
    peak = work.peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * need / (run.chips * peak) / seconds
