"""Host seconds per fit of dispatching the per-block accumulate
(``mrmr.accumulate``); the device runs it after the call returns."""

from mrmrbench import spans


def read(run):
    return spans.read("mrmr.accumulate")
