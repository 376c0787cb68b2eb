"""Seconds per fit of raw block reads from the source (``mrmr.read``),
on whichever thread reads."""

from mrmrbench import spans


def read(run):
    return spans.read("mrmr.read")
