"""Seconds per fit in which the fit's thread waits on the staging or
read-ahead thread (``mrmr.feed_wait``)."""

from mrmrbench import spans


def read(run):
    return spans.read("mrmr.feed_wait")
