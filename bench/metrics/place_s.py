"""Seconds per fit of host-to-device placement: target extraction, pad
and mask (``mrmr.stage``) and the transfers (``mrmr.place``)."""

from mrmrbench import spans


def read(run):
    return spans.read("mrmr.stage", "mrmr.place")
