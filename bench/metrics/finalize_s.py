"""Seconds per fit of the passes' finalize and score copies
(``mrmr.finalize``) and the greedy picks: fold, objective, its copy and the
argmax (``mrmr.pick``)."""

from mrmrbench import spans


def read(run):
    return spans.read("mrmr.finalize", "mrmr.pick")
