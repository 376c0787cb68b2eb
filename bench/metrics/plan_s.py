"""Seconds per fit of the front door before the engine starts
(``mrmr.plan``: the score and its stats scan, the plan, the mesh)."""

from mrmrbench import spans


def read(run):
    return spans.read("mrmr.plan")
