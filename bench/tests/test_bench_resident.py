"""The reader of ``resident_passes``: the passes of one fit counted from
device-resident blocks, and nothing where the program has no such
counter."""

import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from mrmrbench import manifest  # noqa: E402


class Run:
    def __init__(self, io):
        self.io, self.trace = io, None


def test_resident_passes_reads_io():
    read = manifest.reader("resident_passes")
    assert read(Run({"resident_passes": 9, "passes": 10})) == 9.0
    assert read(Run({"resident_passes": 0, "passes": 10})) == 0.0


def test_resident_passes_without_the_counter_reads_nothing():
    read = manifest.reader("resident_passes")
    assert read(Run({"passes": 10, "h2d_bytes": 1})) is None
    assert read(Run(None)) is None
    assert read(Run({})) is None


def test_resident_passes_is_a_declared_metric():
    (m,) = [
        m for m in manifest.load()["per_layer"] if m["name"] == "resident_passes"
    ]
    assert (m["unit"], m["better"], m["source"], m["moves"]) == (
        "count", "higher", "program_counter", "fit_s"
    )
    assert "workloads" not in m
