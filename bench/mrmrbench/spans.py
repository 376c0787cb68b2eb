"""Seconds per fit of the program's own spans (``mrmr.*``, named in
``repro/runtime/tracing.py``), read from the trace of a traced run.

The run's ``.xplane.pb`` is the one file under ``bench/traces/``: the
harness empties that directory before each traced run, and per-layer
metrics are read only in traced runs.  The file is loaded once for all the
span metrics of a run.  A span's time is clipped to the ``bench.window``
annotation, summed over every host thread, and divided by the fits the
window holds.  A trace with no ``mrmr.fit`` span, from a program that
opens none, gives nothing (None); a span that never opened in a fit that
has spans reads 0.0.
"""

from __future__ import annotations

import functools
import glob
import pathlib

from mrmrbench import manifest, trace

TRACE_DIR = manifest.BENCH / "traces"
FIT = "mrmr.fit"


def trace_file(trace_dir: pathlib.Path) -> pathlib.Path | None:
    """The one trace file under ``trace_dir``, or None."""
    found = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    return pathlib.Path(found[0]) if len(found) == 1 else None


@functools.lru_cache(maxsize=1)
def _load(path: str, mtime_ns: int) -> list:
    del mtime_ns  # part of the key: a rewritten file is loaded again
    return trace.load(path)


def seconds_per_fit(planes, names) -> float | None:
    """Seconds per fit of the spans named ``names``, over all host
    threads, inside the window; None where no ``mrmr.fit`` span is there."""
    hosts = trace.host_events(planes)
    if not any(e.name == FIT for e in hosts):
        return None
    lo, hi = trace.window(planes)
    fits = trace.fits(planes, (lo, hi))
    if fits < 1:
        return None
    names = set(names)
    total = sum(
        max(0.0, min(e.end, hi) - max(e.start, lo))
        for e in hosts
        if e.name in names
    )
    return total * 1e-9 / fits


def read(*names) -> float | None:
    """Seconds per fit of the named spans in this run's trace."""
    path = trace_file(TRACE_DIR)
    if path is None:
        return None
    return seconds_per_fit(_load(str(path), path.stat().st_mtime_ns), names)
