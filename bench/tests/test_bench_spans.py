"""The readers of the program's spans and counters: seconds per fit from a
trace written by hand and from one recorded on the CPU, and the per-layer
metrics that read them."""

import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from mrmrbench import manifest, spans, trace  # noqa: E402
from mrmrbench.trace import Event, Plane  # noqa: E402

SPAN_METRICS = {
    "plan_s": ["mrmr.plan"],
    "read_s": ["mrmr.read"],
    "feed_wait_s": ["mrmr.feed_wait"],
    "place_s": ["mrmr.stage", "mrmr.place"],
    "dispatch_s": ["mrmr.accumulate"],
    "finalize_s": ["mrmr.finalize", "mrmr.pick"],
}
COUNTER_METRICS = {"host_syncs": "host_syncs", "h2d_bytes": "h2d_bytes"}


def ev(name, start, end):
    return Event(name, float(start), float(end), {})


def hand_trace():
    """Window 100..1100 ns holding two fits; the fit thread and a staging
    thread each open spans, some of them across the window's edges."""
    main = [
        ev(trace.WINDOW, 100, 1100),
        ev(trace.FIT, 100, 600),
        ev(trace.FIT, 600, 1100),
        ev("mrmr.fit", 110, 590),
        ev("mrmr.fit", 610, 1090),
        ev("mrmr.plan", 50, 150),  # clipped to 100..150
        ev("mrmr.plan", 610, 660),
        ev("mrmr.place", 200, 230),
        ev("mrmr.place", 700, 730),
        ev("mrmr.pick", 1050, 1200),  # clipped to 1050..1100
    ]
    staging = [
        ev("mrmr.read", 160, 260),
        ev("mrmr.read", 660, 760),
        ev("mrmr.stage", 260, 300),
        ev("mrmr.read", 1080, 1150),  # clipped to 1080..1100
    ]
    return [
        Plane("/host:CPU", {"python": main, "block-prefetch": staging}),
        Plane("/device:TPU:0", {trace.OPS_LINE: [ev("fusion", 0, 2000)]}),
    ]


def test_seconds_per_fit_clip_and_sum_over_threads():
    planes = hand_trace()
    # (50 + 50) ns of plan over two fits
    assert spans.seconds_per_fit(planes, ["mrmr.plan"]) == pytest.approx(50e-9)
    # reads on the staging thread: 100 + 100 + 20
    assert spans.seconds_per_fit(planes, ["mrmr.read"]) == pytest.approx(110e-9)
    # stage on one thread and place on the other: 40 + 30 + 30
    assert spans.seconds_per_fit(
        planes, ["mrmr.stage", "mrmr.place"]
    ) == pytest.approx(50e-9)
    assert spans.seconds_per_fit(planes, ["mrmr.pick"]) == pytest.approx(25e-9)


def test_a_span_that_never_opened_reads_zero():
    assert spans.seconds_per_fit(hand_trace(), ["mrmr.feed_wait"]) == 0.0


def test_no_fit_span_gives_nothing():
    planes = hand_trace()
    main = planes[0].lines["python"]
    planes[0].lines["python"] = [e for e in main if e.name != "mrmr.fit"]
    assert spans.seconds_per_fit(planes, ["mrmr.plan"]) is None


def test_trace_file_is_the_one_file(tmp_path):
    assert spans.trace_file(tmp_path) is None
    (tmp_path / "a" / "b").mkdir(parents=True)
    one = tmp_path / "a" / "b" / "host.xplane.pb"
    one.write_bytes(b"")
    assert spans.trace_file(tmp_path) == one
    (tmp_path / "a" / "other.xplane.pb").write_bytes(b"")
    assert spans.trace_file(tmp_path) is None


class Run:
    def __init__(self, io):
        self.io, self.trace = io, None


@pytest.mark.parametrize("name", sorted(COUNTER_METRICS))
def test_counter_metrics_read_io(name):
    read = manifest.reader(name)
    assert read(Run({COUNTER_METRICS[name]: 29, "passes": 10})) == 29.0
    assert read(Run({"passes": 10})) is None
    assert read(Run(None)) is None


@pytest.fixture
def recorded(tmp_path, monkeypatch):
    """A window of two fits traced on the CPU, each fit a front-door fit
    of a small source: the files a traced run leaves under
    ``bench/traces``."""
    import jax
    import numpy as np

    from repro import MRMRSelector
    from repro.data.sources import ArraySource

    rng = np.random.default_rng(0)
    X = rng.integers(0, 2, size=(600, 8)).astype(np.int8)
    y = X[:, 0].copy()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            for _ in range(2):
                with jax.profiler.TraceAnnotation(trace.FIT):
                    MRMRSelector(num_select=3, block_obs=256, prefetch=2).fit(
                        ArraySource(X, y)
                    )
    finally:
        jax.profiler.stop_trace()
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path)
    spans._load.cache_clear()
    yield tmp_path
    spans._load.cache_clear()


@pytest.mark.parametrize("name", sorted(SPAN_METRICS))
def test_span_metrics_read_a_recorded_trace(recorded, name):
    value = manifest.reader(name)(Run({}))
    assert value is not None and value > 0.0
    planes = trace.load(spans.trace_file(recorded))
    win = trace.window(planes)
    fit = sum(
        e.end - e.start for e in trace.host_events(planes)
        if e.name == "mrmr.fit"
    ) * 1e-9 / trace.fits(planes, win)
    assert value < fit


def test_span_metrics_without_a_trace_give_nothing(tmp_path, monkeypatch):
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path)
    for name in SPAN_METRICS:
        assert manifest.reader(name)(Run({})) is None


def test_span_metrics_name_their_spans():
    for name, names in SPAN_METRICS.items():
        text = manifest.metric_path(name).read_text()
        assert all(f'"{n}"' in text for n in names), name
