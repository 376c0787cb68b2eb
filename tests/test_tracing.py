"""The streamed fit's own spans and counters: a small fit traced with the
JAX profiler and read back from its ``.xplane.pb``, and the host round-trip
counters on ``MRMRResult.io`` pinned from the shapes."""

import collections
import glob

import jax
import numpy as np
import pytest

from repro import MRMRSelector
from repro.data.sources import ArraySource, clear_stats_memo
from repro.runtime import tracing

ROWS, COLS, BLOCK, SELECT = 1000, 12, 256, 10
BLOCKS = -(-ROWS // BLOCK)  # 4 a pass, the last padded from 232 rows

# (prefetch, readahead): synchronous, staging thread, cross-pass reader
MODES = {"sync": (0, 0), "prefetch2": (2, 0), "readahead2": (0, 2)}


@pytest.fixture(autouse=True)
def first_fits():
    """Each test's first fit is the first of its data: no memoised stats,
    so the front door leaves the default score to the engine."""
    clear_stats_memo()


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(3)
    X = rng.integers(0, 2, size=(ROWS, COLS)).astype(np.int8)
    y = ((X[:, 0] + X[:, 1] + X[:, 2]) >= 2).astype(np.int8)
    flip = rng.random(ROWS) < 0.1
    y[flip] = 1 - y[flip]
    return X, y


def _fit(data, criterion="mid", mode="sync"):
    prefetch, readahead = MODES[mode]
    return MRMRSelector(
        num_select=SELECT, criterion=criterion, block_obs=BLOCK,
        prefetch=prefetch, readahead=readahead,
    ).fit(ArraySource(*data))


Span = collections.namedtuple("Span", "name start end args thread")


# The host event of one dispatch of the device reduce that sizes a score
SIZING = "PjitFunction(_widen_extrema)"


def _traced(tmp_path, fit, also=()):
    """Run ``fit()`` under the profiler -> (its result, the mrmr.* spans
    and the host events named in ``also``)."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        out = fit()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = []
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for thread, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("mrmr.") or e.name in also:
                    spans.append(Span(
                        e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats), (plane.name, thread),
                    ))
    return out, spans


def _by_name(spans):
    out = collections.defaultdict(list)
    for s in spans:
        out[s.name].append(s)
    return out


def _inside(inner, outer):
    return outer.start <= inner.start and inner.end <= outer.end


@pytest.mark.parametrize("criterion", ["mid", "jmi"])
@pytest.mark.parametrize("mode", sorted(MODES))
def test_spans_of_a_streamed_fit(tmp_path, data, criterion, mode):
    sel, spans = _traced(tmp_path, lambda: _fit(data, criterion, mode))
    io = sel.result_.io
    by = _by_name(spans)
    expected = set(tracing.SPANS) - {tracing.CUT}  # cut in resident passes
    if mode == "sync":
        expected.discard(tracing.FEED_WAIT)  # nothing to wait on
    assert set(by) == expected

    (fit,) = by[tracing.FIT]
    fid = fit.args["fit"]
    assert all(s.args["fit"] == fid for s in spans)
    # the front door's plan, then the engine's: the front door left the
    # default score to the engine, and a fit that streams scans for it
    plan, scan = sorted(by[tracing.PLAN], key=lambda s: s.start)
    assert _inside(plan, fit) and _inside(scan, fit)
    assert plan.end <= scan.start
    assert io["resident_stats"] == 0

    passes = by[tracing.PASS]
    assert len(passes) == io["passes"] == SELECT
    assert sorted(p.args["pass"] for p in passes) == list(range(SELECT))
    kinds = {p.args["pass"]: p.args["kind"] for p in passes}
    cond = "feature_cond" if criterion == "jmi" else "feature"
    assert kinds == {p: "class" if p == 0 else cond for p in range(SELECT)}
    assert all(p.args["resident"] == 0 for p in passes)
    assert all(_inside(p, fit) for p in passes)
    pass_of = {p.args["pass"]: p for p in passes}
    assert scan.end <= pass_of[0].start

    blocks = {(p, b) for p in range(SELECT) for b in range(BLOCKS)}
    for name in (tracing.READ, tracing.STAGE, tracing.PLACE,
                 tracing.ACCUMULATE):
        assert len(by[name]) == io["blocks_read"] == len(blocks), name
        got = {(s.args["pass"], s.args["block"]) for s in by[name]}
        assert got == blocks, name
    for s in by[tracing.PLACE] + by[tracing.ACCUMULATE]:
        assert _inside(s, pass_of[s.args["pass"]])
    assert all(
        _inside(f, pass_of[f.args["pass"]]) for f in by[tracing.FINALIZE]
    )
    assert len(by[tracing.FINALIZE]) == SELECT

    picks = by[tracing.PICK]
    assert sorted(p.args["pick"] for p in picks) == list(range(SELECT))
    for pick in picks:
        assert _inside(pick, fit)
        # a pick leaves out the pass it calls
        assert not any(
            _inside(p, pick) or _inside(pick, p) for p in passes
        )

    main = fit.thread
    readers = {s.thread for s in by[tracing.READ]}
    if mode == "sync":
        assert readers == {main}
    else:
        # reads run on the staging or read-ahead thread, once each
        assert main not in readers and len(readers) == 1
        assert all(s.thread == main for s in by[tracing.FEED_WAIT])
    stagers = {s.thread for s in by[tracing.STAGE]}
    assert stagers == ({main} if mode != "prefetch2" else readers)


@pytest.mark.parametrize("mode", sorted(MODES))
@pytest.mark.parametrize("criterion", ["mid", "jmi"])
def test_host_round_trip_counters(data, criterion, mode):
    io = _fit(data, criterion, mode).result_.io
    # one device-to-host copy per finalize term and per pick's objective
    terms = 1 + (SELECT - 1) * (2 if criterion == "jmi" else 1)
    assert io["host_syncs"] == terms + SELECT
    assert (io["host_syncs"], criterion) in {(20, "mid"), (29, "jmi")}
    # every block lands padded: int8 X, its target, a bool validity mask;
    # jmi's redundancy target fuses column and class into int32 codes.
    # Then the relevance vector and each folded redundancy term, float32.
    rel_pass = BLOCKS * BLOCK * (COLS + 1 + 1)
    target = 4 if criterion == "jmi" else 1
    red_pass = BLOCKS * BLOCK * (COLS + target + 1)
    vectors = 4 * COLS * terms
    assert io["h2d_bytes"] == rel_pass + (SELECT - 1) * red_pass + vectors


def _streamed_h2d(rows, block, cols, terms, passes, red_target=1):
    """Bytes a streamed fit places: every pass places each block padded to
    ``block`` rows, the partial last one too (X, its target, a validity
    byte a row; ``red_target`` bytes a target in the redundancy passes),
    then ``terms`` float32 vectors."""
    blocks = -(-rows // block)
    per_row = (cols + 1 + 1) + (passes - 1) * (cols + red_target + 1)
    return blocks * block * per_row + 4 * cols * terms


def _streamed_read(rows, cols, passes):
    """Bytes a streamed fit reads of int8 X and y: every row, every pass."""
    return passes * rows * (cols + 1)


@pytest.mark.parametrize("criterion", ["mid", "jmi"])
def test_streamed_counters_of_a_ragged_fit(data, criterion):
    # 1000 rows in blocks of 300: three whole ones and one of 100 rows
    io = MRMRSelector(
        num_select=SELECT, criterion=criterion, block_obs=300, prefetch=0,
    ).fit(ArraySource(*data)).result_.io
    terms = 1 + (SELECT - 1) * (2 if criterion == "jmi" else 1)
    target = 4 if criterion == "jmi" else 1
    assert io["resident_passes"] == 0 and io["passes"] == SELECT
    assert io["blocks_read"] == SELECT * 4
    assert io["bytes_read"] == _streamed_read(ROWS, COLS, SELECT)
    assert io["h2d_bytes"] == _streamed_h2d(
        ROWS, 300, COLS, terms, SELECT, target
    )
    # corral_tall_10m at L = 5, mid: 152 blocks of 65,536 rows and one of
    # 38,528, five passes
    assert _streamed_h2d(10_000_000, 65_536, 1_000, 5, 5) == 50_235_330_080
    assert _streamed_read(10_000_000, 1_000, 5) == 50_050_000_000


def _resident_h2d(blocks, block, cols, terms, passes, target=1):
    """Bytes a resident fit places: one pass of blocks (X, the class
    target, a validity byte a row), ``terms`` float32 vectors, and the
    int32 id of the column each later pass cuts its target by."""
    return (
        blocks * block * (cols + target + 1) + 4 * cols * terms
        + 4 * (passes - 1)
    )


def _resident(monkeypatch):
    import repro.dist.streaming as dist

    monkeypatch.setattr(dist, "resident_budget", lambda devices: 1 << 40)


@pytest.mark.parametrize("mode", sorted(MODES))
@pytest.mark.parametrize("criterion", ["mid", "jmi"])
def test_resident_round_trip_counters(data, monkeypatch, criterion, mode):
    _resident(monkeypatch)
    io = _fit(data, criterion, mode).result_.io
    terms = 1 + (SELECT - 1) * (2 if criterion == "jmi" else 1)
    # every pass counts from the resident blocks, the relevance pass too:
    # the score was sized from them, with one more copy to the host
    assert io["resident_passes"] == SELECT
    assert io["resident_stats"] == 1
    assert io["host_syncs"] == terms + SELECT + 1
    assert (io["host_syncs"], criterion) in {(21, "mid"), (30, "jmi")}
    # the passes after the first cut their targets on the device
    assert io["h2d_bytes"] == _resident_h2d(BLOCKS, BLOCK, COLS, terms, SELECT)
    # the benchmark's geometries at L = 10: corral_tall_1m (16 blocks of
    # 65,536 x 1,000 int8) under mid and jmi, corral_wide_50k (4 blocks
    # of 2,048 x 50,000)
    assert _resident_h2d(16, 65_536, 1_000, 10, 10) == 1_050_713_188
    assert _resident_h2d(16, 65_536, 1_000, 19, 10) == 1_050_749_188
    assert _resident_h2d(4, 2_048, 50_000, 10, 10) == 411_616_420


@pytest.mark.parametrize("criterion", ["mid", "jmi"])
def test_spans_of_a_resident_fit(tmp_path, data, monkeypatch, criterion):
    _resident(monkeypatch)
    sel, spans = _traced(
        tmp_path, lambda: _fit(data, criterion), also=(SIZING,)
    )
    by = _by_name(spans)
    passes = {p.args["pass"]: p for p in by[tracing.PASS]}
    # every pass counts from the resident blocks, the relevance pass too
    assert {p: s.args["resident"] for p, s in passes.items()} == {
        p: 1 for p in range(SELECT)
    }
    # the front door's plan, then the engine's, which sizes the score on
    # the device from the placed blocks before the relevance pass counts
    (fit,) = by[tracing.FIT]
    plan, sizing = sorted(by[tracing.PLAN], key=lambda s: s.start)
    assert _inside(plan, fit) and _inside(sizing, fit)
    assert plan.end <= sizing.start and sizing.end <= passes[0].start
    assert by[SIZING] and all(_inside(s, sizing) for s in by[SIZING])
    assert all(
        s.end <= sizing.start for s in by[tracing.STAGE] + by[tracing.PLACE]
    )
    # only the first pass reads, stages and places, ahead of any count;
    # every later pass dispatches the device cut of its target; every
    # pass accumulates
    first = {(0, b) for b in range(BLOCKS)}
    later = {(p, b) for p in range(1, SELECT) for b in range(BLOCKS)}
    for name, want in [
        (tracing.READ, first), (tracing.STAGE, first),
        (tracing.PLACE, first), (tracing.CUT, later),
        (tracing.ACCUMULATE, first | later),
    ]:
        got = [(s.args["pass"], s.args["block"]) for s in by[name]]
        assert sorted(got) == sorted(want), name
    for s in by[tracing.CUT] + by[tracing.ACCUMULATE]:
        assert _inside(s, passes[s.args["pass"]])
    assert sel.result_.io["resident_passes"] == SELECT


@pytest.mark.parametrize("criterion", ["mid", "jmi"])
def test_tracing_leaves_the_selection_unchanged(tmp_path, data, criterion):
    plain = _fit(data, criterion)
    for mode in sorted(MODES):
        traced, _ = _traced(
            tmp_path / mode, lambda: _fit(data, criterion, mode)
        )
        np.testing.assert_array_equal(traced.selected_, plain.selected_)
        np.testing.assert_array_equal(traced.gains_, plain.gains_)
        np.testing.assert_array_equal(traced.scores_, plain.scores_)
    ref = MRMRSelector(
        num_select=SELECT, criterion=criterion, encoding="reference"
    ).fit(*data)
    np.testing.assert_array_equal(plain.selected_, ref.selected_)


def test_fits_take_fresh_ids(tmp_path, data):
    def two():
        return _fit(data), _fit(data)

    _, spans = _traced(tmp_path, two)
    fits = [s.args["fit"] for s in _by_name(spans)[tracing.FIT]]
    assert len(fits) == 2 and fits[0] != fits[1]
    for fid in fits:
        mine = [s for s in spans if s.args["fit"] == fid]
        assert len([s for s in mine if s.name == tracing.PASS]) == SELECT


def test_traced_reads_span_only_the_blocks_read(tmp_path):
    blocks = [(np.zeros((3, 2)), np.zeros(3)), (np.zeros((1, 2)), np.zeros(1))]

    def run():
        return list(tracing.traced_reads(iter(blocks), 4, fit=7))

    out, spans = _traced(tmp_path, run)
    assert len(out) == 2
    assert [s.args["block"] for s in spans] == [0, 1]
    assert {s.name for s in spans} == {tracing.READ}
    # a source yielding past its stated rows still yields every block
    assert len(list(tracing.traced_reads(iter(blocks), 3))) == 2
