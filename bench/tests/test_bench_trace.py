"""The reduction from a profiler trace to per-layer numbers, on a trace
written by hand and on a small trace recorded on a TPU v5e."""

import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from mrmrbench import trace  # noqa: E402
from mrmrbench.trace import Event, Plane  # noqa: E402

RECORDED = BENCH / "tests" / "data" / "tiny_v5e.xplane.pb"


def ev(name, start, end, **stats):
    return Event(name, float(start), float(end), stats)


def hand_trace():
    """Window 100..1100 ns holding two fits; two chips."""
    host = Plane("/host:CPU", {
        "python": [
            ev(trace.WINDOW, 100, 1100),
            ev(trace.FIT, 100, 600),
            ev(trace.FIT, 600, 1100),
            ev("read", 150, 300),
            ev("put", 700, 900),
        ],
    })
    tpu0 = Plane("/device:TPU:0", {
        trace.OPS_LINE: [
            ev("fusion.1", 50, 200),  # clipped to 100..200
            ev("custom-call.2", 180, 250),  # overlaps the fusion
            ev("all-reduce.3", 400, 450, hlo_category="all-reduce"),
            ev("fusion.1", 1000, 1200),  # clipped to 1000..1100
        ],
        trace.MODULES_LINE: [
            ev("jit_accumulate(1)", 100, 250),
            ev("jit_accumulate(2)", 400, 450),
            ev("jit_finalize(3)", 1000, 1100),
        ],
    })
    tpu1 = Plane("/device:TPU:1", {
        trace.OPS_LINE: [ev("fusion.1", 300, 500)],
        trace.MODULES_LINE: [ev("jit_accumulate(1)", 300, 500)],
    })
    return [host, tpu1, tpu0, Plane("/host:metadata", {})]


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]


def test_device_planes_in_order():
    names = [p.name for p in trace.device_planes(hand_trace())]
    assert names == ["/device:TPU:0", "/device:TPU:1"]
    assert len(trace.device_planes(hand_trace(), 1)) == 1


def test_busy_time_is_the_union_inside_the_window():
    planes = hand_trace()
    win = trace.window(planes)
    tpu0, tpu1 = trace.device_planes(planes)
    # 100..250 (fusion + custom call), 400..450, 1000..1100
    assert trace.busy_seconds(tpu0, win) == pytest.approx(300e-9)
    assert trace.busy_seconds(tpu1, win) == pytest.approx(200e-9)


def test_modules_and_collectives():
    planes = hand_trace()
    win = trace.window(planes)
    tpu0, _ = trace.device_planes(planes)
    assert trace.module_seconds(tpu0, ["jit_accumulate"], win) == pytest.approx(200e-9)
    assert trace.collective_seconds(tpu0, win) == pytest.approx(50e-9)


def test_summary():
    s = trace.summarize(hand_trace(), 2, ["jit_accumulate"])
    assert s.window_s == pytest.approx(1000e-9)
    assert s.fits == 2 and s.chips == 2
    assert s.busy_s == pytest.approx([300e-9, 200e-9])
    assert s.accumulate_s == pytest.approx([200e-9, 200e-9])
    assert s.collective_s == pytest.approx([50e-9, 0.0])
    ops = dict((n, v) for n, v in s.top_ops)
    # fusion.1: 100 + 100 ns on chip 0, 200 ns on chip 1; mean over chips
    assert ops["fusion.1"] == pytest.approx(200e-9)
    assert s.top_ops[0][0] == "fusion.1"


def test_idle_gaps_named_by_host_activity():
    s = trace.summarize(hand_trace(), 2, ["jit_accumulate"])
    gaps = dict((n, v) for n, v in s.idle_gaps)
    # chip 0 idle: 250..400 (mid 325: only the first fit open), 450..1000
    # (mid 725: "put" inside the second fit)
    assert gaps == pytest.approx({trace.FIT: 150e-9, "put": 550e-9})
    assert s.idle_gaps[0][0] == "put"


def test_no_tpu_plane_gives_nothing():
    planes = [p for p in hand_trace() if not p.name.startswith("/device")]
    assert trace.summarize(planes, 1, []) is None


def test_window_must_be_unique():
    planes = hand_trace()
    planes[0].lines["python"].append(ev(trace.WINDOW, 0, 10))
    with pytest.raises(ValueError):
        trace.window(planes)


def test_recorded_v5e_trace():
    """One tall fit (131072 x 128 int8, 2 blocks a pass, L=3) traced on a
    v5e by the harness: 3 passes, so 6 runs of the accumulate.  Source
    paths in the file read ``<checkout>/...``."""
    planes = trace.load(RECORDED)
    s = trace.summarize(planes, 1, ["jit_accumulate"])
    assert s.chips == 1 and s.fits == 1
    assert s.window_s == pytest.approx(0.103773251)
    assert s.busy_s == pytest.approx([181.549e-6])
    assert s.accumulate_s == pytest.approx([152.335e-6])
    (tpu,) = trace.device_planes(planes)
    runs = [e for e in tpu.lines[trace.MODULES_LINE]
            if trace.module_name(e) == "jit_accumulate"]
    assert len(runs) == 6
    assert s.collective_s == [0.0]
    assert s.top_ops[0][0].startswith("%contingency_tables")
    # the ten largest groups hold most of the idle time, and no more of it
    idle = s.window_s - s.busy_s[0]
    assert 0.9 * idle < sum(v for _, v in s.idle_gaps) <= idle * (1 + 1e-12)
    assert s.idle_gaps[0][0] == trace.FIT


def test_fit_only_gaps_take_the_sampled_python_function():
    planes = hand_trace()
    # offsets from the window's start (100 ns): 225 is the middle of the
    # 250..400 gap; the 450..1000 gap has "put" open and keeps it.
    samples = [(0, "early"), (225, "repro/data/sources.py:iter_blocks")]
    s = trace.summarize(planes, 2, ["jit_accumulate"], samples)
    gaps = dict((n, v) for n, v in s.idle_gaps)
    assert gaps == pytest.approx(
        {"python: repro/data/sources.py:iter_blocks": 150e-9, "put": 550e-9}
    )


def test_stack_sampler_names_the_innermost_program_frame():
    import time

    import numpy as np

    from mrmrbench import cli
    from repro.data.sources import ArraySource

    src = ArraySource(np.zeros((4, 2), np.int8), np.zeros(4, np.int8))
    with cli.StackSampler() as sampler:
        deadline = time.perf_counter() + 0.1
        while time.perf_counter() < deadline:
            for _ in src.iter_blocks(1):
                pass
    assert sampler.samples and not sampler._thread.is_alive()
    labels = {label for _, label in sampler.samples}
    assert "repro/data/sources.py:iter_blocks < iter_blocks" in labels
    times = [t for t, _ in sampler.samples]
    assert times == sorted(times) and times[0] >= 0
