"""The count layer's required bytes against hand-worked shapes, the peaks
table, and the roofline reader built on them."""

import pathlib
import sys
import types

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from mrmrbench import manifest, work  # noqa: E402


@pytest.mark.parametrize(
    "rows, features, per_pass",
    [
        # 2^20 rows x (1000 int8 codes + target byte + validity byte)
        (1048576, 1000, 1048576 * 1002),
        # 8192 x (50,000 + 2)
        (8192, 50000, 409616384),
        (1, 1, 3),
    ],
)
def test_count_bytes_per_pass(rows, features, per_pass):
    assert work.count_bytes_per_pass(rows, features) == per_pass


def test_count_bytes_per_fit_scales_with_passes():
    # L=10 mid: one relevance pass and nine redundancy passes.
    assert work.count_bytes_per_fit(1048576, 1000, 10) == 10_506_731_520


def test_peaks_of_v5e():
    p = work.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("TPU v99")


def _view(accumulate_s, chips=1, fits=2, passes=10):
    summary = types.SimpleNamespace(
        fits=fits, chips=chips, accumulate_s=accumulate_s, window_s=10.0,
        busy_s=[1.0] * chips, collective_s=[0.0] * chips,
    )
    return types.SimpleNamespace(
        trace=summary, chips=chips, io=dict(passes=passes, bytes_read=7),
        config=dict(rows=1048576, features=1000), device_kind="TPU v5 lite",
    )


def test_roofline_reader_by_hand():
    read = manifest.reader("accumulate_roofline")
    # 10,506,731,520 B at 819 GB/s is 12.8287 ms; 0.1 s of device time per
    # fit (0.2 s over two fits) is 12.8287 % of the roofline.
    got = read(_view([0.2]))
    assert got == pytest.approx(100 * 10_506_731_520 / 819e9 / 0.1)


def test_roofline_reader_four_chips_takes_slowest():
    read = manifest.reader("accumulate_roofline")
    got = read(_view([0.04, 0.05, 0.06, 0.05], chips=4))
    assert got == pytest.approx(100 * 10_506_731_520 / (4 * 819e9) / 0.03)


def test_device_readers_are_silent_without_a_trace():
    view = _view([0.2])
    view.trace = None
    for name in ("device_idle_share", "accumulate_device_s",
                 "accumulate_roofline", "collective_s"):
        assert manifest.reader(name)(view) is None
    assert manifest.reader("bytes_read")(view) == 7.0


def test_collective_reader_needs_chips_and_time():
    read = manifest.reader("collective_s")
    assert read(_view([0.2])) is None
    view = _view([0.2] * 4, chips=4)
    view.trace.collective_s = [0.01, 0.02, 0.015, 0.0]
    assert read(view) == pytest.approx(0.01)


def test_idle_share_reader():
    view = _view([0.2], chips=2)
    view.trace.busy_s = [1.0, 3.0]
    assert manifest.reader("device_idle_share")(view) == pytest.approx(80.0)
