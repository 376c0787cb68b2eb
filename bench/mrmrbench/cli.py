"""One run of one benchmark cell.

    python bench/run.py --workload tall.mid --seed 7 --seconds 30 --trace 0

Set-up writes the cell's CorrAL dataset from the seed (on a line of its
own, outside ``setup_s``), starts JAX, and makes one warm-up fit of two
picks, which compiles or loads from the compile cache every program the
window runs.
The window then fits back to back, one user and one job after another,
until ``--seconds`` have passed; the fit in progress at the deadline
completes and counts.  Every fit is a user's first fit of its dataset: a
fresh ``NpySource`` with the program's per-dataset memos cleared.  Each
fit goes through the front door,
``MRMRSelector(...).fit(NpySource(X.npy, y.npy))``.

Once the window has closed and the device memory peak is read, every
distinct answer the window's fits returned is compared with the plain
reference (:mod:`mrmrbench.reference`).  The last line of standard output
is the result: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` and, with ``--trace 1``, ``breakdown``; its last key,
``checks``, gives each number compared with its limit, and the same lines
end standard error.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import pathlib
import re
import shutil
import sys
import threading
import time

from mrmrbench import corral, manifest, reference, trace

DATA_DIR = manifest.BENCH / "data"
TRACE_DIR = manifest.BENCH / "traces"
CACHE_DIR = manifest.BENCH / ".jax_cache"


def _parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap


def _log(**fields) -> None:
    print(json.dumps(fields), flush=True)


def set_environment() -> None:
    """Before JAX starts: the compile cache at a fixed path inside the
    checkout, and the TPU runtime's log files off."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def require_chips(chips: int):
    """The first ``chips`` TPU devices; exits where there are fewer."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"bench: no TPU (JAX platform is {devices[0].platform!r}); "
            "this benchmark runs only on TPU chips"
        )
    if len(devices) < chips:
        raise SystemExit(
            f"bench: the cell needs {chips} chips, JAX found {len(devices)}"
        )
    return devices[:chips]


class CompileCounter:
    """Counts XLA backend compiles (cache loads included) while on."""

    def __init__(self):
        import jax

        self.count, self.on = 0, False
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, secs, **_):
        if self.on and name == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def fitter(config: dict, traffic: dict, devices, x_path, y_path):
    """The fit a window repeats: the front door on the dataset's files,
    returning ``(reference.Answer, MRMRResult.io)``."""
    from repro import MRMRSelector
    from repro.data.binning import clear_binner_memo
    from repro.data.sources import NpySource, clear_stats_memo

    def fit(num_select: int = int(config["num_select"])):
        # Every fit is a user's first fit of its dataset.
        clear_stats_memo()
        clear_binner_memo()
        sel = MRMRSelector(
            num_select=num_select,
            criterion=traffic["criterion"],
            devices=list(devices),
            block_obs=int(config["block_obs"]),
            prefetch=config["prefetch"],
            batch_candidates=int(config["batch_candidates"]),
        ).fit(NpySource(str(x_path), str(y_path)))
        answer = reference.Answer(
            sel.selected_.copy(), sel.gains_.copy(), sel.scores_.copy()
        )
        return answer, dict(sel.result_.io)

    return fit


def _module_names(log) -> list[str]:
    """Names of the accumulate programs an ``AccumulateLog`` recorded, as
    the trace names their runs."""
    names = []
    for text in log.compiled_texts():
        m = re.search(r"^HloModule ([^\s,]+)", text, re.M)
        if m:
            names.append(m.group(1))
    return names


def _window(fit, seconds: float, counter: CompileCounter):
    """Fits back to back for ``seconds``; -> (answers, io, elapsed s)."""
    answers, io = [], None
    counter.on = True
    t0 = time.perf_counter()
    deadline = t0 + seconds
    import jax

    while True:
        with jax.profiler.TraceAnnotation(trace.FIT):
            answer, io = fit()
        answers.append(answer)
        if time.perf_counter() >= deadline:
            break
    elapsed = time.perf_counter() - t0
    counter.on = False
    return answers, io, elapsed


class StackSampler:
    """Samples, every 5 ms, where the main thread is: the innermost function
    of the program and the innermost function of all (a wait, a copy, a
    library call), so that idle time no runtime event covers can be named.
    Samples are ``(ns since the sampler started, label)``."""

    INTERVAL = 0.005

    def __init__(self):
        self.samples: list = []
        self._main = threading.get_ident()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    @staticmethod
    def _label(frame) -> str:
        """``<innermost program function> < <innermost function>``."""
        if frame is None:
            return "?"
        inner, where = frame.f_code.co_name, None
        while frame is not None:
            path = frame.f_code.co_filename.replace(os.sep, "/")
            if "/repro/" in path:
                where = f"{path[path.rindex('/repro/') + 1 :]}:{frame.f_code.co_name}"
                break
            frame = frame.f_back
        return inner if where is None else f"{where} < {inner}"

    def _run(self):
        while not self._stop.wait(self.INTERVAL):
            frame = sys._current_frames().get(self._main)
            t = time.perf_counter_ns() - self.t0
            self.samples.append((t, self._label(frame)))

    def __enter__(self) -> "StackSampler":
        self.t0 = time.perf_counter_ns()
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join()


def _traced_window(fit, seconds: float, counter, trace_dir: pathlib.Path):
    import jax
    from repro.core.streaming import AccumulateLog

    shutil.rmtree(trace_dir, ignore_errors=True)
    trace_dir.mkdir(parents=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        with AccumulateLog() as log:
            with jax.profiler.TraceAnnotation(trace.WINDOW), StackSampler() as py:
                out = _window(fit, seconds, counter)
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace file, found {files}")
    return out, log, pathlib.Path(files[0]), py.samples


def _device(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return dict(
        platform=devices[0].platform,
        kind=devices[0].device_kind,
        count=len(devices),
        memory_peak_bytes=peak,
    )


def check_answers(answers, config: dict, traffic: dict, x_path, y_path):
    """-> (numbers, per-answer verdicts): every distinct answer against the
    reference, numbers taken as the widest over the answers."""
    import numpy as np

    distinct: dict = {}
    for a in answers:
        distinct.setdefault(a.key(), a)
    picks = sorted({int(s) for a in distinct.values() for s in a.ids})
    X = np.load(str(x_path), mmap_mode="r")
    y = np.load(str(y_path), mmap_mode="r")
    tables = reference.count_tables(
        X, y, picks, int(config["num_values"]), int(config["num_classes"])
    )
    ref = reference.Scorer(tables, traffic["criterion"])
    numbers = {k: 0.0 for k in reference.LIMITS}
    verdict = {}
    for key, a in distinct.items():
        got = reference.compare(a, ref)
        verdict[key] = reference.judge(got)
        for k in numbers:
            numbers[k] = max(numbers[k], got[k])
    return numbers, verdict


class RunView:
    """What a per-layer metric's ``read`` sees."""

    def __init__(self, config, chips, io, summary, device_kind):
        self.config, self.chips, self.io = config, chips, io
        self.trace, self.device_kind = summary, device_kind


def run_cell(
    spec: dict,
    workload: str,
    seed: int,
    seconds: float,
    traced: bool,
    devices,
    t0: float,
    data_dir: pathlib.Path = DATA_DIR,
    trace_dir: pathlib.Path = TRACE_DIR,
    config: dict | None = None,
) -> dict:
    """Set up, measure and check one run; -> the result line's object."""
    cell = manifest.cell(spec, workload)
    config = config or manifest.config(spec, cell["config"])
    traffic = manifest.traffic(cell["traffic"])
    if (traffic["loop"], traffic["users"]) != ("closed", 1):
        raise ValueError("this harness drives one user in a closed loop")
    if int(traffic["devices"]) != len(devices):
        raise ValueError(
            f"traffic {cell['traffic']!r} fits on {traffic['devices']} chips, "
            f"given {len(devices)}"
        )
    from repro.runtime.compile_cache import enable_compile_cache

    t = time.perf_counter()
    x_path, y_path, wrote = corral.ensure_dataset(data_dir, config, seed)
    data_s = time.perf_counter() - t
    _log(dataset=str(x_path.parent), written=wrote, data_s=data_s)

    enable_compile_cache()
    counter = CompileCounter()
    fit = fitter(config, traffic, devices, x_path, y_path)
    # Warm-up: a fit with two picks runs every program of the window (the
    # relevance pass, a redundancy pass, the fold and the argmax) at the
    # window's shapes, so each compiles or loads here, in a fifth of the
    # time of a whole fit.
    fit(min(2, int(config["num_select"])))
    setup_s = time.perf_counter() - t0 - data_s

    if traced:
        (answers, io, elapsed), log, path, samples = _traced_window(
            fit, seconds, counter, trace_dir
        )
    else:
        answers, io, elapsed = _window(fit, seconds, counter)
    device = _device(devices)
    _log(fits=len(answers), window_s=elapsed, compiles_in_window=counter.count)

    result = dict(correct=False, attempted=len(answers), failed=0)
    if traced:
        summary = trace.summarize(
            trace.load(path), len(devices), _module_names(log), samples
        )
        view = RunView(config, len(devices), io, summary, device["kind"])
        metrics = {}
        for m in manifest.metrics_of(spec, "per_layer", workload):
            value = manifest.reader(m["name"])(view)
            if value is not None:
                metrics[m["name"]] = dict(value=value, unit=m["unit"])
        if summary is not None:
            device.update(
                busy_s=sum(summary.busy_s) / len(summary.busy_s),
                window_s=summary.window_s,
            )
            result["breakdown"] = dict(
                device_ops=summary.top_ops, idle_gaps=summary.idle_gaps
            )
    else:
        metrics = dict(
            fit_s=dict(value=elapsed / len(answers), unit="s"),
            setup_s=dict(value=setup_s, unit="s"),
        )
    result.update(metrics=metrics, device=device)

    numbers, verdict = check_answers(answers, config, traffic, x_path, y_path)
    failed = sum(1 for a in answers if not verdict[a.key()])
    result.update(correct=failed == 0 and bool(answers), failed=failed)
    result["checks"] = {
        k: dict(value=numbers[k], limit=reference.LIMITS[k]) for k in numbers
    }
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    return result


def main(argv=None, t0: float | None = None) -> int:
    t0 = time.perf_counter() if t0 is None else t0
    args = _parser().parse_args(argv)
    spec = manifest.load()
    cell = manifest.cell(spec, args.workload)
    set_environment()
    devices = require_chips(int(cell["chips"]))
    result = run_cell(
        spec, args.workload, args.seed, args.seconds, bool(args.trace),
        devices, t0,
    )
    print(json.dumps(result), flush=True)
    return 0
