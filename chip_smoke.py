#!/usr/bin/env python3
"""Bring-up smoke test: streamed mRMR selection on a TPU.

    python chip_smoke.py              # four phases on one chip
    python chip_smoke.py --chips 4    # tall and wide, sharded over 4 chips

Every phase fits through the library front door,
``MRMRSelector(...).fit(NpySource(...))``, with the Pallas count kernels,
and checks the result against a second fit that differs in one way only.
On one chip that fit counts with ``MIScore(use_pallas=False)``: exact jnp
integer counting on the same chip, with no Pallas.  With ``--chips 4`` it
is the same fit on a one-device mesh of the first chip.  The selected ids
must be identical and the gains within 1e-6.  The compiled accumulate must
hold a ``tpu_custom_call``, so neither interpret mode nor a quiet fall-back
to jnp passes; every accumulate program the fit ran is checked (the
conditional phase runs two: class target and class-fused pair target).

Phases on one chip:

1. tall: CorrAL 2^21 x 256 int8 (512 MiB), block_obs 65536, select 10, mid;
2. conditional: the same file, criterion jmi, select 5;
3. continuous: 2^20 x 64 float32, bins 16, select 5 (bin codes are made on
   the device, fused with the counts);
4. wide: CorrAL 4096 x 16384, select 10, on a one-device ("model",) mesh,
   which is the feature-sharded plan.

With ``--chips 4`` only phases 1 and 4 run: tall with blocks sharded over
("data",), wide with blocks and statistics sharded over ("model",).  The
wide phase also prints the statistics bytes each device holds.

Each phase prints one JSON line; the last line is
``{"ok": true, "device": {...}}``.  Without a TPU, or when a phase fails or
disagrees, the script exits non-zero and prints no such line.  Data is made
from ``--seed`` under ``--data-dir`` and deleted at the end.  All four
phases run at full size within about two minutes on one v5e, so no rows
are cut.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
GAIN_TOL = 1e-6


def _parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--data-dir", default=str(HERE / "chip_smoke_data"))
    return ap


class CompileClock:
    """Seconds JAX spent in XLA compilation (persistent-cache loads
    included) since the last ``reset``."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def reset(self):
        self.seconds = 0.0


def _fit(clock, source, score, *, select, mesh=None, criterion="mid",
         bins=None):
    """-> (fitted selector, wall s, compile s, log of its accumulates)."""
    from repro import MRMRSelector
    from repro.core.streaming import AccumulateLog

    clock.reset()
    t0 = time.perf_counter()
    with AccumulateLog() as log:
        sel = MRMRSelector(
            num_select=select, score=score, mesh=mesh, criterion=criterion,
            bins=bins, block_obs=65536,
        ).fit(source)
    return sel, time.perf_counter() - t0, clock.seconds, log


def _compare(phase, got, want):
    import numpy as np

    if not np.array_equal(got.selected_, want.selected_):
        raise SystemExit(
            f"{phase}: selected ids differ: {got.selected_.tolist()} vs "
            f"{want.selected_.tolist()}"
        )
    gap = float(np.max(np.abs(got.gains_ - want.gains_)))
    if not gap <= GAIN_TOL:
        raise SystemExit(f"{phase}: gains differ by {gap} > {GAIN_TOL}")
    return gap


def _inspect(log):
    """-> (accumulate programs the fit ran, whether each holds a
    ``tpu_custom_call``, per-device bytes and global bytes of the first
    state it returned)."""
    texts = log.compiled_texts()
    state = log.entries[0].output
    shard_bytes = {
        str(s.device.id): int(s.data.nbytes) for s in state.addressable_shards
    }
    custom = bool(texts) and all("tpu_custom_call" in t for t in texts)
    return len(texts), custom, shard_bytes, int(state.nbytes)


def _report(phase, source, sel, wall, compile_s, ref_wall, gap, programs,
            custom, extra=None):
    io = sel.result_.io or {}
    line = dict(
        phase=phase,
        rows=source.num_obs,
        cols=source.num_features,
        wall_s=wall,
        compile_s=compile_s,
        reference_wall_s=ref_wall,
        passes=io.get("passes"),
        bytes_read=io.get("bytes_read"),
        selected=sel.selected_.tolist(),
        max_gain_gap=gap,
        accumulates=programs,
        tpu_custom_call=custom,
    )
    line.update(extra or {})
    print(json.dumps(line), flush=True)
    if not custom:
        raise SystemExit(f"{phase}: an accumulate has no tpu_custom_call")


def _make_data(data_dir: pathlib.Path, seed: int, chips: int, cut: int = 1):
    """Write each phase's dataset; ``cut`` divides row counts (tiny CPU
    rehearsals only)."""
    import numpy as np

    from repro.data.sources import CorralSource, NpySource
    from repro.data.synthetic import continuous_wide_dataset

    def corral(name, rows, cols):
        x, y = data_dir / f"{name}_X.npy", data_dir / f"{name}_y.npy"
        CorralSource(rows // cut, cols, seed=seed).to_npy(str(x), str(y))
        return NpySource(str(x), str(y))

    data = dict(
        tall=corral("tall", 2**21, 256), wide=corral("wide", 4096, 16384)
    )
    if chips == 1:
        X, y = continuous_wide_dataset(2**20 // cut, 64, seed=seed)
        x_path, y_path = data_dir / "cont_X.npy", data_dir / "cont_y.npy"
        np.save(x_path, np.asarray(X, np.float32))
        np.save(y_path, np.asarray(y, np.int32))
        data["continuous"] = NpySource(str(x_path), str(y_path))
    return data


def _one_chip(clock, data):
    from repro.core.scores import MIScore
    from repro.dist.meshes import make_mesh

    runs = [
        ("tall", data["tall"], dict(select=10), 2),
        ("conditional", data["tall"], dict(select=5, criterion="jmi"), 2),
        ("continuous", data["continuous"], dict(select=5, bins=16), 16),
        ("wide", data["wide"], dict(select=10), 2),
    ]
    for phase, source, kw, values in runs:
        if phase == "wide":
            kw = dict(kw, mesh=make_mesh((1,), ("model",)))
        score = MIScore(num_values=values, num_classes=2)
        sel, wall, comp, log = _fit(clock, source, score, **kw)
        ref_score = MIScore(num_values=values, num_classes=2, use_pallas=False)
        ref, ref_wall, _, _ = _fit(clock, source, ref_score, **kw)
        gap = _compare(phase, sel, ref)
        programs, custom, _, _ = _inspect(log)
        _report(phase, source, sel, wall, comp, ref_wall, gap, programs, custom)


def _four_chips(clock, data):
    import jax

    from repro.core.scores import MIScore
    from repro.dist.meshes import make_mesh

    devices = jax.devices()
    if len(devices) < 4:
        raise SystemExit(f"--chips 4 needs 4 devices, found {len(devices)}")
    score = MIScore(num_values=2, num_classes=2)
    for phase, axis in (("tall", "data"), ("wide", "model")):
        source = data[phase]
        mesh = make_mesh((4,), (axis,), devices=devices[:4])
        one = make_mesh((1,), (axis,), devices=devices[:1])
        sel, wall, comp, log = _fit(clock, source, score, select=10, mesh=mesh)
        ref, ref_wall, _, _ = _fit(clock, source, score, select=10, mesh=one)
        gap = _compare(phase, sel, ref)
        programs, custom, shard_bytes, total = _inspect(log)
        extra = dict(
            mesh={axis: 4}, state_bytes=total,
            state_bytes_per_device=shard_bytes,
        )
        if phase == "wide" and sum(shard_bytes.values()) != total:
            raise SystemExit(f"wide: statistics are not split: {shard_bytes}")
        _report(
            phase, source, sel, wall, comp, ref_wall, gap, programs, custom,
            extra,
        )


def main(argv=None) -> int:
    args = _parser().parse_args(argv)
    sys.path.insert(0, str(HERE / "src"))
    from repro.runtime.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: no TPU found (JAX platform is {dev.platform!r}); "
            "this script only runs on a TPU",
            file=sys.stderr,
        )
        return 2
    clock = CompileClock()
    print(json.dumps(dict(compile_cache=cache_dir)), flush=True)
    data_dir = pathlib.Path(args.data_dir)
    data_dir.mkdir(parents=True, exist_ok=False)
    try:
        t0 = time.perf_counter()
        data = _make_data(data_dir, args.seed, args.chips)
        print(json.dumps(dict(data_s=time.perf_counter() - t0)), flush=True)
        if args.chips == 1:
            _one_chip(clock, data)
        else:
            _four_chips(clock, data)
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)
    device = dict(
        platform=dev.platform, kind=dev.device_kind, count=len(jax.devices())
    )
    print(json.dumps(dict(ok=True, device=device)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
