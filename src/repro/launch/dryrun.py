import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# NOTE (§Perf iteration 4): the CPU backend float-normalizes bf16 compute to
# f32 and no XLA flag disables it (--xla_allow_excess_precision=false was
# tried: zero effect — the normalization pass, not excess precision, is
# responsible).  The TPU-width correction therefore lives in
# repro.analysis.hlo_analysis.analyze_hlo(bf16_model=True).

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: for each cell we AOT-lower ``train_step`` / ``prefill`` /
``serve_step`` against ShapeDtypeStruct inputs (no allocation), compile for
the production mesh, and record

* ``memory_analysis()``  — fits-in-HBM evidence,
* ``cost_analysis()``    — per-device FLOPs / bytes for §Roofline,
* collective operand/wire bytes parsed from the partitioned HLO
  (``repro.analysis.hlo_analysis``), scan trip counts unrolled.

Results are cached as JSON under ``results/dryrun/<mesh>/<arch>__<shape>.json``
so the matrix re-runs incrementally.

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis.hlo_analysis import analyze_hlo
from repro.analysis.roofline import model_flops, param_counts, roofline_terms
from repro.configs import REGISTRY, SHAPES, get_config, get_shape, shape_applicable
from repro.launch.mesh import make_production_mesh
from repro.models.model import build_model
from repro.train.optimizer import AdamWConfig
from repro.train.train_step import (
    make_train_state_specs,
    make_train_step,
    train_state_shapes,
)

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "results", "dryrun")


def _named(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def _apply_overrides(cfg, overrides: dict):
    if not overrides:
        return cfg
    import dataclasses

    typed = {}
    for k, v in overrides.items():
        cur = getattr(cfg, k)
        if isinstance(cur, bool):
            typed[k] = v in ("1", "true", "True")
        elif isinstance(cur, int):
            typed[k] = int(v)
        elif isinstance(cur, float):
            typed[k] = float(v)
        else:
            typed[k] = v
    return dataclasses.replace(cfg, **typed)


def build_cell(arch: str, shape_name: str, mesh, overrides: dict | None = None):
    """-> (fn, example_args, in_shardings, donate_argnums, step_kind)."""
    cfg = _apply_overrides(get_config(arch), overrides or {})
    shape = get_shape(shape_name)
    bundle = build_model(cfg, mesh)
    batch_sds = bundle.input_specs(shape)
    batch_shardings = _named(mesh, bundle.input_shardings(shape))

    if shape.kind == "train":
        opt_cfg = AdamWConfig(moment_dtype=cfg.optimizer_moment_dtype)
        step = make_train_step(bundle, opt_cfg)
        state_sds = train_state_shapes(bundle, opt_cfg)
        state_shardings = _named(mesh, make_train_state_specs(bundle))
        return (
            step,
            (state_sds, batch_sds),
            (state_shardings, batch_shardings),
            (0,),
            "train_step",
            bundle,
        )
    # Serve cells lower with f32 params on purpose: the CPU backend computes
    # in f32 either way, and the analyzer's bf16 width correction counts the
    # f32 weight reads at 2 bytes — i.e. the dry-run models bf16-stored
    # serving weights (cfg.serve_params_dtype, used by the real engine)
    # without the spurious convert temps a bf16 SDS causes on CPU (§Perf B1).
    params_sds = bundle.shapes()
    params_shardings = bundle.shardings()
    if shape.kind == "prefill":
        return (
            bundle.prefill,
            (params_sds, batch_sds),
            (params_shardings, batch_shardings),
            (),
            "prefill",
            bundle,
        )
    return (
        bundle.serve_step,
        (params_sds, batch_sds),
        (params_shardings, batch_shardings),
        (1,),  # donate the cache-carrying batch
        "serve_step",
        bundle,
    )


def _memory_dict(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    if ma is None:
        return {}
    out = {}
    for k in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "generated_code_size_in_bytes",
        "alias_size_in_bytes",
    ):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    if out:
        out["total_hbm_bytes"] = (
            out.get("argument_size_in_bytes", 0)
            + out.get("output_size_in_bytes", 0)
            + out.get("temp_size_in_bytes", 0)
            - out.get("alias_size_in_bytes", 0)
        )
    return out


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
             force: bool = False, keep_hlo: bool = False,
             overrides: dict | None = None, tag: str = "") -> dict:
    name = f"{arch}__{shape_name}" + (f"__{tag}" if tag else "")
    path = os.path.join(out_dir, mesh_kind, f"{name}.json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    cfg = get_config(arch)
    shape = get_shape(shape_name)
    ok, reason = shape_applicable(cfg, shape)
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_kind,
        "status": "skipped" if not ok else "pending",
    }
    if not ok:
        rec["skip_reason"] = reason
        _save(path, rec)
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_dev = mesh.size
    if overrides:
        rec["overrides"] = dict(overrides)
    try:
        fn, args, in_sh, donate, step_kind, bundle = build_cell(
            arch, shape_name, mesh, overrides
        )
        jitted = jax.jit(fn, in_shardings=in_sh, donate_argnums=donate)
        t0 = time.time()
        lowered = jitted.lower(*args)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
        cost = dict(compiled.cost_analysis() or {})
        hlo = compiled.as_text()
        bf16 = jnp.dtype(bundle.cfg.dtype) == jnp.bfloat16
        hc = analyze_hlo(hlo, bf16_model=bf16)  # trip-aware, TPU-width
        hc_raw = analyze_hlo(hlo, bf16_model=False) if bf16 else hc
        coll = hc["collectives"]
        mem = _memory_dict(compiled)
        n_total, n_active = param_counts(bundle.cfg)
        mf = model_flops(bundle.cfg, shape)
        roof = roofline_terms(
            flops_per_device=float(hc["flops"]),
            bytes_per_device=float(hc["bytes"]),
            collective_operand_bytes=float(coll["operand_bytes"]),
            n_devices=n_dev,
            model_flops_global=mf,
        )
        rec.update(
            status="ok",
            step_kind=step_kind,
            n_devices=n_dev,
            mesh_shape={k: int(v) for k, v in mesh.shape.items()},
            params_total=float(bundle.num_params()),
            params_matmul_total=float(n_total),
            params_matmul_active=float(n_active),
            lower_s=round(t1 - t0, 2),
            compile_s=round(t2 - t1, 2),
            cost_xla={
                k: float(v)
                for k, v in cost.items()
                if isinstance(v, (int, float)) and k in ("flops", "bytes accessed")
            },
            cost={"flops": float(hc["flops"]), "bytes": float(hc["bytes"])},
            cost_raw_f32={
                "bytes": float(hc_raw["bytes"]),
                "collective_operand_bytes": float(
                    hc_raw["collectives"]["operand_bytes"]
                ),
            },
            memory=mem,
            collectives=coll,
            roofline=roof,
            hlo_bytes=len(hlo),
        )
        if keep_hlo:
            hp = path[:-5] + ".hlo.txt"
            os.makedirs(os.path.dirname(hp), exist_ok=True)
            with open(hp, "w") as f:
                f.write(hlo)
    except Exception as e:  # a failing cell is a bug; record it loudly
        rec.update(status="error", error=repr(e), trace=traceback.format_exc())
    _save(path, rec)
    return rec


def _save(path: str, rec: dict) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)


def _summary_line(rec: dict) -> str:
    tag = f"{rec['arch']:<24s} {rec['shape']:<12s} {rec['mesh']:<6s}"
    if rec["status"] == "skipped":
        return f"{tag} SKIP  ({rec['skip_reason'][:60]}...)"
    if rec["status"] == "error":
        return f"{tag} ERROR {rec['error'][:90]}"
    r = rec["roofline"]
    mem = rec.get("memory", {}).get("total_hbm_bytes")
    memgb = f"{mem/2**30:7.2f}GiB" if mem else "      n/a"
    return (
        f"{tag} ok    comp={r['compute_s']:9.3e}s mem={r['memory_s']:9.3e}s "
        f"coll={r['collective_s']:9.3e}s dom={r['dominant'][:-2]:<10s} "
        f"hbm/dev={memgb} useful={r['useful_flops_ratio']:5.2f} "
        f"compile={rec['compile_s']:.0f}s"
    )


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="arch id or 'all'")
    ap.add_argument("--shape", default=None, help="shape id or 'all'")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true", help="full matrix")
    ap.add_argument("--force", action="store_true", help="ignore cache")
    ap.add_argument("--keep-hlo", action="store_true")
    ap.add_argument("--out", default=os.path.abspath(RESULTS_DIR))
    ap.add_argument("--set", action="append", default=[], dest="overrides",
                    help="config override key=value (repeatable; §Perf)")
    ap.add_argument("--tag", default="", help="suffix for the result file")
    args = ap.parse_args()
    overrides = dict(kv.split("=", 1) for kv in args.overrides)

    archs = sorted(REGISTRY) if (args.all or args.arch in (None, "all")) else [args.arch]
    shapes = sorted(SHAPES) if (args.all or args.shape in (None, "all")) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    n_bad = 0
    for mesh_kind in meshes:
        for arch in archs:
            for shape_name in shapes:
                rec = run_cell(
                    arch, shape_name, mesh_kind, args.out,
                    force=args.force, keep_hlo=args.keep_hlo,
                    overrides=overrides, tag=args.tag,
                )
                print(_summary_line(rec), flush=True)
                n_bad += rec["status"] == "error"
    if n_bad:
        raise SystemExit(f"{n_bad} cells failed")


if __name__ == "__main__":
    main()
