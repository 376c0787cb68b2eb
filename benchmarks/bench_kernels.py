"""Micro-benchmarks of the paper's scoring hot loop (kernel substrate).

Times the jnp/MXU formulations that back the Pallas kernels (the Pallas
bodies themselves only run in interpret mode on CPU — their timing is
meaningless here; correctness is covered by tests/test_kernels.py):

* batched contingency tables (one-hot matmul)  — conventional-encoding pass
* fused Pearson correlation                    — alternative-encoding pass
* MI from stacked tables                       — reducer payload
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import SCALE, csv_row, save
from repro.core.contingency import batched_counts
from repro.core.scores import mi_from_counts, pearson_rows

SIZES = {
    "smoke": dict(M=100_000, F=512, T=16),
    "full": dict(M=1_000_000, F=1024, T=16),
}


def _time(fn, *args, repeats=5):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.mean(ts))


def main() -> dict:
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    p = SIZES[SCALE]
    M, F, T = p["M"], p["F"], p["T"]
    key = jax.random.PRNGKey(0)
    X = jax.random.randint(key, (M, F), 0, 2, jnp.int8)
    y = jax.random.randint(key, (M,), 0, 2, jnp.int8)
    Xr = jax.random.normal(key, (F, M // 16), jnp.float32)
    Yr = jax.random.normal(key, (T, M // 16), jnp.float32)

    out = {"figure": "kernels", "scale": SCALE, "points": []}

    f1 = jax.jit(lambda a, b: batched_counts(a, b, 2, 2))
    t = _time(f1, X, y)
    eff = M * F * 4 * 2 / t  # one-hot matmul MACs*2
    out["points"].append({"name": "contingency", "s": t, "flops_per_s": eff})
    csv_row("kernel/contingency", t * 1e6, f"{eff/1e9:.1f}GFLOP/s")

    counts = f1(X, y)
    f2 = jax.jit(mi_from_counts)
    t = _time(f2, counts)
    out["points"].append({"name": "mi_from_counts", "s": t})
    csv_row("kernel/mi_from_counts", t * 1e6, f"F={F}")

    f3 = jax.jit(pearson_rows)
    t = _time(f3, Xr, Yr)
    eff = F * T * (M // 16) * 2 / t
    out["points"].append({"name": "pearson", "s": t, "flops_per_s": eff})
    csv_row("kernel/pearson", t * 1e6, f"{eff/1e9:.1f}GFLOP/s")

    save("kernels", out)
    return out


if __name__ == "__main__":
    main()
