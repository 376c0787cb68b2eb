"""``BENCHMARK.json`` and the files it names, found by name.

* a configuration: the file its entry names (``bench/configs/<name>.json``);
* a traffic mix: ``bench/traffic/<name>.json``;
* a per-layer metric: ``bench/metrics/<name>.py``, a module whose
  ``read(run)`` returns the metric's value or None where the run gives it
  nothing to read.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load(root: pathlib.Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.exists():
        raise FileNotFoundError(f"{path} is missing")
    return json.loads(path.read_text())


def cell(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(
        f"no workload {name!r}; known: {[w['name'] for w in spec['workloads']]}"
    )


def config(spec: dict, name: str, root: pathlib.Path = ROOT) -> dict:
    for c in spec["configs"]:
        if c["name"] == name:
            return json.loads((root / c["file"]).read_text())
    raise KeyError(f"no configuration {name!r}")


def traffic(name: str, bench: pathlib.Path = BENCH) -> dict:
    return json.loads((bench / "traffic" / f"{name}.json").read_text())


def metric_path(name: str, bench: pathlib.Path = BENCH) -> pathlib.Path:
    return bench / "metrics" / f"{name}.py"


def reader(name: str, bench: pathlib.Path = BENCH):
    """The ``read`` function of one per-layer metric."""
    path = metric_path(name, bench)
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def metrics_of(spec: dict, kind: str, workload: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics one cell reports."""
    return [
        m for m in spec[kind]
        if "workloads" not in m or workload in m["workloads"]
    ]
