"""``BENCHMARK.json`` against the rules a harness driven by data needs:
every file found by name, names and units from the allowed characters,
every ``moves`` reported where its metric is, and the four-chip share."""

import json
import math
import pathlib
import re
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from mrmrbench import manifest  # noqa: E402

SPEC = manifest.load()
CELLS = [w["name"] for w in SPEC["workloads"]]
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TEXT = re.compile(r"^[^\t\n]{1,200}$")


def test_top_level_keys():
    assert set(SPEC) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert SPEC["command"][:1] == ["python3"] and len(SPEC["command"]) <= 32
    for word in SPEC["command"][1:]:
        assert not word.startswith("/") and ".." not in word
        assert any(word.startswith(p + "/") for p in SPEC["paths"])


def test_run_seconds_fit_a_full_check_of_24_cells():
    s = SPEC["run_seconds"]
    assert isinstance(s, int) and 1 <= s <= 51
    runs = 2 + 14 * 24
    assert runs * (s + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_found_by_name(cell):
    w = manifest.cell(SPEC, cell)
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    config = manifest.config(SPEC, w["config"])
    assert config["name"] == w["config"]
    traffic = manifest.traffic(w["traffic"])
    assert traffic["name"] == w["traffic"]
    assert int(traffic["devices"]) == w["chips"] in (1, 4)
    for m in manifest.metrics_of(SPEC, "per_layer", cell):
        assert callable(manifest.reader(m["name"]))


@pytest.mark.parametrize("name", [c["name"] for c in SPEC["configs"]])
def test_config_entry_matches_its_file(name):
    entry = next(c for c in SPEC["configs"] if c["name"] == name)
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"].startswith("bench/")
    data = json.loads((BENCH.parent / entry["file"]).read_text())
    assert data["reduced"] == entry["reduced"]
    assert data["source"] == entry["source"]
    assert any(w["config"] == name for w in SPEC["workloads"])
    for key in ("rows", "features", "num_values", "num_classes",
                "num_select", "block_obs", "flip_prob"):
        assert key in data


def test_names_are_unique_and_allowed():
    for group in ("configs", "workloads"):
        names = [x["name"] for x in SPEC[group]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    words = names + CELLS + [c["name"] for c in SPEC["configs"]]
    words += [w["config"] for w in SPEC["workloads"]]
    words += [w["traffic"] for w in SPEC["workloads"]]
    words += [k for c in SPEC["configs"] for k in c["reduced"]]
    for word in words:
        assert NAME.match(word), word
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_metric_entry(metric):
    m = next(x for x in METRICS if x["name"] == metric)
    assert UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    if m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    else:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves",
        }
        assert m["source"] in (
            "device_trace", "program_span", "program_counter", "host_clock",
        )
        assert TEXT.match(m["layer"])
        assert manifest.metric_path(metric).exists()


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_moves_is_reported_where_the_metric_is(metric):
    m = next(x for x in SPEC["per_layer"] if x["name"] == metric)
    for cell in m.get("workloads", CELLS):
        assert cell in CELLS
        reported = [x["name"] for x in manifest.metrics_of(SPEC, "end_to_end", cell)]
        assert m["moves"] in reported, (metric, cell)


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_reports_enough(cell):
    e2e = [x["name"] for x in manifest.metrics_of(SPEC, "end_to_end", cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert manifest.metrics_of(SPEC, "per_layer", cell)


def test_four_chip_share():
    four = sum(1 for w in SPEC["workloads"] if w["chips"] == 4)
    assert four <= max(1, math.floor(len(CELLS) / 2))


def test_texts_and_paths():
    for x in SPEC["configs"] + SPEC["workloads"]:
        assert TEXT.match(x["why"])
    for c in SPEC["configs"]:
        assert TEXT.match(c["source"])
    for p in SPEC["paths"]:
        assert re.match(r"^[A-Za-z0-9_./-]{1,200}$", p) and ".." not in p
    assert len((BENCH.parent / "BENCHMARK.json").read_bytes()) <= 64 * 1024
