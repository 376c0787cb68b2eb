"""The examples run end to end, in-process, at a size of a few seconds.

Each example keeps its dataset sizes in module constants; the tests load
the module, shrink the constants and check what ``main()`` returns and
prints against the library's own answer on the same data.
"""

import importlib.util
import pathlib

import jax.numpy as jnp
import numpy as np

from repro import MIScore, MRMRSelector
from repro.core import mrmr_reference
from repro.data.sources import CorralSource
from repro.data.synthetic import corral_dataset_np

_ROOT = pathlib.Path(__file__).parent.parent


def _load(name):
    spec = importlib.util.spec_from_file_location(
        f"example_{name}", _ROOT / "examples" / f"{name}.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_quickstart(monkeypatch, capsys):
    qs = _load("quickstart")
    sizes = dict(ROWS=3000, COLS=16, L=6, WIDE_ROWS=128, WIDE_COLS=512,
                 FLOAT_ROWS=1500, FLOAT_COLS=12, TALL_ROWS=20_000,
                 MULTIHOST_ROWS=2000)
    for name, value in sizes.items():
        monkeypatch.setattr(qs, name, value)
    # The multi-host demo spawns the launcher, which must find the package.
    monkeypatch.setenv("PYTHONPATH", str(_ROOT / "src"))
    out = qs.main()
    printed = capsys.readouterr().out

    assert out["auto"] == "conventional"
    # Layout invariance: both encodings select the same features.
    assert out["conventional"] == out["alternative"]
    assert len(set(out["conventional"]) & set(range(9))) >= 5
    for crit in ("miq", "jmi", "cmim"):
        assert len(out[crit]) == 6 and len(set(out[crit])) == 6
    binned_mem, binned_str = out["binned"]
    assert binned_mem == binned_str
    assert set(binned_mem) == {0, 1, 2, 3}
    plain, fast = out["io_passes"]
    assert plain == 6 and fast < plain
    want = MRMRSelector(num_select=6).fit(CorralSource(3000, 16, seed=0))
    assert out["service"] == want.selected_.tolist()
    assert out["service_cache_hit"] is True
    X, y = corral_dataset_np(2000, 24, seed=0)
    ref = mrmr_reference(jnp.asarray(X.T), jnp.asarray(y), 4, MIScore(2, 2))
    assert out["multihost"]["selected"] == np.asarray(ref.selected).tolist()
    assert f"selected {out['conventional']}" in printed
    assert "resubmission cache_hit=True" in printed
    assert "per-host share of bytes read: [0.5, 0.5]" in printed


def test_custom_score(monkeypatch, capsys):
    cs = _load("custom_score")
    monkeypatch.setattr(cs, "N_OBS", 500)
    monkeypatch.setattr(cs, "N_FEAT", 512)
    selected = cs.main()
    printed = capsys.readouterr().out

    # The paper's Listing 8 written as a CustomScore is the built-in
    # Pearson-MI score, so both select the same features.
    assert selected["Listing 8 (custom)"] == selected["built-in PearsonMI"]
    for name, sel in selected.items():
        assert len(sel) == cs.K and len(set(sel)) == cs.K, name
        assert len(set(sel) & set(range(8))) >= 6, (name, sel)
        assert f"{name}: selected {sel}" in printed
    assert printed.count("(encoding=alternative)") == 3


def test_feature_selection_pipeline(monkeypatch, capsys):
    fsp = _load("feature_selection_pipeline")
    monkeypatch.setattr(fsp, "N_OBS", 500)
    monkeypatch.setattr(fsp, "N_FEAT", 2048)
    monkeypatch.setattr(fsp, "K", 8)
    out = fsp.main()
    printed = capsys.readouterr().out

    assert "planned encoding: alternative" in printed
    # The signal sits in columns 0..7 of the synthetic table.
    assert sorted(out["selected"].tolist()) == list(range(8))
    assert out["acc_sel"] > out["acc_rnd"] + 0.05
    assert out["acc_sel"] >= out["acc_all"]
    for acc in ("acc_all", "acc_sel", "acc_rnd"):
        assert 0.0 <= out[acc] <= 1.0
    assert f"8 mRMR features:     {out['acc_sel']:.3f}" in printed

