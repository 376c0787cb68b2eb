"""Run the multi-device semantics suite as a subprocess.

The main pytest process keeps ONE device, so the multi-device checks run
in a child process with ``--xla_force_host_platform_device_count=8``.
The child runs once per module and reports every check by name; each
check is one case here.
"""

import importlib.util
import os
import pathlib
import re
import subprocess
import sys

import pytest

_HERE = pathlib.Path(__file__).parent
_SRC = str(_HERE.parent / "src")
_SCRIPT = _HERE / "multidevice" / "md_mrmr.py"


def _check_names() -> list:
    """The names in the script's ``CHECKS``, read without running them."""
    spec = importlib.util.spec_from_file_location("md_mrmr", _SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return list(mod.CHECKS)


@pytest.fixture(scope="module")
def md_mrmr_report():
    """-> (completed process, {check name: its lines of output})."""
    proc = subprocess.run(
        [sys.executable, str(_SCRIPT)],
        capture_output=True,
        text=True,
        env={**os.environ, "PYTHONPATH": _SRC, "JAX_PLATFORMS": "cpu"},
        timeout=900,
    )
    # Each check's report runs from its CHECK line to the next one.
    parts = re.split(r"^(?=CHECK \S+ (?:OK|FAIL)$)", proc.stdout, flags=re.M)
    reports = {}
    for part in parts:
        m = re.match(r"CHECK (\S+) (OK|FAIL)$", part, flags=re.M)
        if m:
            reports[m.group(1)] = part
    return proc, reports


@pytest.mark.slow
@pytest.mark.parametrize("check", _check_names())
def test_multidevice_mrmr(md_mrmr_report, check):
    proc, reports = md_mrmr_report
    report = reports.get(check)
    if report is None or not report.startswith(f"CHECK {check} OK"):
        pytest.fail(
            f"md_mrmr.py check {check!r} "
            f"{'did not report' if report is None else 'failed'} "
            f"(exit {proc.returncode})\n--- report ---\n{(report or '')[-4000:]}"
            f"\n--- stderr ---\n{proc.stderr[-4000:]}"
        )
