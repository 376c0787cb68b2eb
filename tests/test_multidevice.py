"""Launch the multi-device semantics suites as subprocesses.

The main pytest process must keep ONE device (the 512-device flag is
reserved for the dry-run), so every multi-device test runs in a child
process with ``--xla_force_host_platform_device_count=8``.
"""

import os
import pathlib
import subprocess
import sys

import pytest

_HERE = pathlib.Path(__file__).parent
_SRC = str(_HERE.parent / "src")


def _run(script: str) -> None:
    proc = subprocess.run(
        [sys.executable, str(_HERE / "multidevice" / script)],
        capture_output=True,
        text=True,
        env={**os.environ, "PYTHONPATH": _SRC, "JAX_PLATFORMS": "cpu"},
        timeout=900,
    )
    if proc.returncode != 0:
        pytest.fail(
            f"{script} failed\n--- stdout ---\n{proc.stdout[-4000:]}"
            f"\n--- stderr ---\n{proc.stderr[-4000:]}"
        )


@pytest.mark.slow
def test_multidevice_mrmr():
    _run("md_mrmr.py")


@pytest.mark.slow
def test_multidevice_train_checkpoint_elastic():
    _run("md_train.py")


@pytest.mark.slow
def test_multidevice_grad_compression():
    _run("md_compression.py")


@pytest.mark.slow
def test_multidevice_moe_exactness():
    _run("md_moe.py")


@pytest.mark.slow
def test_multidevice_pipeline_parallelism():
    _run("md_pipeline.py")
