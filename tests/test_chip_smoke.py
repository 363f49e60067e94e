"""``chip_smoke.py``: refuses to run without a TPU, and its phases agree
with each other at mini size on the CPU."""
from __future__ import annotations

import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


def _run_script(cwd: Path, script: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, str(script)], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


@pytest.mark.parametrize("alone", [False, True], ids=["in_repo", "alone"])
def test_fails_without_tpu(tmp_path, alone):
    """On the CPU, and in a directory that holds nothing of the repo but
    the script, it exits non-zero and prints no result."""
    script = SCRIPT
    if alone:
        script = tmp_path / SCRIPT.name
        shutil.copy(SCRIPT, script)
    proc = _run_script(script.parent, script)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_phases_agree_at_mini_size(chip_smoke, tmp_path):
    """Every phase at the mini pems_sf shape: the decode tile called in
    interpret mode, and served payloads on the CPU's own decode path, all
    within the stated tolerance of the f32 oracle, with no failed ticket
    and no excluded instance."""
    rep = chip_smoke.run(seed=3, mini=True, interpret=True, out_dir=tmp_path)
    assert rep["data_shape"] == (96, 48, 56)
    assert rep["loss_last"] < rep["loss_first"]
    assert rep["served_impls"] == ["oracle"]  # the tile's CPU path
    assert set(rep["max_err_over_std"]) == {
        f"{s}.{k}" for s in ("untiled", "tiled", "fleet") for k in ("decode_at", "submit")
    } | {"direct.tile"}
    assert max(rep["max_err_over_std"].values()) <= chip_smoke.TOL
    assert rep["failed_tickets"] == 0 and rep["excluded_instances"] == []

