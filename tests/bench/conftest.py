"""Fixtures of the benchmark's own tests, and the repository root on the
path (for ``bench``)."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


@pytest.fixture
def fused_decode(monkeypatch):
    """Served decodes on the one-program path, as on the chip (on the CPU
    it is the jitted oracle in place of the Pallas tile)."""
    monkeypatch.setenv("REPRO_DECODE_IMPL", "fused")


@pytest.fixture
def work_dir(tmp_path, monkeypatch):
    from bench import harness

    monkeypatch.setattr(harness, "WORK_DIR", tmp_path)
    return tmp_path
