"""Helpers of the benchmark's own tests: configurations cut to a CPU-sized
shape with their widths kept."""
from __future__ import annotations

from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
MINI_SHAPES = {"pems_sf": (96, 48, 56), "nyc": (48, 48, 24, 12)}


def mini_config(name: str) -> dict:
    """A configuration at its dataset's CPU-sized shape."""
    from bench import harness, reference

    cfg = harness.config_of(harness.load_benchmark(), name)
    shape = MINI_SHAPES[cfg["dataset"]["name"]]
    return dict(cfg, dataset=dict(cfg["dataset"], shape=list(shape)),
                folded_shape=list(reference.Folding(shape, cfg["d_prime"]).folded_shape))


def mini_cell(cell_name: str) -> tuple[dict, dict]:
    """(config, traffic) of a cell of BENCHMARK.json at a CPU-sized shape."""
    from bench import harness

    cell = harness.cell_of(harness.load_benchmark(), cell_name)
    return mini_config(cell["config"]), harness.traffic_of(cell["traffic"])
