"""The harness finds configurations, traffic mixes, runners and per-layer
metrics by name, from files of their own; a new file and a new
BENCHMARK.json entry add one with no edit to an existing file."""
import json
import shutil
import subprocess
import sys

import pytest
from bench import harness
from bench_testing import ROOT


def test_every_named_piece_has_its_file():
    bench = harness.load_benchmark()
    names = {c["name"] for c in bench["configs"]}
    for cell in bench["workloads"]:
        assert cell["config"] in names
        traffic = harness.traffic_of(cell["traffic"])
        assert callable(harness.runner_of(traffic["kind"]))
        harness.config_of(bench, cell["config"])
    for metric in bench["per_layer"]:
        assert callable(harness.reader_of(metric["name"]))


def test_each_cell_reports_setup_another_end_to_end_and_a_layer_metric():
    bench = harness.load_benchmark()
    for cell in bench["workloads"]:
        e2e = {m["name"] for m in harness.end_to_end_of(bench, cell["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2, cell["name"]
        layer = harness.per_layer_of(bench, cell["name"])
        assert layer, cell["name"]
        assert all(m["moves"] in e2e for m in layer), cell["name"]


def test_a_new_metric_mix_and_config_are_found_from_new_files(tmp_path):
    base = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", base)
    (base / "layer_metrics" / "new_metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    (base / "traffic" / "new_mix.json").write_text(
        json.dumps({"kind": "closed_slices", "slice": ["pick", "all", "all"],
                    "check_fraction": 0.5}))
    cfg = json.loads((base / "configs" / "pems_sf-medium.json").read_text())
    (base / "configs" / "new_config.json").write_text(json.dumps(dict(cfg, name="new_config")))
    bench = harness.load_benchmark()
    bench["configs"].append({"name": "new_config", "file": "bench/configs/new_config.json"})
    bench["workloads"].append({"name": "new_config.new_mix", "config": "new_config",
                               "traffic": "new_mix", "chips": 1})
    bench["per_layer"].append({"name": "new_metric", "moves": "read_entries_per_s",
                               "workloads": ["new_config.new_mix"]})
    assert harness.reader_of("new_metric", base)(None) == 42.0
    assert harness.traffic_of("new_mix", base)["kind"] == "closed_slices"
    assert harness.config_of(bench, "new_config", tmp_path)["name"] == "new_config"
    assert [m["name"] for m in harness.per_layer_of(bench, "new_config.new_mix")] == ["new_metric"]
    # no file of the benchmark itself changed
    for path in (ROOT / "bench").rglob("*.py"):
        assert (base / path.relative_to(ROOT / "bench")).read_bytes() == path.read_bytes()


def test_a_metric_without_workloads_goes_to_every_cell_reporting_what_it_moves():
    bench = harness.load_benchmark()
    bench["per_layer"].append({"name": "x", "moves": "fit_entries_per_s"})
    fits = [c["name"] for c in bench["workloads"]
            if any(m["name"] == "x" for m in harness.per_layer_of(bench, c["name"]))]
    assert fits == ["nyc-small.stream_fit"]


def test_a_listed_metric_that_reads_nothing_is_named():
    from bench import trace_reduce

    bench = harness.load_benchmark()
    cell = "pems_sf-medium.bulk_reads"
    trace = trace_reduce.TraceSummary(window_s=1.0, busy_s=0.25, n_devices=1,
                                      programs={"jit_other": (3, 0.2)}, ops={}, idle_gaps={})
    cfg = harness.config_of(bench, "pems_sf-medium")
    ctx = harness.Context(cfg, {}, {"entries": 63360, "elapsed": 1.0}, trace,
                          harness.peak_of("TPU v5 lite"), [])
    metrics, unread = harness.layer_metrics(bench, cell, ctx)
    assert unread == ["decode_tile_roofline"]  # no decode-tile program in this trace
    assert metrics["device_idle.read_rate"]["value"] == 75.0
    assert set(metrics) | set(unread) == {m["name"] for m in harness.per_layer_of(bench, cell)}


def test_unknown_names_are_errors():
    bench = harness.load_benchmark()
    with pytest.raises(KeyError):
        harness.cell_of(bench, "no-such-cell")
    with pytest.raises(FileNotFoundError):
        harness.reader_of("no_such_metric")


def _run_cli(cwd, env_extra=None):
    import os

    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "pems_sf-medium.bulk_reads",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240,
    )


def test_cli_refuses_a_backend_that_is_not_a_tpu():
    proc = _run_cli(ROOT)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert "{" not in proc.stdout


def test_cli_fails_without_the_program(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    proc = _run_cli(tmp_path)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
