"""The general harness: finds a cell's configuration, traffic mix and
per-layer metrics by name, runs set-up, the measured window and the
correctness check, and assembles the result line.

Nothing here names a cell.  A traffic file's ``kind`` names the runner in
``kinds/<kind>.py`` that runs it; a per-layer metric is read by
``layer_metrics/<metric>.py``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
WORK_DIR = ROOT / ".bench_work"
TRACE_SECONDS = 4.0  # traced windows are short: traces are large and slow the host

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


# ------------------------------------------------------------ discovery
def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def cell_of(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    names = ", ".join(c["name"] for c in bench["workloads"])
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have: {names}")


def config_of(bench: dict, name: str, root: Path = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return load_json(root / c["file"])
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def traffic_of(name: str, base: Path = BENCH_DIR) -> dict:
    return load_json(base / "traffic" / f"{name}.json")


def _load_module(path: Path, modname: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    if spec is None or not path.exists():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def runner_of(kind: str, base: Path = BENCH_DIR):
    """The runner class of a traffic kind, from ``kinds/<kind>.py``."""
    return _load_module(base / "kinds" / f"{kind}.py", f"bench_kind_{kind}").Runner


def reader_of(metric: str, base: Path = BENCH_DIR):
    """The ``read(ctx)`` function of a per-layer metric."""
    mod = _load_module(
        base / "layer_metrics" / f"{metric}.py",
        "bench_metric_" + metric.replace(".", "_"),
    )
    return mod.read


def _applies(metric: dict, cell: str, reported: set[str] | None = None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return reported is None or metric.get("moves") in reported


def end_to_end_of(bench: dict, cell: str) -> list[dict]:
    return [m for m in bench["end_to_end"] if _applies(m, cell)]


def per_layer_of(bench: dict, cell: str) -> list[dict]:
    reported = {m["name"] for m in end_to_end_of(bench, cell)}
    return [m for m in bench["per_layer"] if _applies(m, cell, reported)]


def peak_of(device_kind: str) -> dict:
    peaks = load_json(BENCH_DIR / "peaks.json")
    if device_kind not in peaks:
        raise KeyError(f"no peaks for device kind {device_kind!r} in bench/peaks.json")
    return peaks[device_kind]


# -------------------------------------------------------------- jax set-up
def enable_compile_cache() -> str:
    """The program's persistent compilation cache (a fixed path inside the
    checkout, or where ``JAX_COMPILATION_CACHE_DIR`` says), caching every
    program however small or quick to compile."""
    import jax

    from repro.compile_cache import enable_compile_cache as enable

    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


@contextlib.contextmanager
def compile_clock():
    """Count XLA backend compiles (JAX's monitoring event) inside the block;
    yields {"count", "seconds"}, filled as compiles happen."""
    import jax

    clock = {"count": 0, "seconds": 0.0}

    def on_duration(event: str, duration: float, **_kw) -> None:
        if event == _COMPILE_EVENT:
            clock["count"] += 1
            clock["seconds"] += duration

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        yield clock
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)


def annotate(name: str):
    """A host span in the profiler's trace (a no-op when not tracing)."""
    import jax

    return jax.profiler.TraceAnnotation(f"bench.{name}")


def say(key: str, value) -> None:
    print(f"{key}: {value}", file=sys.stderr, flush=True)


# --------------------------------------------------------------- the run
@dataclasses.dataclass
class Check:
    """One number compared with its limit; it passes when value <= limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit  # NaN fails


@dataclasses.dataclass
class Context:
    """What a per-layer reader sees."""

    config: dict
    traffic: dict
    stats: dict
    trace: object | None
    peak: dict
    spans: list


def run_cell(bench: dict, cell_name: str, seed: int, seconds: float, trace: bool,
             t_start: float, device, cfg: dict | None = None,
             traffic: dict | None = None) -> dict:
    """Set up, measure, check; returns the result object.  ``cfg`` and
    ``traffic`` stand in for the cell's own files (the tests run a cell at
    a CPU-sized shape)."""
    import jax

    cell = cell_of(bench, cell_name)
    cfg = cfg or config_of(bench, cell["config"])
    traffic = traffic or traffic_of(cell["traffic"])
    workdir = WORK_DIR / cell_name
    workdir.mkdir(parents=True, exist_ok=True)
    runner = runner_of(traffic["kind"])(cfg, traffic, seed, workdir)
    runner.setup()
    setup_s = time.perf_counter() - t_start
    say("setup_s", setup_s)

    trace_dir = workdir / "trace"
    spans: list = []
    with compile_clock() as compiles:
        if trace:
            from repro import obs

            shutil.rmtree(trace_dir, ignore_errors=True)
            obs.enable_tracing()
            obs.get_recorder().clear()
            with jax.profiler.trace(str(trace_dir)):
                with annotate("window"):
                    stats = runner.window(min(seconds, TRACE_SECONDS))
            spans = obs.get_recorder().drain()
            obs.disable_tracing()
        else:
            stats = runner.window(seconds)
    say("compiles_in_window", compiles["count"])
    if stats["failed"]:
        say("last_error", getattr(runner, "error", "unknown"))
    for key, value in runner.notes(stats).items():
        say(key, value)
    mem = device.memory_stats() or {}
    memory_peak = int(mem.get("peak_bytes_in_use", 0))
    runner.release()
    checks = runner.check()

    metrics: dict[str, dict] = {}
    unread: list[str] = []
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    result = {"correct": all(c.ok for c in checks),
              "attempted": stats["attempted"], "failed": stats["failed"]}
    if trace:
        from bench import trace_reduce

        summary = trace_reduce.reduce_dir(str(trace_dir))
        ctx = Context(cfg, traffic, stats, summary, peak_of(device.device_kind), spans)
        metrics, unread = layer_metrics(bench, cell_name, ctx)
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        values = {**stats, "setup_s": setup_s}
        for m in end_to_end_of(bench, cell_name):
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = dev
    # JSON has no infinity: a number that could not be read is null
    result["checks"] = {c.name: {"value": c.value if math.isfinite(c.value) else None,
                                 "limit": c.limit} for c in checks}
    for name, c in result["checks"].items():
        say(f"check {name}", f"{c['value']} limit {c['limit']}")
    if unread:
        # BENCHMARK.json lists these for this cell: a reader that finds
        # nothing here reads the wrong names, not an empty layer
        raise RuntimeError(f"no reading of {', '.join(unread)} in {cell_name}'s trace; "
                           f"programs seen: {sorted(summary.programs)}")
    return result


def layer_metrics(bench: dict, cell_name: str, ctx: Context) -> tuple[dict, list[str]]:
    """The cell's per-layer metrics read from ``ctx``, and the names of
    those whose reader found nothing to read."""
    metrics, unread = {}, []
    for m in per_layer_of(bench, cell_name):
        value = reader_of(m["name"])(ctx)
        if value is None:
            unread.append(m["name"])
        else:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics, unread
