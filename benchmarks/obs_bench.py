"""Tracing-overhead benchmark: the ``repro.obs`` cost contract, measured.

Serves the NTTD payload through ONE fleet over the same batch sequence
with tracing toggled between passes (fused decode, so the traced passes
carry the full span stack: frontend → transport → service stages →
``kernel_decode``) and reports the traced slowdown as a percentage.
Answers must be bit-identical across traced and untraced passes (tracing
is observational only) and the overhead must stay under the gate CI
enforces (``obs.traced_overhead_pct`` <= 10 in ``check_bench``).

Untraced/traced passes ALTERNATE on the same warm fleet and the MEDIAN
wall time per mode is compared — the quantity under test (a hundred-odd
spans of bookkeeping, well under a millisecond) is far smaller than the
scheduler noise on any single pass, so interleaving cancels slow drift
and the median (unlike min-of-N, whose extremes are themselves noise
samples) converges on the true per-mode cost as repeats grow.

The traced run's spans land in ``results/obs_trace.json`` (Chrome
trace-event format with the fleet metrics snapshot embedded — the CI
artifact, loadable in Perfetto and summarized by
``python -m repro.obs.report``).

A second pair of cells measures the ONLINE FITNESS CANARY cost the same
way (two warm fleets, canaries off vs sampling ``CANARY_FRACTION`` of
decode calls against the payload's TCDQ held-out block): answers must
again be bit-identical and ``canary_overhead_pct`` joins the bench gate
at an absolute 10%% ceiling.

    python -m benchmarks.obs_bench --smoke        # the CI cell
    python -m benchmarks.obs_bench --procs 3      # real worker processes
"""
from __future__ import annotations

import json
import os
import statistics
import sys
import time

import numpy as np

from benchmarks.common import RESULTS_DIR, emit
from benchmarks.fleet_bench import _batches, _ensure_nttd_payload
from repro import obs
from repro.compile_cache import enable_compile_cache
from repro.fleet import FleetFrontend, SocketTransport, collect

TRACE_OUT = os.path.join(RESULTS_DIR, "obs_trace.json")


def _make_fleet(n: int, procs: bool) -> FleetFrontend:
    if procs:
        return FleetFrontend(
            [f"w{k}" for k in range(n)],
            transport_factory=lambda iid: SocketTransport.spawn(iid, timeout=60.0),
        )
    return FleetFrontend(n)


def _pass(fleet, batches) -> tuple[float, list[np.ndarray]]:
    t0 = time.perf_counter()
    outs = [fleet.decode_at("nttd", idx) for idx in batches]
    return time.perf_counter() - t0, outs


#: sampling fraction for the canary cells.  A check costs one extra
#: ~2ms decode DISPATCH (entry count is irrelevant at held-out sizes),
#: which the smoke cells' ~1ms flushes cannot hide — so the bench
#: samples sparsely; production fractions amortize over real batches.
CANARY_FRACTION = 0.02


def _canary_cells(path, batches, tile_entries, repeats):
    """Canary-overhead cells: the same interleaved-median methodology as
    the tracing cells, except the canary knob is a constructor parameter,
    so the modes alternate ACROSS two otherwise-identical warm in-process
    fleets instead of toggling one.  Answers must be bit-identical
    (canary decodes are pure extra reads) and the online checks must
    actually fire (the payload carries a TCDQ held-out block).

    Returns (overhead_pct, checks, eps_off, eps_on)."""
    fleets: dict[bool, FleetFrontend] = {}
    for on in (False, True):
        f = FleetFrontend(3, canary_fraction=CANARY_FRACTION if on else 0.0)
        f.load_stream("nttd", path, tile_entries=tile_entries)
        _pass(f, batches)  # warm-up (jit, materialization, tile fill)
        fleets[on] = f
    try:
        times: dict[bool, list[float]] = {False: [], True: []}
        results: dict[bool, list[np.ndarray]] = {}

        def _round() -> None:
            for _ in range(repeats):
                for on in (False, True):
                    dt, outs = _pass(fleets[on], batches)
                    times[on].append(dt)
                    if on not in results:
                        results[on] = outs

        def _overhead() -> float:
            off = statistics.median(times[False])
            on_t = statistics.median(times[True])
            return (on_t - off) / off * 100

        _round()
        if _overhead() > 10.0:
            # same pooled re-round policy as the tracing cells: the
            # medians converge on the true (few-percent) cost
            _round()
        for a, b in zip(results[False], results[True]):
            assert np.array_equal(a, b), "canaries changed answers"
        canary = collect(fleets[True]).canary
        checks = canary.get("nttd", {}).get("checks", 0)
        assert checks > 0, "canary never sampled a served batch"
        assert canary["nttd"]["rolling_fitness"] > 0.0
        n_entries = len(batches) * len(batches[0])
        return (
            _overhead(),
            checks,
            n_entries / statistics.median(times[False]),
            n_entries / statistics.median(times[True]),
        )
    finally:
        for f in fleets.values():
            f.close()


def run(smoke: bool = False, procs: int | None = None) -> None:
    path = _ensure_nttd_payload()
    n = procs if procs is not None else 3
    n_batches, batch, repeats = (16, 2048, 15) if smoke else (24, 4096, 21)
    rec = obs.get_recorder()
    try:
        probe = FleetFrontend(1)
        probe.load_stream("nttd", path)
        shape = probe.routes["nttd"].shape
        probe.close()
        tile_entries = max(int(np.prod(shape)) // 64, 64)
        batches = _batches(shape, n_batches, batch, seed=11)

        obs.disable_tracing()
        fleet = _make_fleet(n, procs is not None)
        try:
            fleet.load_stream("nttd", path, tile_entries=tile_entries)
            # warm-up: one untraced pass (jit, materialization) and one
            # traced pass (span code paths, worker-side lazy enable)
            _pass(fleet, batches)
            obs.enable_tracing()
            _pass(fleet, batches)
            rec.clear()

            times: dict[bool, list[float]] = {False: [], True: []}
            results: dict[bool, list[np.ndarray]] = {}

            def _round() -> None:
                for rep in range(repeats):
                    for traced in (False, True):
                        if traced:
                            # start each traced pass from an empty ring so
                            # every rep pays the same bookkeeping (a filling
                            # ring grows the GC's survivor set, which would
                            # drift later traced passes slower)
                            rec.clear()
                            obs.enable_tracing()
                        else:
                            obs.disable_tracing()
                        dt, outs = _pass(fleet, batches)
                        times[traced].append(dt)
                        if traced not in results:
                            results[traced] = outs

            def _overhead() -> float:
                off = statistics.median(times[False])
                on = statistics.median(times[True])
                return (on - off) / off * 100

            _round()
            if _overhead() > 10.0:
                # one pooled re-round before declaring failure: the medians
                # converge on the true cost (a few percent), so a first
                # estimate past the gate is noise more often than signal
                _round()
            overhead_pct = _overhead()
            # the last traced pass's spans + the metrics snapshot become
            # the CI trace artifact
            trace_spans = rec.snapshot()
            trace_metrics = collect(fleet).as_dict()
        finally:
            fleet.close()
            obs.disable_tracing()

        for a, b in zip(results[False], results[True]):
            assert np.array_equal(a, b), "tracing changed answers"
        best = {traced: statistics.median(ts) for traced, ts in times.items()}
        assert trace_spans, "traced run recorded no spans"
        n_spans = obs.export_chrome_trace(
            TRACE_OUT, spans=trace_spans, metrics=trace_metrics
        )
        # the artifact must be a loadable Chrome trace-event file
        with open(TRACE_OUT) as f:
            doc = json.load(f)
        assert doc["traceEvents"] and all(
            "ph" in ev for ev in doc["traceEvents"]
        )

        # canary cells run untraced and in-process either way — the knob
        # under test is the online fitness check, not the transport
        canary_pct, canary_checks, canary_eps_off, canary_eps_on = (
            _canary_cells(path, batches, tile_entries, repeats)
        )

        eps_off = n_batches * batch / best[False]
        eps_on = n_batches * batch / best[True]
        emit("obs_untraced", best[False] * 1e6 / n_batches,
             f"entries_per_sec={eps_off:.0f}")
        emit("obs_traced", best[True] * 1e6 / n_batches,
             f"entries_per_sec={eps_on:.0f};spans={n_spans}")
        emit("obs_traced_overhead", 0.0,
             f"overhead_pct={overhead_pct:.2f};bit_identical=True")
        emit("obs_canary_overhead", 0.0,
             f"overhead_pct={canary_pct:.2f};checks={canary_checks};"
             f"fraction={CANARY_FRACTION};bit_identical=True")

        out = os.path.join(RESULTS_DIR, "BENCH_obs.json")
        with open(out, "w") as f:
            json.dump({
                "mode": "smoke" if smoke else "default",
                "transport": "socket" if procs is not None else "local",
                "batches": n_batches,
                "batch_entries": batch,
                "repeats": repeats,
                "trace_file": os.path.basename(TRACE_OUT),
                "runs": [{
                    "instances": n,
                    "payload": "nttd",
                    "decode_impl": "fused",
                    "untraced_entries_per_sec": round(eps_off, 1),
                    "traced_entries_per_sec": round(eps_on, 1),
                    "traced_spans": n_spans,
                    "traced_overhead_pct": round(overhead_pct, 2),
                    "canary_fraction": CANARY_FRACTION,
                    "canary_checks": canary_checks,
                    "canary_entries_per_sec_off": round(canary_eps_off, 1),
                    "canary_entries_per_sec_on": round(canary_eps_on, 1),
                    "canary_overhead_pct": round(canary_pct, 2),
                }],
            }, f, indent=2)
        emit("obs_json", 0.0, out)
        # the same bound check_bench enforces, asserted at the source.
        # Only the in-process cell (what CI runs) carries the budget:
        # over sockets each flush additionally ships its span block, a
        # per-flush wire cost these tiny smoke batches cannot amortize.
        if procs is None:
            assert overhead_pct <= 10.0, (
                f"tracing overhead {overhead_pct:.2f}% exceeds the 10% budget"
            )
        # the canary cells are in-process in every mode, so their budget
        # always holds at the source (check_bench re-gates it in CI)
        assert canary_pct <= 10.0, (
            f"canary overhead {canary_pct:.2f}% exceeds the 10% budget"
        )
    finally:
        os.environ.pop("REPRO_TRACE", None)
        obs.disable_tracing()
        obs.get_recorder().clear()


if __name__ == "__main__":
    enable_compile_cache()
    procs = None
    if "--procs" in sys.argv:
        procs = int(sys.argv[sys.argv.index("--procs") + 1])
    run(smoke="--smoke" in sys.argv, procs=procs)
