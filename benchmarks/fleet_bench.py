"""Fleet serving benchmark: throughput + tail latency vs instance count,
and cache hit rates across a live rebalance.

Serves the checked-in chunked payload (``results/fig5_stream_payload.tcdc``,
regenerated by ``fig5_compress_scaling --stream``; a TT payload is built
on the fly if it is missing) through ``repro.fleet`` at instance counts
1/2/4 (``--smoke``: 3, the CI cell), verifying every batch bit-identical
against a single resident ``CodecService``.  Then the largest fleet loses
one instance mid-query-stream — the report records hit rates before and
after the rebalance (warm tile handoff should keep the after-rate close),
chunks/tiles moved, and that ZERO tickets failed.

``--procs N`` runs the same protocol over N real OS-process workers
(``repro.fleet.worker`` behind ``SocketTransport``) instead of in-process
services — the multi-process cell CI gates on: every batch must still be
bit-identical to the single resident instance, across the process
boundary and across a live rebalance that terminates one worker.

The fused-path cells serve an NTTD payload, whose served decodes run
one compiled program per decode tile (the Pallas kernel on a TPU, the
jitted oracle elsewhere), and measure the COLD pass — the only pass
where tile decode compute exists to overlap — with chunk/tile
prefetching off and on.  Answers must be bit-identical across prefetch
settings and against the single resident service; the prefetch-on cell
lands in ``runs`` for ``check_bench`` to gate.

Results land in ``results/BENCH_fleet.json`` (in-process) or
``results/BENCH_fleet_procs.json`` (``--procs``) so CI tracks both fleet
trajectories from PR to PR, like BENCH_stream.json does for streaming.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

from benchmarks.common import RESULTS_DIR, emit
from repro.compile_cache import enable_compile_cache
from repro.fleet import FleetFrontend, SocketTransport, collect, rebalance
from repro.serve.codec_service import CodecService

PAYLOAD = os.path.join(RESULTS_DIR, "fig5_stream_payload.tcdc")
NTTD_PAYLOAD = os.path.join(RESULTS_DIR, "fleet_nttd_payload.tcdc")


def _ensure_payload() -> str:
    if os.path.exists(PAYLOAD):
        return PAYLOAD
    from repro.codecs import get_codec
    from repro.stream import write_chunked

    rng = np.random.default_rng(0)
    x = rng.random((64, 32, 32)).astype(np.float32)
    enc = get_codec("ttd").fit(x, max_rank=6)
    os.makedirs(RESULTS_DIR, exist_ok=True)
    write_chunked(PAYLOAD, enc, chunk_bytes=2048)
    return PAYLOAD


def _ensure_nttd_payload() -> str:
    """A small NTTD payload for the fused-decode serving cells (training
    quality is irrelevant here — only the decode path is under test)."""
    if os.path.exists(NTTD_PAYLOAD):
        return NTTD_PAYLOAD
    from repro.codecs import get_codec
    from repro.stream import sample_heldout, write_chunked

    rng = np.random.default_rng(1)
    x = rng.random((32, 24, 16)).astype(np.float32)
    enc = get_codec("nttd").fit(
        x, rank=8, hidden=16, epochs=2, init_reorder=False,
        update_reorder=False, batch_size=4096, eval_batch=4096,
    )
    os.makedirs(RESULTS_DIR, exist_ok=True)
    # the TCDQ held-out block is what lets the canary cells (obs_bench)
    # and a canary-on fleet score served answers online
    write_chunked(NTTD_PAYLOAD, enc, chunk_bytes=2048,
                  heldout=sample_heldout(x, 256, seed=1))
    return NTTD_PAYLOAD


def _batches(shape, n_batches, batch, seed=0):
    rng = np.random.default_rng(seed)
    return [
        np.stack([rng.integers(0, s, batch) for s in shape], axis=1)
        for _ in range(n_batches)
    ]


def _pass_stats(fleet, name, batches, reference=None):
    """One query pass; returns (seconds, pass hit rate, pass p99 ms) —
    hit rate and p99 cover ONLY this pass's flushes, so a warm pass is
    not polluted by the cold pass's jit compile."""
    before = collect(fleet).fleet
    seen = {iid: fleet.flush_count(iid) for iid in fleet.instances()}
    t0 = time.perf_counter()
    for k, idx in enumerate(batches):
        out = fleet.decode_at(name, idx)
        if reference is not None:
            assert np.array_equal(out, reference[k]), "fleet drifted from single"
    dt = time.perf_counter() - t0
    after = collect(fleet).fleet
    hits = after.hits - before.hits
    misses = after.misses - before.misses
    rate = hits / max(hits + misses, 1)
    lat = []
    for iid in fleet.instances():
        new = fleet.flush_count(iid) - seen.get(iid, 0)
        if new:  # last `new` samples = this pass (window may have wrapped)
            lat.extend(fleet.latency_seconds(iid)[-new:])
    p99 = float(np.percentile(np.asarray(lat), 99)) * 1e3 if lat else None
    return dt, rate, p99


def _make_fleet(n: int, procs: bool, prefetch: bool = False) -> FleetFrontend:
    if not procs:
        return FleetFrontend(n, prefetch=prefetch)
    return FleetFrontend(
        [f"w{k}" for k in range(n)],
        transport_factory=lambda iid: SocketTransport.spawn(iid, prefetch=prefetch),
    )


def _fused_prefetch_cells(
    n: int, procs: bool, smoke: bool
) -> list[dict]:
    """Cold-pass cells on the NTTD payload, decoded fused, prefetch off/on.

    Only the cold pass exercises tile decode compute (the warm pass is all
    cache hits), so it is the only pass where I/O overlap can show.  Both
    cells must be bit-identical to the single resident reference."""
    path = _ensure_nttd_payload()
    probe = CodecService()
    probe.load_stream("nttd", path)
    shape = probe.shape_of("nttd")
    n_entries = int(np.prod(shape))
    tile_entries = max(n_entries // 64, 64)
    n_batches, batch = (8, 512) if smoke else (24, 2048)
    batches = _batches(shape, n_batches, batch, seed=7)
    single = CodecService()
    single.load_stream("nttd", path, tile_entries=tile_entries)
    reference = [single.decode_at("nttd", idx) for idx in batches]

    # throwaway cold pass: absorb one-time process state (jit compiles,
    # page cache, allocator growth) so the off/on cells — which run in
    # a fixed order — compare prefetching, not process warm-up
    warm = _make_fleet(n, procs)
    warm.load_stream("nttd", path, tile_entries=tile_entries)
    for idx in batches:
        warm.decode_at("nttd", idx)
    warm.close()

    cells = []
    outputs: dict[bool, list[np.ndarray]] = {}
    for pf in (False, True):
        fleet = _make_fleet(n, procs, prefetch=pf)
        fleet.load_stream("nttd", path, tile_entries=tile_entries)
        outs = []
        t0 = time.perf_counter()
        for idx in batches:  # COLD: every tile decodes exactly once
            outs.append(fleet.decode_at("nttd", idx))
        dt = time.perf_counter() - t0
        fleet.close()
        outputs[pf] = outs
        for out, ref in zip(outs, reference):
            assert np.array_equal(out, ref), "fused fleet drifted from single"
        eps = n_batches * batch / dt
        cells.append({
            "instances": n,
            "payload": "nttd",
            "decode_impl": "fused",
            "pass": "cold",
            "prefetch": pf,
            "entries_per_sec": round(eps, 1),
            "p99_ms": None,
        })
        emit(
            f"fleet_fused_cold_prefetch{'_on' if pf else '_off'}",
            dt * 1e6 / n_batches,
            f"entries_per_sec={eps:.0f};bit_identical=True",
        )
    for a, b in zip(outputs[False], outputs[True]):
        assert np.array_equal(a, b), "prefetch changed answers"
    off, on = (c["entries_per_sec"] for c in cells)
    emit("fleet_fused_prefetch_speedup", 0.0, f"{on / off:.3f}x")
    return cells


def run(smoke: bool = False, procs: int | None = None) -> None:
    path = _ensure_payload()
    probe = CodecService()
    probe.load_stream("payload", path)
    shape = probe.shape_of("payload")
    n_entries = int(np.prod(shape))
    tile_entries = max(n_entries // 128, 64)
    single = CodecService()
    single.load_stream("payload", path, tile_entries=tile_entries)

    if procs is not None:
        counts = [procs]
    else:
        counts = [3] if smoke else [1, 2, 4]
    n_batches, batch = (8, 512) if smoke else (32, 2048)
    batches = _batches(shape, n_batches, batch)
    reference = [single.decode_at("payload", idx) for idx in batches]
    label = "procs" if procs is not None else "n"

    runs = []
    largest_fleet = None
    for n in counts:
        fleet = _make_fleet(n, procs is not None)
        fleet.load_stream("payload", path, tile_entries=tile_entries)
        _pass_stats(fleet, "payload", batches, reference)   # cold + verify
        dt, hit_rate, p99 = _pass_stats(fleet, "payload", batches, reference)
        eps = n_batches * batch / dt
        m = collect(fleet)
        runs.append({
            "instances": n,
            "entries_per_sec": round(eps, 1),
            "p99_ms": round(p99, 4) if p99 is not None else None,
            "warm_hit_rate": round(hit_rate, 4),
            "resident_bytes_per_instance": [
                m.instances[iid].cache.resident_bytes
                for iid in sorted(m.instances)
            ],
        })
        emit(f"fleet_{label}{n}", dt * 1e6 / n_batches,
             f"entries_per_sec={eps:.0f};p99_ms={p99:.3f};hit_rate={hit_rate:.3f}")
        if largest_fleet is not None:
            largest_fleet.close()
        largest_fleet = fleet

    # live rebalance on the largest fleet: drop one instance mid-stream
    # (with --procs that terminates a real worker process)
    fleet = largest_fleet
    victim = fleet.instances()[-1]
    _, rate_before, _ = _pass_stats(fleet, "payload", batches, reference)
    pending = [fleet.submit("payload", idx) for idx in batches[:2]]
    report = rebalance(fleet, remove=[victim])
    resolved = fleet.flush()
    assert not fleet.failed, f"rebalance failed tickets: {fleet.failed}"
    for t, ref in zip(pending, reference[:2]):
        assert np.array_equal(resolved[t], ref), "mid-rebalance ticket drifted"
    _, rate_after, _ = _pass_stats(fleet, "payload", batches, reference)
    fleet.close()
    rebalance_record = {
        "removed": victim,
        "hit_rate_before": round(rate_before, 4),
        "hit_rate_after": round(rate_after, 4),
        "chunks_moved": report.chunks_moved,
        "tiles_moved": report.tiles_moved,
        "tiles_warmed": report.tiles_warmed,
        "bytes_dropped": report.bytes_dropped,
        "failed_tickets": 0,
    }
    emit("fleet_rebalance", 0.0,
         f"hit_before={rate_before:.3f};hit_after={rate_after:.3f};"
         f"moved={report.total_moved};warmed={sum(report.tiles_warmed.values())}")

    # fused-decode serving cells (cold pass, prefetch off/on) ride in the
    # same runs list; rows are tagged so check_bench can gate them apart
    # from the warm ttd cells above
    runs.extend(_fused_prefetch_cells(counts[-1], procs is not None, smoke))

    fname = "BENCH_fleet_procs.json" if procs is not None else "BENCH_fleet.json"
    out = os.path.join(RESULTS_DIR, fname)
    with open(out, "w") as f:
        json.dump({
            "mode": "smoke" if smoke else "default",
            "transport": "socket" if procs is not None else "local",
            "payload": os.path.basename(path),
            "shape": list(shape),
            "tile_entries": tile_entries,
            "batches": n_batches,
            "batch_entries": batch,
            "runs": runs,
            "rebalance": rebalance_record,
        }, f, indent=2)
    emit("fleet_json", 0.0, out)


if __name__ == "__main__":
    enable_compile_cache()
    procs = None
    if "--procs" in sys.argv:
        procs = int(sys.argv[sys.argv.index("--procs") + 1])
    run(smoke="--smoke" in sys.argv, procs=procs)
