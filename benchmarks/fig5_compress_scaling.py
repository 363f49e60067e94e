"""Fig. 5: compression time scales linearly with the number of entries.

Two modes:

* default — every codec in the ``repro.codecs`` registry is fit on
  synthetic full tensors of growing size under one budget protocol, and
  the per-codec log-log slope of wall time vs entries is reported
  (1.0 = linear, the paper's claim for TensorCodec).
* ``--stream`` — the headline scalability claim measured the honest way:
  ``fit_stream("nttd", ...)`` over a seeded ``SyntheticTensorSource``
  that computes slabs from indices, so the tensor is NEVER materialized.
  Entries/sec lands in ``results/BENCH_stream.json`` so CI tracks the
  streaming-throughput trajectory (``--smoke`` shrinks it to a CI-sized
  cell; REPRO_BENCH_FULL=1 grows it to 2^26 entries).
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

from benchmarks.common import (
    FULL,
    NTTD_FIT_OPTS,
    RESULTS_DIR,
    emit,
    save_rows,
    scaling_budget,
)
from repro.codecs import available, get_codec
from repro.compile_cache import enable_compile_cache

SIZES = [(16, 16, 16), (24, 24, 24), (32, 32, 32), (48, 48, 48)]
if FULL:
    SIZES += [(64, 64, 64), (96, 96, 96)]

NTTD_OPTS = {**NTTD_FIT_OPTS, "init_reorder": True}


def _nttd_epoch_seconds(codec, x) -> float:
    """Compile-excluded per-epoch seconds: fit at epochs=1 and epochs=5
    and difference.  The epoch count is a Python loop, not a traced shape,
    so jit compile, TSP init, and backend warm-up cancel exactly and what
    remains is the model-update + eval work the linear claim is about."""
    t0 = time.time()
    codec.fit(x, **{**NTTD_OPTS, "epochs": 1, "patience": 10})
    t1 = time.time() - t0
    t0 = time.time()
    codec.fit(x, **{**NTTD_OPTS, "epochs": 5, "patience": 10})
    t5 = time.time() - t0
    return max((t5 - t1) / 4, 1e-9)


def run() -> None:
    rows = []
    per_codec: dict[str, list[tuple[int, float]]] = {}
    for shape in SIZES:
        rng = np.random.default_rng(0)
        x = rng.random(shape).astype(np.float32)
        n = x.size
        budget = scaling_budget(n)
        for name in available():
            codec = get_codec(name)
            try:
                if name == "nttd":  # cold wall time is compile-dominated
                    dt = _nttd_epoch_seconds(codec, x)
                else:
                    t0 = time.time()
                    codec.fit(x, budget)
                    dt = time.time() - t0
            except ValueError as e:  # e.g. szlite floor above budget
                emit(f"fig5_{name}_n{n}", 0.0, f"skipped:{e}")
                continue
            if dt <= 1e-9:  # below timer resolution: would poison the slope
                emit(f"fig5_{name}_n{n}", 0.0, "skipped:below-timer-resolution")
                continue
            per_codec.setdefault(name, []).append((n, dt))
            rows.append([name, n, round(dt, 4)])
            emit(f"fig5_{name}_n{n}", dt * 1e6, f"seconds={dt:.3f}")
    for name, pts in per_codec.items():
        if len(pts) < 2:
            continue
        ns = np.log([p[0] for p in pts])
        ts = np.log([max(p[1], 1e-9) for p in pts])
        slope = float(np.polyfit(ns, ts, 1)[0])
        emit(f"fig5_{name}_loglog_slope", 0.0,
             f"slope={slope:.3f};linear_if~1")
    save_rows("fig5_compress_scaling.csv", ["codec", "entries", "seconds"], rows)


# ---------------------------------------------------------------------------
# streaming mode: the linear-time claim without materializing the tensor
# ---------------------------------------------------------------------------
def run_stream(smoke: bool = False) -> None:
    from repro.serve.codec_service import CodecService
    from repro.stream import SyntheticTensorSource, fit_stream, write_chunked

    if smoke:
        shapes = [(64, 32, 32)]                 # 2^16 entries, CI-sized
        slab_entries = 1 << 13
    else:
        shapes = [(256, 64, 64), (1024, 64, 64), (4096, 64, 64)]  # up to 2^24
        if FULL:
            shapes.append((16384, 64, 64))      # 2^26
        slab_entries = 1 << 18
    records = []
    for shape in shapes:
        src = SyntheticTensorSource(shape, slab_entries=slab_entries, seed=1)
        t0 = time.time()
        enc = fit_stream("nttd", src, rank=6, hidden=12, steps_per_slab=2,
                         batch_size=4096 if smoke else 8192, seed=0)
        dt = time.time() - t0
        eps = src.n_entries / dt
        # round-trip the payload through the chunked container + lazy serve
        path = os.path.join(RESULTS_DIR, "fig5_stream_payload.tcdc")
        os.makedirs(RESULTS_DIR, exist_ok=True)
        # small chunks so the checked-in payload has a multi-chunk index
        # (with entry ranges) for the fleet smoke to shard over
        write_chunked(path, enc, chunk_bytes=2048)
        svc = CodecService()
        svc.load_stream("stream", path)
        rng = np.random.default_rng(0)
        idx = np.stack([rng.integers(0, s, 128) for s in shape], axis=1)
        served = svc.decode_at("stream", idx)
        direct = np.asarray(enc.decode_at(idx))
        assert np.array_equal(served, direct), "load_stream round-trip drifted"
        records.append({
            "shape": list(shape),
            "entries": src.n_entries,
            "slab_entries": slab_entries,
            "n_slabs": src.n_slabs,
            "seconds": round(dt, 3),
            "entries_per_sec": round(eps, 1),
            "payload_bytes": enc.payload_bytes(),
        })
        emit(f"fig5_stream_n{src.n_entries}", dt * 1e6,
             f"entries_per_sec={eps:.0f};slabs={src.n_slabs}")
    if len(records) >= 2:
        # the smallest run pays the one-time jit compile; drop it from the
        # slope fit when there are enough points so the asymptote shows
        pts = records[1:] if len(records) >= 3 else records
        ns = np.log([r["entries"] for r in pts])
        ts = np.log([r["seconds"] for r in pts])
        slope = float(np.polyfit(ns, ts, 1)[0])
        emit("fig5_stream_loglog_slope", 0.0, f"slope={slope:.3f};linear_if~1")
    else:
        slope = None
    out = os.path.join(RESULTS_DIR, "BENCH_stream.json")
    with open(out, "w") as f:
        json.dump({"mode": "smoke" if smoke else ("full" if FULL else "default"),
                   "loglog_slope": slope, "runs": records}, f, indent=2)
    emit("fig5_stream_json", 0.0, out)


if __name__ == "__main__":
    enable_compile_cache()
    if "--stream" in sys.argv:
        run_stream(smoke="--smoke" in sys.argv)
    else:
        run()
