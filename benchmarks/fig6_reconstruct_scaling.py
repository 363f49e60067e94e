"""Fig. 6: reconstruction time vs the largest mode size, per codec.

Every codec in the ``repro.codecs`` registry is fit once per mode size
(cheap knobs — this figure times QUERIES, not fitting) and a fixed batch
of ``decode_at`` lookups is timed.  The paper's claim (Theorem 3) is that
NTTD reconstruction is logarithmic in N_max: its time follows d' =
O(log N_max) while the table-lookup decompositions stay flat and SZ-lite
pays a full decompression; the summary row reports NTTD's time ratio
against the 64x mode growth."""
from __future__ import annotations

import numpy as np

from benchmarks.common import (
    FULL,
    NTTD_FIT_OPTS,
    emit,
    save_rows,
    scaling_budget,
    timeit,
)
from repro.codecs import available, get_codec
from repro.compile_cache import enable_compile_cache

EXPS = [6, 8, 10, 12] + ([14] if FULL else [])
N_QUERIES = 1 << 14
NTTD_OPTS = {**NTTD_FIT_OPTS, "init_reorder": False}


def _fit(name: str, x: np.ndarray):
    if name == "nttd":
        return get_codec(name).fit(x, **NTTD_OPTS)
    return get_codec(name).fit(x, scaling_budget(x.size))


def run() -> None:
    rows = []
    nttd_pts = []
    for e in EXPS:
        n = 1 << e
        shape = (n, 8, 8)
        rng = np.random.default_rng(0)
        x = rng.random(shape).astype(np.float32)
        idx = np.stack([rng.integers(0, s, N_QUERIES) for s in shape], axis=1)
        for name in available():
            try:
                enc = _fit(name, x)
            except ValueError as err:
                emit(f"fig6_{name}_nmax_2e{e}", 0.0, f"skipped:{err}")
                continue
            enc.decode_at(idx)  # warm (jit compile / dense cache)
            dt = timeit(lambda: np.asarray(enc.decode_at(idx)))
            rows.append([name, n, round(dt, 5)])
            emit(f"fig6_{name}_nmax_2e{e}", dt * 1e6 / N_QUERIES,
                 f"total_s={dt:.4f}")
            if name == "nttd":
                nttd_pts.append((e, dt))
    # NTTD should grow ~linearly in log(N_max) == e, far below linear in N
    ts = np.array([p[1] for p in nttd_pts], float)
    ratio = float(ts[-1] / max(ts[0], 1e-12))
    nratio = (1 << EXPS[-1]) / (1 << EXPS[0])
    emit("fig6_sublinearity", 0.0,
         f"nttd_time_ratio={ratio:.2f};mode_ratio={nratio:.0f};log_like={ratio < 4}")
    save_rows("fig6_reconstruct_scaling.csv", ["codec", "n_max", "seconds"], rows)


if __name__ == "__main__":
    enable_compile_cache()
    run()
