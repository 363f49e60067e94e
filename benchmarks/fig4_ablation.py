"""Fig. 4: component ablation — TensorCodec vs -R (no repeated reorder),
-T (no TSP init either), -N (no neural net: plain TT-SVD on the folded
tensor at matched payload).  All fits go through the codec registry."""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import FULL, emit, save_rows
from repro.codecs import get_codec
from repro.codecs.indexing import flat_to_multi
from repro.compile_cache import enable_compile_cache
from repro.core.folding import make_folding_spec
from repro.data import synthetic_tensors as st

DATASETS = ["uber", "stock"] if not FULL else ["uber", "air_quality", "action", "stock"]


def _folded_ttsvd_fitness(x: np.ndarray, budget_bytes: int) -> float:
    """TensorCodec-N: TT-SVD on the folded tensor at the same payload
    budget (paper §V-C)."""
    spec = make_folding_spec(x.shape)
    folded = np.zeros(spec.folded_shape, dtype=np.float32)
    n = x.size
    flat = np.arange(n)
    idx = flat_to_multi(flat, x.shape)
    fidx = np.asarray(spec.fold_indices(idx))
    folded[tuple(fidx[:, j] for j in range(spec.d_prime))] = x.reshape(-1)
    t = get_codec("ttd").fit(folded, budget_bytes)
    recon = t.to_dense()[tuple(fidx[:, j] for j in range(spec.d_prime))]
    err = np.linalg.norm(recon - x.reshape(-1))
    return 1.0 - err / np.linalg.norm(x.reshape(-1))


def run() -> None:
    rows = []
    epochs = 50 if not FULL else 150
    nttd_codec = get_codec("nttd")
    for name in DATASETS:
        x = st.load(name, mini=True)
        common = dict(rank=6, hidden=12, epochs=epochs, batch_size=8192,
                      lr=1e-2, patience=8)
        t0 = time.time()
        full = nttd_codec.fit(x, **common)
        fit_full = full.fitness(x)
        no_r = nttd_codec.fit(x, update_reorder=False, **common)
        fit_r = no_r.fitness(x)
        no_t = nttd_codec.fit(x, update_reorder=False, init_reorder=False, **common)
        fit_t = no_t.fitness(x)
        fit_n = _folded_ttsvd_fitness(x, full.payload_bytes())
        dt = time.time() - t0
        rows.append([name, round(fit_full, 4), round(fit_r, 4), round(fit_t, 4),
                     round(fit_n, 4)])
        emit(
            f"fig4_{name}", dt * 1e6,
            f"full={fit_full:.4f};-R={fit_r:.4f};-T={fit_t:.4f};-N={fit_n:.4f}",
        )
    save_rows("fig4_ablation.csv", ["dataset", "full", "minus_R", "minus_T", "minus_N"], rows)


if __name__ == "__main__":
    enable_compile_cache()
    run()
