"""Fig. 7 (quantitative proxy): order quality on a tensor with planted
spatial structure.  The paper shows NYC maps; offline we plant a 1-D
latent coordinate per index, shuffle, and measure how well the learned
order recovers latent adjacency (Spearman-style displacement) and the
Eq. 6 objective vs identity/random orders."""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import emit, save_rows
from repro.codecs import get_codec
from repro.compile_cache import enable_compile_cache
from repro.core import reorder


def run() -> None:
    rng = np.random.default_rng(0)
    n0, n1, n2 = 40, 24, 16
    coord = np.linspace(0, 1, n0)
    x = (
        np.exp(-((coord[:, None, None] - np.linspace(0, 1, n1)[None, :, None]) ** 2) * 8)
        + 0.3 * np.sin(6 * coord)[:, None, None]
        + 0.05 * rng.normal(size=(n0, n1, n2))
    ).astype(np.float32)
    perm = rng.permutation(n0)
    xp = x[perm]

    t0 = time.time()
    enc = get_codec("nttd").fit(
        xp, rank=6, hidden=12, epochs=60, batch_size=4096, lr=1e-2, patience=10,
    )
    dt = time.time() - t0
    learned = enc.pi[0]

    def adjacency_score(order):
        # positions in latent space along the learned order
        latent = perm[order]
        return float(np.median(np.abs(np.diff(np.argsort(np.argsort(coord))[latent]))))

    ident = np.arange(n0)
    scores = {
        "learned": adjacency_score(learned),
        "identity": adjacency_score(ident),
        "random": adjacency_score(rng.permutation(n0)),
    }
    obj = {
        k: reorder.order_objective(xp, 0, v)
        for k, v in [("learned", learned), ("identity", ident)]
    }
    emit(
        "fig7_order_quality", dt * 1e6,
        f"median_latent_jump_learned={scores['learned']:.1f};identity={scores['identity']:.1f};"
        f"random={scores['random']:.1f};eq6_learned={obj['learned']:.1f};"
        f"eq6_identity={obj['identity']:.1f}",
    )
    save_rows("fig7_order_quality.csv", ["order", "median_jump"],
              [[k, v] for k, v in scores.items()])


if __name__ == "__main__":
    enable_compile_cache()
    run()
