"""Fig. 8: expressiveness — tensors GENERATED by a random NTTD have high
linear rank, so classical decompositions need far more parameters to match
the fitness NTTD encodes for free."""
from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks.common import emit, save_rows
from repro.codecs import get_codec
from repro.compile_cache import enable_compile_cache
from repro.core import nttd
from repro.core.folding import make_folding_spec


def run() -> None:
    t0 = time.time()
    shape = (64, 64, 64)
    spec = make_folding_spec(shape)
    cfg = nttd.NTTDConfig(rank=5, hidden=5)  # paper: R = h = 5
    params = nttd.init_params(jax.random.PRNGKey(42), spec, cfg)
    # paper: "parameters of NTTD were randomly initialized" — replace the
    # production near-identity head biases with random ones so the
    # generated tensor is a generic point of the NTTD family
    for head in ("head_first", "head_mid", "head_last"):
        k = jax.random.fold_in(jax.random.PRNGKey(7), hash(head) % 1000)
        params[head]["b"] = jax.random.normal(k, params[head]["b"].shape) * 0.5
        params[head]["w"] = params[head]["w"] * 3.0
    x = nttd.generate_tensor(params, spec, cfg)
    n_params_nttd = nttd.count_params(params)

    # singular spectrum of the mode-0 unfolding: effective rank
    m = x.reshape(shape[0], -1)
    s = np.linalg.svd(m, compute_uv=False)
    energy = np.cumsum(s**2) / np.sum(s**2)
    eff_rank = int(np.searchsorted(energy, 0.99)) + 1

    # parameters TT-SVD needs for fitness >= 0.95 on this tensor
    tt_codec = get_codec("ttd")
    need = None
    for r in [2, 4, 8, 12, 16, 24, 32, 48, 64]:
        t = tt_codec.fit(x, max_rank=r)
        if t.fitness(x) >= 0.95:
            need = t.payload_bytes() // tt_codec.bytes_per_param
            break
    dt = time.time() - t0
    emit(
        "fig8_expressiveness", dt * 1e6,
        f"nttd_params={n_params_nttd};eff_rank99={eff_rank};"
        f"ttd_params_for_0.95={need};blowup={need / n_params_nttd if need else 'inf'}",
    )
    save_rows("fig8_expressiveness.csv",
              ["nttd_params", "eff_rank", "ttd_params_needed"],
              [[n_params_nttd, eff_rank, need]])


if __name__ == "__main__":
    enable_compile_cache()
    run()
