"""Kernel micro-benchmarks (XLA path timing on CPU; the Pallas path is the
TPU target and is validated, not timed, in this container).

The fused-decode section is the roofline record for ROADMAP item 3: it
times the jnp chain (``nttd.apply_at_positions``) EAGER, multi-launch,
one dispatch per op, against the served decode tile
(``ops.nttd_decode_tile``, one program: the Pallas kernel on TPU, the
jitted oracle on CPU), validates interpret-mode bit-parity against the
oracle, and writes ``results/BENCH_kernels.json`` for ``check_bench`` to
gate.
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import RESULTS_DIR, emit
from repro.compile_cache import enable_compile_cache
from repro.core import nttd
from repro.core.folding import make_folding_spec
from repro.kernels import ops, ref


def _time(fn, *args, reps=10):
    fn(*args).block_until_ready()
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
    out.block_until_ready()
    return (time.time() - t0) / reps


def _time_eager(fn, *args, reps=10):
    """Per-call wall time WITHOUT jit — the multi-launch dispatch cost is
    the thing being measured, so no warmup-compile is subtracted beyond
    the first call."""
    np.asarray(fn(*args))  # first call pays any per-op compile
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
    np.asarray(out)
    return (time.time() - t0) / reps


def decode_tile_roofline(smoke: bool = False) -> dict:
    """Fused vs multi-launch NTTD decode on one serving tile workload."""
    shape = (48, 40, 32)
    spec = make_folding_spec(shape)
    cfg = nttd.NTTDConfig(rank=8, hidden=16)
    params = nttd.init_params(jax.random.PRNGKey(0), spec, cfg)
    bsz = 1024 if smoke else 4096
    rng = np.random.default_rng(0)
    pos = jnp.asarray(
        np.stack([rng.integers(0, s, bsz) for s in shape], axis=1), jnp.int32
    )

    # interpret-mode Pallas vs the jitted oracle: same compiled op order,
    # so parity is BITWISE (the gate tests also sweep this; the bench
    # asserts it on the exact workload being timed)
    folded = spec.fold_indices(pos)
    flat = nttd.fused_decode_inputs(params, spec, cfg)
    got_i = np.asarray(
        ops.nttd_decode_tile(folded, *flat, interpret=True, tile_b=256)
    )
    got_f = np.asarray(ops.nttd_decode_tile(folded, *flat))
    assert np.array_equal(got_i, got_f), "interpret kernel drifted from oracle"

    # multi-launch: the jnp chain un-jitted — one dispatch per op
    multi = lambda p: nttd.apply_at_positions(params, p, spec, cfg)  # noqa: E731
    dt_multi = _time_eager(multi, pos, reps=3 if smoke else 10)

    # fused: the fold and the decode tile as one XLA program
    fused = jax.jit(lambda p: ops.nttd_decode_tile(spec.fold_indices(p), *flat))
    dt_fused = _time(fused, pos, reps=10 if smoke else 50)

    # roofline accounting: weight bytes stream once per tile, flops are
    # dominated by the per-entry LSTM gate matmuls
    t_steps, hid, rank = spec.d_prime, cfg.hidden, cfg.rank
    flops_per_entry = t_steps * (2 * 2 * hid * 4 * hid) + 2 * hid * (
        2 * rank + (t_steps - 2) * rank * rank
    )
    weight_bytes = sum(int(np.prod(a.shape)) * 4 for a in flat)
    rec = {
        "batch": bsz,
        "shape": list(shape),
        "d_prime": t_steps,
        "multilaunch_entries_per_sec": round(bsz / dt_multi, 1),
        "fused_entries_per_sec": round(bsz / dt_fused, 1),
        "fused_speedup": round(dt_multi / dt_fused, 2),
        "fused_gflops": round(flops_per_entry * bsz / dt_fused / 1e9, 2),
        "weight_bytes_per_tile": weight_bytes,
        "parity_bitwise": True,
    }
    emit(
        "kernel_decode_tile_fused", dt_fused * 1e6,
        f"B={bsz};T={t_steps};{bsz/dt_fused/1e6:.2f}M entries/s;"
        f"speedup={rec['fused_speedup']:.1f}x over multi-launch",
    )
    return rec


def run(smoke: bool = False) -> None:
    rng = np.random.default_rng(0)
    b, k, r = 65536, 10, 8
    f = jnp.asarray(rng.normal(size=(b, r)), jnp.float32)
    m = jnp.asarray(rng.normal(size=(b, k, r, r)) * 0.2, jnp.float32)
    last = jnp.asarray(rng.normal(size=(b, r)), jnp.float32)
    fn = jax.jit(ref.tt_contract)
    dt = _time(fn, f, m, last)
    emit("kernel_tt_contract_ref", dt * 1e6, f"B={b};K={k};R={r};{b/dt/1e6:.1f}M entries/s")

    t, h = 10, 16
    x = jnp.asarray(rng.normal(size=(b, t, h)), jnp.float32)
    wi = jnp.asarray(rng.normal(size=(h, 4 * h)) * 0.3, jnp.float32)
    wh = jnp.asarray(rng.normal(size=(h, 4 * h)) * 0.3, jnp.float32)
    bb = jnp.zeros((4 * h,), jnp.float32)
    fn = jax.jit(ref.lstm_scan)
    dt = _time(fn, x, wi, wh, bb)
    emit("kernel_lstm_ref", dt * 1e6, f"B={b};T={t};H={h};{b/dt/1e6:.1f}M seq/s")

    bq, s, hq, hkv, d = 1, 1024, 8, 2, 64
    q = jnp.asarray(rng.normal(size=(bq, s, hq, d)), jnp.float32)
    kk = jnp.asarray(rng.normal(size=(bq, s, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(bq, s, hkv, d)), jnp.float32)
    fn = jax.jit(lambda *a: ops.attention(*a, impl="ref"))
    dt = _time(fn, q, kk, v, reps=3)
    flops = 4 * bq * hq * s * s * d
    emit("kernel_attention_ref", dt * 1e6, f"S={s};GQA{hq}/{hkv};{flops/dt/1e9:.1f}GFLOP/s")

    rec = decode_tile_roofline(smoke=smoke)
    os.makedirs(RESULTS_DIR, exist_ok=True)
    out = os.path.join(RESULTS_DIR, "BENCH_kernels.json")
    with open(out, "w") as f:
        json.dump(
            {"mode": "smoke" if smoke else "default", "runs": [rec]}, f, indent=2
        )
    emit("kernels_json", 0.0, out)


if __name__ == "__main__":
    import sys

    enable_compile_cache()
    run(smoke="--smoke" in sys.argv)
