"""LM substrate micro-benchmarks: smoke-config train/prefill/decode step
latency on CPU (sanity + regression tracking; real perf lives in the
dry-run roofline)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit
from repro import configs
from repro.compile_cache import enable_compile_cache
from repro.models import model
from repro.optim import optimizers
from repro.train import step as step_lib

ARCHS = ["deepseek-coder-33b", "grok-1-314b", "mamba2-1.3b", "jamba-1.5-large-398b"]


def run() -> None:
    for arch in ARCHS:
        cfg = configs.get_smoke(arch)
        key = jax.random.PRNGKey(0)
        params = model.init_params(key, cfg)
        toks = jax.random.randint(key, (4, 64), 0, cfg.vocab)
        if cfg.input_kind == "embeddings":
            batch = {
                "embeds": jax.random.normal(key, (4, 64, cfg.d_model), jnp.float32),
                "labels": toks,
            }
        else:
            batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
        opt = optimizers.adamw(1e-3)
        ost = opt.init(params)
        step = jax.jit(step_lib.make_train_step(cfg, opt))
        params, ost, _ = step(params, ost, batch)  # compile
        t0 = time.time()
        reps = 5
        for _ in range(reps):
            params, ost, m = step(params, ost, batch)
        jax.block_until_ready(m["loss"])
        emit(f"lm_train_step_{arch}", (time.time() - t0) / reps * 1e6,
             f"smoke;tokens={4*64}")


if __name__ == "__main__":
    enable_compile_cache()
    run()
