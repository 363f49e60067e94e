"""Serving launcher: batched requests through the slot engine.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-4b --smoke \
        --requests 8 --max-new 16
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import configs
from repro.compile_cache import enable_compile_cache
from repro.models import model
from repro.serve.engine import Request, ServeEngine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get(args.arch)
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(cfg, params, args.slots, args.max_len,
                         temperature=args.temperature)
    rng = np.random.default_rng(0)
    t0 = time.time()
    for uid in range(args.requests):
        engine.submit(
            Request(
                uid=uid,
                prompt=rng.integers(0, cfg.vocab, size=args.prompt_len),
                max_new_tokens=args.max_new,
            )
        )
    results = engine.run()
    dt = time.time() - t0
    total_new = sum(len(r.tokens) for r in results)
    for r in sorted(results, key=lambda r: r.uid)[:4]:
        print(f"req {r.uid}: {r.tokens[:8]}...")
    print(
        f"served {len(results)} requests, {total_new} tokens in {dt:.2f}s "
        f"({total_new/dt:.1f} tok/s)"
    )
    return results


if __name__ == "__main__":
    main()
