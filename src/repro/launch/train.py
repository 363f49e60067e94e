"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch minicpm-2b --smoke \
        --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt --resume auto

Runs the real pjit train loop on whatever mesh fits the local devices
(the production mesh shape comes from launch.mesh on a real pod).
Includes: WSD/cosine schedules, grad clipping, async checkpointing with
auto-resume, SIGTERM -> final checkpoint, straggler watchdog (p95
step-time outliers logged), optional gradient compression, optional
NTTD-compressed checkpoint export.
"""
from __future__ import annotations

import argparse
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.compile_cache import enable_compile_cache
from repro.data.pipeline import PipelineConfig, SyntheticSource
from repro.dist import sharding
from repro.launch.mesh import make_mesh
from repro.models import model
from repro.optim import optimizers, schedules
from repro.train import checkpoint as ckpt_lib
from repro.train import step as step_lib


class StragglerWatchdog:
    """Flags steps slower than ``factor`` x the trailing median."""

    def __init__(self, window: int = 50, factor: float = 2.0):
        self.times: list[float] = []
        self.window = window
        self.factor = factor
        self.flagged = 0

    def record(self, dt: float) -> bool:
        hist = self.times[-self.window :]
        slow = len(hist) >= 10 and dt > self.factor * float(np.median(hist))
        self.times.append(dt)
        if slow:
            self.flagged += 1
        return slow


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="use the reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--schedule", default="wsd", choices=["wsd", "cosine", "constant"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", default="none", choices=["none", "auto"])
    ap.add_argument("--grad-compress", default="none", choices=["none", "int8", "topk"])
    ap.add_argument("--data", default=None, help="path to int32 token file (mmap)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--mesh", default=None,
                    help="DxM, e.g. 2x2 (default: all devices data-parallel)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get(args.arch)
    n_dev = len(jax.devices())
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = make_mesh((d, m), ("data", "model"))
    else:
        mesh = make_mesh((n_dev, 1), ("data", "model"))

    sched = {
        "wsd": schedules.wsd(args.lr, args.steps, warmup=min(20, args.steps // 10)),
        "cosine": schedules.cosine(args.lr, args.steps, warmup=min(20, args.steps // 10)),
        "constant": schedules.constant(args.lr),
    }[args.schedule]
    opt = optimizers.adamw(sched, weight_decay=0.1, max_grad_norm=1.0)

    # ---- grad compression hook ------------------------------------------------
    comp = None
    if args.grad_compress != "none":
        from repro.dist import grad_compress

        comp = (
            grad_compress.ErrorFeedbackInt8()
            if args.grad_compress == "int8"
            else grad_compress.TopK(0.05)
        )

    rules = sharding.BASE_RULES
    ps = step_lib.param_shardings(mesh, cfg, rules)
    os_sh = step_lib.opt_shardings(mesh, cfg, rules)

    key = jax.random.PRNGKey(0)
    with sharding.sharding_ctx(mesh, rules):
        params = jax.jit(
            lambda k: model.init_params(k, cfg), out_shardings=ps
        )(key)
        opt_state = jax.jit(opt.init, out_shardings=os_sh)(params)
        comp_state = comp.init(params) if comp else None

        if comp is None:
            raw_step = step_lib.make_train_step(cfg, opt)
            train_step = jax.jit(raw_step, donate_argnums=(0, 1))
        else:

            def step_with_comp(params, opt_state, comp_state, batch):
                def loss(p):
                    return model.loss_fn(p, cfg, batch)

                (loss_val, metrics), grads = jax.value_and_grad(loss, has_aux=True)(params)
                grads, comp_state = comp.transform(grads, comp_state)
                updates, opt_state = opt.update(grads, opt_state, params)
                params = optimizers.apply_updates(params, updates)
                m = dict(metrics)
                m["loss"] = loss_val
                return params, opt_state, comp_state, m

            train_step = jax.jit(step_with_comp, donate_argnums=(0, 1, 2))

        # ---- data ------------------------------------------------------------------
        pcfg = PipelineConfig(
            batch_size=args.batch, seq_len=args.seq, vocab=cfg.vocab, seed=0
        )
        if args.data:
            from repro.data.pipeline import MMapSource

            source = MMapSource(args.data, pcfg)
        else:
            source = SyntheticSource(pcfg)

        # ---- checkpointing / resume ----------------------------------------------
        start_step = 0
        ckpt = None
        if args.ckpt_dir:
            ckpt = ckpt_lib.Checkpointer(args.ckpt_dir)
            if args.resume == "auto":
                state, start_step = ckpt_lib.auto_resume(
                    ckpt, {"params": params, "opt": opt_state}, {"params": ps, "opt": os_sh}
                )
                if state is not None:
                    params, opt_state = state["params"], state["opt"]
                    print(f"resumed from step {start_step}")

        stop = {"flag": False}

        def on_sigterm(signum, frame):
            stop["flag"] = True

        signal.signal(signal.SIGTERM, on_sigterm)

        watchdog = StragglerWatchdog()
        losses = []
        for step in range(start_step, args.steps):
            t0 = time.time()
            batch_np = source.batch_at(step)
            batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
            if comp is None:
                params, opt_state, metrics = train_step(params, opt_state, batch)
            else:
                params, opt_state, comp_state, metrics = train_step(
                    params, opt_state, comp_state, batch
                )
            loss = float(metrics["loss"])
            losses.append(loss)
            dt = time.time() - t0
            if watchdog.record(dt):
                print(f"[watchdog] step {step} straggled: {dt:.3f}s")
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"step {step}: loss={loss:.4f} ({dt*1000:.0f} ms)")
            if ckpt and (step + 1) % args.ckpt_every == 0:
                ckpt.save(step + 1, {"params": params, "opt": opt_state})
            if stop["flag"]:
                print("SIGTERM: writing final checkpoint")
                break

        if ckpt:
            ckpt.save(args.steps if not stop["flag"] else step + 1,
                      {"params": params, "opt": opt_state})
            ckpt.wait()
    print(f"done. first loss {losses[0]:.4f} -> last {losses[-1]:.4f}")
    return losses


if __name__ == "__main__":
    main()
