"""Dry-run cell for the paper's own workload: the NTTD compression
training step, data-parallel over sampled tensor entries on the
production mesh.

    PYTHONPATH=src python -m repro.launch.dryrun_codec \
        [--mesh single|multi] \
        [--batch 65536] [--steps 8] [--rank 8] [--hidden 16]

Reports the same three-term roofline as the LM cells.  This is the
Perf-C hillclimb target (EXPERIMENTS.md §Perf).
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse
import json

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.compile_cache import enable_compile_cache
from repro.core import codec as codec_lib
from repro.core import nttd
from repro.core.folding import make_folding_spec
from repro.launch import dryrun, mesh as mesh_lib
from repro.optim import optimizers

# the paper's largest tensor family, scaled to a production-sized workload:
# compressing a (16384, 4096, 1024) dense tensor (~0.5 TB fp64)
DEFAULT_SHAPE = (16384, 4096, 1024)


def run(mesh_name: str, batch: int, steps: int, rank: int,
        hidden: int, shape=DEFAULT_SHAPE, verbose: bool = True) -> dict:
    mesh = mesh_lib.make_production_mesh(multi_pod=mesh_name == "multi")
    spec = make_folding_spec(shape)
    cfg = nttd.NTTDConfig(rank=rank, hidden=hidden)
    opt = optimizers.adam(1e-2)
    epoch_fn = codec_lib._make_train_epoch(spec, cfg, opt)

    ab_params = jax.eval_shape(
        lambda k: nttd.init_params(k, spec, cfg), jax.random.PRNGKey(0)
    )
    ab_opt = jax.eval_shape(opt.init, ab_params)
    pos = jax.ShapeDtypeStruct((steps, batch, len(shape)), jnp.int32)
    vals = jax.ShapeDtypeStruct((steps, batch), jnp.float32)
    repl = NamedSharding(mesh, P())
    dp_axes = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    dp = NamedSharding(mesh, P(None, dp_axes))

    lowered = jax.jit(
        epoch_fn,
        in_shardings=(
            jax.tree.map(lambda _: repl, ab_params),
            jax.tree.map(lambda _: repl, ab_opt),
            dp,
            dp,
        ),
        donate_argnums=(0, 1),
    ).lower(ab_params, ab_opt, pos, vals)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = dryrun.collective_bytes_per_device(compiled.as_text())

    # cost_analysis under-counts the steps-loop (while); per-step numbers
    # are what matter — divide by the scan length is unnecessary since the
    # scan body is counted once: numbers below are PER STEP already.
    flops = float(cost.get("flops", 0.0))
    bytes_ = float(cost.get("bytes accessed", 0.0))
    n_entries = batch  # per step
    # useful flops per entry: LSTM (8h^2 per step x d') + heads + chain,
    # x3 for fwd+bwd
    d_prime = spec.d_prime
    per_entry = d_prime * (8 * hidden * hidden + 2 * hidden * rank * rank) + (
        d_prime - 2
    ) * 2 * rank * rank
    mf = 3.0 * per_entry * n_entries
    terms = {
        "compute_s": flops / mesh_lib.PEAK_FLOPS_BF16,
        "memory_s": bytes_ / mesh_lib.HBM_BW,
        "collective_s": coll["total"] / mesh_lib.ICI_BW,
    }
    dominant = max(terms, key=terms.get)
    ideal = max(
        (mf / mesh.size) / mesh_lib.PEAK_FLOPS_BF16,
        mem.argument_size_in_bytes / mesh_lib.HBM_BW,
    )
    res = {
        "arch": "tensorcodec-codec",
        "shape": f"entries{batch}x{steps}",
        "mesh": mesh_name,
        "rules": "dp",
        "status": "ok",
        "n_devices": mesh.size,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_per_device": mem.argument_size_in_bytes
            + mem.output_size_in_bytes
            + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes,
        },
        "flops_per_device": flops,
        "hlo_bytes_per_device": bytes_,
        "collective_bytes_per_device": coll,
        "model_flops": mf,
        "useful_flops_ratio": mf / max(flops * mesh.size, 1.0),
        "roofline": dict(
            terms,
            dominant=dominant,
            bound_s=max(terms.values()),
            ideal_s=ideal,
            roofline_fraction=ideal / max(terms.values()),
        ),
    }
    if verbose:
        print(f"[codec x {mesh_name} x batch={batch}]")
        print(f"  memory: args={mem.argument_size_in_bytes/1e6:.1f}MB "
              f"temp={mem.temp_size_in_bytes/1e6:.1f}MB")
        print(f"  flops/dev={flops:.3e} bytes/dev={bytes_:.3e} "
              f"coll/dev={coll['total']:.3e}")
        print("  roofline: " + " ".join(f"{k}={v:.6f}s" for k, v in terms.items())
              + f" dominant={dominant} fraction={res['roofline']['roofline_fraction']:.3f}")
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--batch", type=int, default=1 << 20)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--rank", type=int, default=8)
    ap.add_argument("--hidden", type=int, default=16)
    args = ap.parse_args()
    enable_compile_cache()
    res = run(args.mesh, args.batch, args.steps, args.rank, args.hidden)
    path = dryrun.cell_path("tensorcodec-codec", f"b{args.batch}",
                            args.mesh, "dp")
    with open(path, "w") as f:
        json.dump(res, f, indent=2)


if __name__ == "__main__":
    main()
