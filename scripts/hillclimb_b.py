"""Perf-B hillclimb: llama4-maverick train_4k single (worst roofline
fraction AND most collective-bound).  Each iteration recompiles the cell
with one change and reports the three terms + per-dtype collective
attribution."""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import dataclasses
import json
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402

from repro import configs  # noqa: E402
from repro.configs.base import SHAPES  # noqa: E402
from repro.dist import sharding  # noqa: E402
from repro.launch import dryrun, mesh as mesh_lib  # noqa: E402
from repro.models import model  # noqa: E402
from repro.optim import optimizers  # noqa: E402
from repro.train import step as step_lib  # noqa: E402

ARCH = "llama4-maverick-400b-a17b"
SHAPE = "train_4k"


def measure(tag: str, cfg_override=None, rules_override=None, depths=(2, 4)):
    mesh = mesh_lib.make_production_mesh(multi_pod=False)
    shape = SHAPES[SHAPE]
    base_cfg = configs.get(ARCH)
    cfg = cfg_override(base_cfg) if cfg_override else dataclasses.replace(
        base_cfg, remat="full"
    )
    rules = step_lib.effective_rules(mesh, shape, sharding.FSDP_RULES, cfg)
    if rules_override:
        rules = rules_override(rules)

    def lower(depth):
        c = dataclasses.replace(cfg, n_layers=cfg.block_size * depth,
                                scan_layers=False) if depth else cfg
        ab_params = model.abstract_params(c)
        ps = sharding.tree_shardings(mesh, model.param_specs(c), rules)
        batch_spec = step_lib.input_specs(c, shape)
        bs = step_lib.batch_shardings(mesh, c, batch_spec, rules)
        opt = optimizers.adamw(1e-4, weight_decay=0.1, max_grad_norm=1.0)
        fn = step_lib.make_train_step(c, opt)
        ab_opt = jax.eval_shape(opt.init, ab_params)
        os_ = step_lib.opt_shardings(mesh, c, rules)
        with sharding.sharding_ctx(mesh, rules):
            return jax.jit(fn, in_shardings=(ps, os_, bs),
                           donate_argnums=(0, 1)).lower(ab_params, ab_opt, batch_spec)

    t0 = time.time()
    # memory from the scanned full program
    mem = lower(None).compile().memory_analysis()

    def costs(depth):
        comp = lower(depth).compile()
        cost = comp.cost_analysis()
        coll = dryrun.collective_bytes_per_device(comp.as_text(), by_dtype=True)
        return (float(cost.get("flops", 0.0)),
                float(cost.get("bytes accessed", 0.0)), coll)

    d1, d2 = depths
    f1, b1, c1 = costs(d1)
    f2, b2, c2 = costs(d2)
    nb = cfg.n_blocks
    ex = lambda v1, v2: v1 + (nb - d1) * (v2 - v1) / (d2 - d1)  # noqa: E731
    flops = ex(f1, f2)
    bytes_ = ex(b1, b2)
    coll = {k: ex(c1.get(k, 0.0), c2.get(k, 0.0)) for k in set(c1) | set(c2)}
    terms = dict(
        compute_s=flops / mesh_lib.PEAK_FLOPS_BF16,
        memory_s=bytes_ / mesh_lib.HBM_BW,
        collective_s=coll["total"] / mesh_lib.ICI_BW,
    )
    mf = dryrun.model_flops(cfg, shape)
    ideal = max((mf / 256) / mesh_lib.PEAK_FLOPS_BF16,
                mem.argument_size_in_bytes / mesh_lib.HBM_BW)
    frac = ideal / max(terms.values())
    print(f"== {tag} ({time.time()-t0:.0f}s) ==")
    print("  terms: " + " ".join(f"{k}={v:.3f}" for k, v in terms.items())
          + f" fraction={frac:.4f}")
    print(f"  temp={mem.temp_size_in_bytes/1e9:.0f}GB args={mem.argument_size_in_bytes/1e9:.0f}GB")
    bd = {k: v for k, v in sorted(coll.items()) if ":" in k and v > 1e9}
    print("  coll by dtype: " + " ".join(f"{k}={v:.2e}" for k, v in bd.items()))
    return dict(tag=tag, terms=terms, fraction=frac, coll=coll,
                temp=mem.temp_size_in_bytes, flops=flops, bytes=bytes_)


if __name__ == "__main__":
    results = []
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    if which in ("all", "b1"):
        results.append(measure("B.1-baseline-fsdp-rematfull"))
    if which in ("all", "b2"):
        results.append(measure(
            "B.2-remat-dots",
            cfg_override=lambda c: dataclasses.replace(c, remat="dots"),
        ))
    if which in ("all", "b3"):
        # experts already on 'model' via fallback; keep expert_mlp unsharded
        # over data so expert weights gather only over 'data' on d_model
        results.append(measure(
            "B.3-capacity-1.0",
            cfg_override=lambda c: dataclasses.replace(
                c, remat="full", moe_capacity_factor=1.0),
        ))
    if which in ("all", "b4"):
        # expert parallelism: experts stationary (sharded data x model via
        # expert_mlp), tokens all-to-all through the dispatch constraint
        def ep_rules(rules):
            rules = dict(rules)
            # only the EP-specific keys; keep cell adjustments (CP/SP) intact
            rules["experts"] = ("data",)
            rules["expert_in"] = None
            rules["moe_group"] = None
            return rules

        results.append(measure("B.4-expert-parallel", rules_override=ep_rules))
    with open("/tmp/hillclimb_b.json", "w") as f:
        json.dump(results, f, indent=2, default=float)
