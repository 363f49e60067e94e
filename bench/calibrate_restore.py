"""Readings that set the restore cells' ``read_rms_gap`` limit.

Not run by the benchmark.  Run on the chip, one process:

    python3 bench/calibrate_restore.py <cell> --seeds 6 --seconds 4

For each seed, a set-up and a window of the cell, then, for each leaf the
window restored, the root mean square gap from the reference over its
spread: of the program (the lower reading), and of the reference computed
at the precision below the configuration's (``reference.CONTROL``, the
upper reading) and at the backend's own ``high``, in the program's place.
The cell's ``read_rms_gap`` is the worst leaf's.  One JSON object a line.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import checkpoints, harness, reference  # noqa: E402

SEED_BASE = 3_000_000_000


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("cell")
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--seed-base", type=int, default=SEED_BASE)
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args(argv)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate_restore: needs a TPU", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    bench = harness.load_benchmark()
    cell = harness.cell_of(bench, args.cell)
    cfg, traffic = harness.config_of(bench, cell["config"]), harness.traffic_of(cell["traffic"])
    control = reference.CONTROL[cfg["precision"]["decode"]]
    workdir = harness.WORK_DIR / f"calibrate-{args.cell}"
    workdir.mkdir(parents=True, exist_ok=True)
    for k in range(args.seeds):
        seed = args.seed_base + 7919 * k
        d = harness.runner_of(traffic["kind"])(cfg, traffic, seed, workdir)
        d.setup()
        stats = d.window(args.seconds)
        d.release()
        readings = {"program": checkpoints.gaps(d.ckpt, d.kept)}
        for mode in (control, "high"):
            readings[f"control_{mode}"] = checkpoints.gaps(d.ckpt, d.kept, mode)
        print(json.dumps({"cell": args.cell, "seed": seed, "slabs": stats["attempted"],
                          "compared": int(sum(len(f) for f, _ in d.kept.values())),
                          **{name: max(g.values()) for name, g in readings.items()},
                          "by_leaf": readings}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
