"""Readings that set the correctness limits, and the point-read knee.

Not run by the benchmark.  Run on the chip, one process per command:

    python3 bench/calibrate.py reads <cell> --seeds 12 --seconds 10
    python3 bench/calibrate.py fit <cell> --seeds 12
    python3 bench/calibrate.py sweep <config>:<mix> --rates 50,100,200 --seconds 8
    python3 bench/calibrate.py trace <cell> --seconds 0.3 --out DIR

``reads`` prints, per seed, the program's gap from the reference (the
lower reading) and the control's (the reference at the precision below
the configuration's, in the program's place: the upper reading), each
as quantiles, widest and root mean square over the reference's std.
``fit`` prints the program's gaps over the first three updates, the
control's (``reference.CONTROL`` of the configuration's precision, and
the backend's own ``high``), and the planted half-batch fault's.  ``sweep`` runs
an open-loop mix at each rate in place of its own and prints the tail
and backlog.  ``trace`` records one short profiled window and copies the
``.xplane.pb`` to DIR.
Each prints one JSON object per line.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import numpy as np  # noqa: E402

from bench import harness, reads, reference  # noqa: E402

SEED_BASE = 3_000_000_000


def _runner(cell_name: str, seed: int):
    """A cell of BENCHMARK.json, or ``<config>:<mix>`` for a mix that no
    cell runs yet."""
    bench = harness.load_benchmark()
    if ":" in cell_name:
        config, mix = cell_name.split(":", 1)
    else:
        cell = harness.cell_of(bench, cell_name)
        config, mix = cell["config"], cell["traffic"]
    cfg, traffic = harness.config_of(bench, config), harness.traffic_of(mix)
    workdir = harness.WORK_DIR / f"calibrate-{cell_name}"
    workdir.mkdir(parents=True, exist_ok=True)
    return harness.runner_of(traffic["kind"])(cfg, traffic, seed, workdir)


def emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


def profile(got: np.ndarray, ref: np.ndarray) -> dict:
    """Quantiles and root mean square of |got - ref| over the reference's std."""
    gap = np.abs(got - ref) / (float(np.std(ref)) or 1.0)
    out = {f"q{q}": float(np.percentile(gap, q)) for q in (50, 99, 99.9, 99.99)}
    return {**out, "max": float(gap.max()), "rms": float(np.sqrt(np.mean(gap**2)))}


def cmd_reads(args) -> None:
    for k in range(args.seeds):
        seed = args.seed_base + 7919 * k
        d = _runner(args.cell, seed)
        d.setup()
        stats = d.window(args.seconds)
        d.release()
        program = {c.name: c.value for c in d.check()}
        idx, served = reads.gather(d.kept)
        ref = reference.decode(d.payload.ref, idx, "highest")
        emit(cell=args.cell, seed=seed, requests=stats["attempted"], compared=len(idx),
             program=program["read_rms_gap"], program_profile=profile(served, ref),
             **{f"control_{mode}": profile(reference.decode(d.payload.ref, idx, mode), ref)
                for mode in ("bf16x3", "high", "bf16")})


def cmd_fit(args) -> None:
    for k in range(args.seeds):
        seed = args.seed_base + 7919 * k
        d = _runner(args.cell, seed)
        t0 = time.perf_counter()
        d.setup()
        setup = time.perf_counter() - t0
        d.release()
        ref = d.reference_run()
        control = reference.CONTROL[d.cfg["precision"]["fit"]]
        emit(cell=args.cell, seed=seed, setup_s=setup,
             program=d.gaps(d.readings, ref),
             **{f"control_{mode}": d.gaps(d.reference_run(mode), ref)
                for mode in (control, "high")},
             fault_half_batch=d.gaps(d.reference_run(fault="half_batch"), ref),
             losses=d.readings["losses"], ref_losses=ref["losses"])


def cmd_sweep(args) -> None:
    d = _runner(args.cell, SEED_BASE)
    d.setup()
    for rate in (float(r) for r in args.rates.split(",")):
        d.rate = rate
        stats = d.window(args.seconds)
        emit(cell=args.cell, rate=rate, **{k: v for k, v in stats.items()})
    d.release()


def cmd_trace(args) -> None:
    import jax

    d = _runner(args.cell, SEED_BASE)
    d.setup()
    tdir = harness.WORK_DIR / "calibrate-trace"
    shutil.rmtree(tdir, ignore_errors=True)
    with jax.profiler.trace(str(tdir)):
        with harness.annotate("window"):
            stats = d.window(args.seconds)
    from bench import trace_reduce

    src = trace_reduce.find_xplane(str(tdir))
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(src, out / "sample.xplane.pb")
    s = trace_reduce.reduce_dir(str(tdir))
    emit(cell=args.cell, stats=stats, window_s=s.window_s, busy_s=s.busy_s,
         programs=s.programs, breakdown=s.breakdown())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("what", choices=("reads", "fit", "sweep", "trace"))
    ap.add_argument("cell")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--seed-base", type=int, default=SEED_BASE)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", default="50,100,150,200")
    ap.add_argument("--out", default=".bench_work/trace-sample")
    args = ap.parse_args(argv)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    {"reads": cmd_reads, "fit": cmd_fit, "sweep": cmd_sweep, "trace": cmd_trace}[args.what](args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
