"""Run one cell of BENCHMARK.json on the chip this process holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (inputs from the seed, loading, warming every shape the cell's
traffic uses) is timed as ``setup_s``; then the window runs for
``--seconds`` (with ``--trace 1``, a short profiled window instead), and
what it produced is compared with the plain reference in
``bench/reference.py``.  The last line of standard output is one JSON
object; the numbers compared, each with its limit, are the last lines of
standard error and the result's last key.  Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def parse(argv: list[str] | None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv: list[str] | None = None) -> int:
    args = parse(argv)
    from bench import harness

    bench = harness.load_benchmark()
    cell = harness.cell_of(bench, args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU, JAX found {devices[0].platform!r}", file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} chips, found {len(devices)}",
              file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    harness.say("device", f"{devices[0].platform} {devices[0].device_kind} x{len(devices)}")
    result = harness.run_cell(bench, args.workload, args.seed, args.seconds,
                              bool(args.trace), T_START, devices[0])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
