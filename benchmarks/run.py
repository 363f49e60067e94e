"""Benchmark harness: one module per paper table/figure + substrate benches.

Prints ``name,us_per_call,derived`` CSV rows.  Set REPRO_BENCH_FULL=1 for
the full dataset/epoch budgets (hours); the default budget finishes on a
single CPU core in ~15 minutes.
"""
from __future__ import annotations

import sys
import traceback

from repro.compile_cache import enable_compile_cache

MODULES = [
    "benchmarks.table2_stats",
    "benchmarks.fig3_tradeoff",
    "benchmarks.fig4_ablation",
    "benchmarks.fig5_compress_scaling",
    "benchmarks.fig6_reconstruct_scaling",
    "benchmarks.fig7_order_quality",
    "benchmarks.fig8_expressiveness",
    "benchmarks.fig9_speed",
    "benchmarks.kernels_bench",
    "benchmarks.lm_steps",
    "benchmarks.fleet_bench",
]


def main() -> None:
    import importlib

    enable_compile_cache()
    only = sys.argv[1] if len(sys.argv) > 1 else None
    print("name,us_per_call,derived")
    failed = []
    for mod_name in MODULES:
        if only and only not in mod_name:
            continue
        try:
            importlib.import_module(mod_name).run()
        except Exception as e:  # noqa: BLE001 — keep the harness going
            traceback.print_exc()
            failed.append(mod_name)
            print(f"{mod_name},0,ERROR:{type(e).__name__}")
    if failed:
        print(f"# FAILED: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
