"""Fig. 9: total compression wall time, TensorCodec vs competitors (same
budget protocol as fig3, one dataset, every codec the registry knows)."""
from __future__ import annotations

import time

from benchmarks.common import FULL, emit, save_rows
from repro.codecs import available, get_codec
from repro.compile_cache import enable_compile_cache
from repro.data import synthetic_tensors as st

NTTD_OPTS = dict(rank=6, hidden=12, epochs=40 if not FULL else 150,
                 batch_size=8192, lr=1e-2, patience=6)


def run() -> None:
    x = st.load("uber", mini=True)
    rows = []
    times = {}

    t0 = time.time()
    ref = get_codec("nttd").fit(x, **NTTD_OPTS)
    times["nttd"] = time.time() - t0
    budget = ref.payload_bytes()

    for name in available():
        if name == "nttd":
            continue
        t0 = time.time()
        try:
            get_codec(name).fit(x, budget)
        except ValueError as e:  # budget below a codec's floor: report, go on
            emit(f"fig9_{name}", 0.0, f"skipped:{e}")
            continue
        times[name] = time.time() - t0

    for name, t in times.items():
        rows.append([name, round(t, 3)])
        emit(f"fig9_{name}", t * 1e6, f"seconds={t:.3f}")
    if "ttd" in times:
        emit("fig9_slowdown_vs_ttd", 0.0,
             f"x{times['nttd'] / max(times['ttd'], 1e-9):.1f}")
    save_rows("fig9_speed.csv", ["method", "seconds"], rows)


if __name__ == "__main__":
    enable_compile_cache()
    run()
