"""Fig. 3: compressed size vs fitness trade-off, TensorCodec vs every other
registered codec at matched payload budgets.

Datasets are the synthetic Table-II replicas (mini shapes; the container is
offline — see DESIGN.md §9).  Competitors get the SAME payload budget the
codec used (paper protocol: sizes matched, fitness compared) — each rival
comes from ``repro.codecs.available()``, so adding a codec to the registry
adds a column here with no wiring.
"""
from __future__ import annotations

import time

from benchmarks.common import FULL, emit, save_rows
from repro.codecs import available, get_codec
from repro.compile_cache import enable_compile_cache
from repro.data import synthetic_tensors as st

DATASETS = ["uber", "air_quality", "stock", "nyc"] if not FULL else list(st.DATASETS)


def run() -> None:
    rivals = [n for n in available() if n != "nttd"]
    rows = []
    for name in DATASETS:
        x = st.load(name, mini=True)
        epochs = 60 if not FULL else 200
        t0 = time.time()
        enc = get_codec("nttd").fit(
            x, rank=6, hidden=12, epochs=epochs, batch_size=8192, lr=1e-2,
            reorder_samples=1024, patience=8,
        )
        t = time.time() - t0
        budget_bytes = enc.payload_bytes()          # paper: fp64 convention
        fits = {"nttd": enc.fitness(x)}
        for rival in rivals:
            try:
                fits[rival] = get_codec(rival).fit(x, budget_bytes).fitness(x)
            except ValueError:  # codec cannot meet this budget (e.g. szlite floor)
                fits[rival] = float("nan")

        best_rival = max(
            (fits[r] for r in rivals if fits[r] == fits[r]), default=float("-inf")
        )
        rows.append([name, x.size, budget_bytes]
                    + [round(fits[c], 4) for c in ["nttd"] + rivals])
        derived = ";".join(f"{c}={fits[c]:.4f}" for c in ["nttd"] + rivals)
        emit(
            f"fig3_{name}",
            t * 1e6,
            f"bytes={budget_bytes};{derived};"
            f"tc_minus_best={fits['nttd'] - best_rival:+.4f}",
        )
    save_rows(
        "fig3_tradeoff.csv",
        ["dataset", "entries", "budget_bytes", "nttd"] + rivals,
        rows,
    )


if __name__ == "__main__":
    enable_compile_cache()
    run()
