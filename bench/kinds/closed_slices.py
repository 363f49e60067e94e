"""Closed loop, one caller: each request is a slice of the tensor (one
index drawn in some modes, a run of consecutive indices or the whole mode
in the others), sent through ``CodecService.decode_at`` as soon as the
previous one is answered.

Traffic parameters: ``slice``, one entry per mode: ``"pick"`` (one index
drawn from the seed), ``"all"`` (the whole mode) or ``{"run": L}`` (L
consecutive indices from a drawn start); ``check_fraction``, the share of
requests whose answers are kept for the comparison.
"""
from __future__ import annotations

import time

import numpy as np

from bench import inputs, reads
from bench.harness import annotate


class Runner:
    def __init__(self, cfg: dict, traffic: dict, seed: int, workdir):
        self.cfg, self.traffic, self.seed, self.workdir = cfg, traffic, seed, workdir
        self.shape = tuple(cfg["dataset"]["shape"])
        self.limit = float(cfg["limits"]["read_rms_gap"])
        axes, self.high = [], []
        for n, part in zip(self.shape, traffic["slice"]):
            if part == "pick":
                axes.append(np.zeros(1, np.int64))
                self.high.append(n)
            elif part == "all":
                axes.append(np.arange(n, dtype=np.int64))
                self.high.append(1)
            else:
                length = int(part["run"])
                axes.append(np.arange(length, dtype=np.int64))
                self.high.append(n - length + 1)
        grid = np.meshgrid(*axes, indexing="ij")
        self.template = np.stack([g.reshape(-1) for g in grid], axis=1)
        self.kept: list = []
        self.missing = 0

    @property
    def request_entries(self) -> int:
        return len(self.template)

    def _request(self, rng) -> np.ndarray:
        return self.template + rng.integers(0, self.high)

    def setup(self) -> None:
        self.payload = reads.Payload(self.cfg, self.seed, self.workdir)
        self.svc = self.payload.serve()
        warm = inputs.host_rng(self.seed, "warm")
        for _ in range(2):  # the first compiles, the second runs warm
            self.svc.decode_at(reads.NAME, self._request(warm))

    def window(self, seconds: float) -> dict:
        rng = inputs.host_rng(self.seed, "requests")
        keep = inputs.host_rng(self.seed, "keep")
        frac = float(self.traffic["check_fraction"])
        kept, attempted, failed, entries = [], 0, 0, 0
        t0 = time.perf_counter()
        end = t0 + seconds
        while time.perf_counter() < end:
            with annotate("make_request"):
                idx = self._request(rng)
            attempted += 1
            try:
                with annotate("decode_at"):
                    ans = self.svc.decode_at(reads.NAME, idx)
            except Exception as e:  # noqa: BLE001 - a failed request is counted, not fatal
                failed += 1
                self.error = repr(e)
                continue
            entries += len(idx)
            if attempted == 1 or keep.random() < frac:
                kept.append((idx, ans))
        elapsed = time.perf_counter() - t0
        self.kept = kept
        self.missing = failed
        return {"attempted": attempted, "failed": failed, "entries": entries,
                "elapsed": elapsed, "read_entries_per_s": entries / elapsed}

    def notes(self, stats: dict) -> dict:
        return {"requests": stats["attempted"], "request_entries": self.request_entries,
                "entries": stats["entries"], "window_s": stats["elapsed"],
                "answers_kept": len(self.kept)}

    def release(self) -> None:
        self.svc.unload(reads.NAME)
        del self.svc

    def check(self):
        return reads.checks(self.payload, self.kept, self.limit, self.missing)
