"""Open loop at a fixed rate: requests of a few fixed sizes, each entry
drawn uniformly over the tensor, sent through ``CodecService.decode_at``
when due, whether or not the previous one has been answered.

Every seed gets the same set of sizes and of gaps between arrivals, in
another order: the sizes in exact proportion to ``probs``, the gaps the
quantiles of an exponential at ``rate_per_s``.  Each request is timed
from when it was due, so a stall delays the requests behind it.

Traffic parameters: ``rate_per_s``, ``sizes``, ``probs``.
"""
from __future__ import annotations

import time

import numpy as np

from bench import inputs, reads
from bench.harness import annotate

SPIN_S = 0.0005  # sleep until this close to a due time, then spin


def schedule(rate: float, seconds: float, sizes, probs, rng) -> tuple[np.ndarray, np.ndarray]:
    """(due times from the window's start, request sizes), both in the
    seed's order.  The sizes are split in exact proportion (largest
    remainder) and the gaps are exponential quantiles."""
    n = max(int(round(rate * seconds)), 1)
    raw = np.asarray(probs, np.float64) * n
    counts = np.floor(raw).astype(int)
    for j in np.argsort(-(raw - counts))[: n - counts.sum()]:
        counts[j] += 1
    sz = rng.permutation(np.repeat(np.asarray(sizes, np.int64), counts))
    gaps = rng.permutation(-np.log1p(-(np.arange(n) + 0.5) / n) / rate)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return due, sz


class Runner:
    def __init__(self, cfg: dict, traffic: dict, seed: int, workdir):
        self.cfg, self.traffic, self.seed, self.workdir = cfg, traffic, seed, workdir
        self.shape = np.asarray(cfg["dataset"]["shape"], np.int64)
        self.limit = float(cfg["limits"]["read_rms_gap"])
        self.rate = float(traffic["rate_per_s"])
        self.kept: list = []
        self.missing = 0

    def setup(self) -> None:
        self.payload = reads.Payload(self.cfg, self.seed, self.workdir)
        self.svc = self.payload.serve()
        warm = inputs.host_rng(self.seed, "warm")
        for b in self.traffic["sizes"]:
            for _ in range(2):  # the first compiles, the second runs warm
                self.svc.decode_at(reads.NAME, warm.integers(0, self.shape, (b, len(self.shape))))

    def window(self, seconds: float) -> dict:
        rng = inputs.host_rng(self.seed, "requests")
        due, sizes = schedule(self.rate, seconds, self.traffic["sizes"],
                              self.traffic["probs"], rng)
        flat = rng.integers(0, self.shape, (int(sizes.sum()), len(self.shape)))
        requests = np.split(flat, np.cumsum(sizes)[:-1])
        lat = np.full(len(due), np.nan)
        late = np.zeros(len(due))
        kept, failed, entries = [], 0, 0
        t0 = time.perf_counter() + 0.001
        prev_done = t0
        for k, idx in enumerate(requests):
            target = t0 + due[k]
            with annotate("wait_arrival"):
                wait = target - time.perf_counter()
                if wait > SPIN_S:
                    time.sleep(wait - SPIN_S)
                while time.perf_counter() < target:
                    pass
            issued = time.perf_counter()
            late[k] = issued - max(target, prev_done)
            try:
                with annotate("decode_at"):
                    ans = self.svc.decode_at(reads.NAME, idx)
            except Exception as e:  # noqa: BLE001 - a failed request is counted, not fatal
                failed += 1
                self.error = repr(e)
                prev_done = time.perf_counter()
                continue
            prev_done = time.perf_counter()
            lat[k] = prev_done - target
            entries += len(idx)
            kept.append((idx, ans))
        elapsed = prev_done - t0
        self.kept = kept
        self.missing = failed
        ok = lat[~np.isnan(lat)] * 1e3
        # a request that failed counts as missing every latency limit
        tail = np.concatenate([ok, np.full(failed, np.inf)])
        return {"attempted": len(requests), "failed": failed, "entries": entries,
                "elapsed": elapsed, "read_p95_ms": float(np.percentile(tail, 95)),
                "read_p50_ms": float(np.percentile(tail, 50)),
                "late_p50_ms": float(np.percentile(late, 50) * 1e3),
                "late_p95_ms": float(np.percentile(late, 95) * 1e3),
                "late_max_ms": float(late.max() * 1e3),
                "backlog_end_ms": float((prev_done - (t0 + seconds)) * 1e3)}

    def notes(self, stats: dict) -> dict:
        keys = ("attempted", "entries", "elapsed", "read_p50_ms", "read_p95_ms",
                "late_p50_ms", "late_p95_ms", "late_max_ms", "backlog_end_ms")
        return {f"open_loop.{k}": stats[k] for k in keys}

    def release(self) -> None:
        self.svc.unload(reads.NAME)
        del self.svc

    def check(self):
        return reads.checks(self.payload, self.kept, self.limit, self.missing)
