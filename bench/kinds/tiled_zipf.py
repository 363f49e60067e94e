"""Closed loop, one caller, through the decode-tile cache: the payload is
served by ``CodecService.load_stream(..., tile_entries=T)`` under a
``cache_bytes`` of ``cache_share`` of the decoded bytes of all its tiles.
A request is ``request_entries`` entries inside one tile: the tile drawn
Zipf(``zipf_s``) over a seeded permutation of the tiles, the offsets
uniform within it.  A hit is answered from the cached tile on the host; a
miss decodes the whole tile (T entries) on the device.

Set-up decodes the last (short) tile, then sends the same mix from a seed
stream of its own until the cache has evicted a tile, so the window starts
with a full cache.  ``correct`` is decided as in the bulk-read cells.

Besides the rate, the window counts the entries the misses decoded (a
whole tile each) and times every request, so that a slow run shows
whether its time went to slower misses, slower hits or a stall: the
notes give each kind's median, the slowest request, the requests done in
each second of the window and the garbage collections run in it.

Traffic parameters: ``tile_entries``, ``request_entries``, ``zipf_s``,
``cache_share``, ``check_fraction``.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import inputs, reads
from bench.harness import annotate


class Runner:
    def __init__(self, cfg: dict, traffic: dict, seed: int, workdir):
        self.cfg, self.traffic, self.seed, self.workdir = cfg, traffic, seed, workdir
        self.shape = tuple(cfg["dataset"]["shape"])
        self.limit = float(cfg["limits"]["read_rms_gap"])
        self.n = int(np.prod(self.shape))
        self.tile = int(traffic["tile_entries"])
        self.n_tiles = -(-self.n // self.tile)
        self.hot = inputs.host_rng(seed, "hot_tiles").permutation(self.n_tiles)
        weights = 1.0 / np.arange(1, self.n_tiles + 1, dtype=np.float64) ** traffic["zipf_s"]
        self.cdf = np.cumsum(weights) / weights.sum()
        self.kept: list = []
        self.missing = 0

    @property
    def cache_bytes(self) -> int:
        """``cache_share`` of the decoded (float32) bytes of every tile."""
        return int(self.traffic["cache_share"] * self.n * np.dtype(np.float32).itemsize)

    def _tile_of(self, rng) -> int:
        rank = min(int(np.searchsorted(self.cdf, rng.random())), self.n_tiles - 1)
        return int(self.hot[rank])

    def _tile_length(self, tid: int) -> int:
        return min(self.tile, self.n - tid * self.tile)

    def _request(self, rng, tid: int) -> np.ndarray:
        start = tid * self.tile
        flat = start + rng.integers(0, self._tile_length(tid), int(self.traffic["request_entries"]))
        return np.stack(np.unravel_index(flat, self.shape), axis=1).astype(np.int64)

    def setup(self) -> None:
        from repro.serve.codec_service import CodecService

        self.payload = reads.Payload(self.cfg, self.seed, self.workdir)
        self.svc = CodecService(cache_bytes=self.cache_bytes)
        self.svc.load_stream(reads.NAME, self.payload.path, tile_entries=self.tile)
        warm = inputs.host_rng(self.seed, "warm")
        for _ in range(2):  # the short last tile: the first compiles, the second runs warm
            self.svc.decode_at(reads.NAME, self._request(warm, self.n_tiles - 1))
        while self.svc.cache_stats.evictions == 0:
            self.svc.decode_at(reads.NAME, self._request(warm, self._tile_of(warm)))

    def window(self, seconds: float) -> dict:
        rng = inputs.host_rng(self.seed, "requests")
        keep = inputs.host_rng(self.seed, "keep")
        frac = float(self.traffic["check_fraction"])
        # a request looks up one tile; the payload's body is resident, so
        # every miss is a tile's
        info = self.svc.info(reads.NAME)
        stats0 = (info.cache_misses, self.svc.cache_stats.evictions)
        gc0 = sum(g["collections"] for g in gc.get_stats())
        kept, attempted, failed, entries, decoded = [], 0, 0, 0, 0
        times: dict[bool, list[float]] = {False: [], True: []}  # by miss
        ends: list[float] = []
        t0 = time.perf_counter()
        end = t0 + seconds
        while time.perf_counter() < end:
            with annotate("make_request"):
                tid = self._tile_of(rng)
                idx = self._request(rng, tid)
            attempted += 1
            misses = info.cache_misses
            t = time.perf_counter()
            try:
                with annotate("decode_at"):
                    ans = self.svc.decode_at(reads.NAME, idx)
            except Exception as e:  # noqa: BLE001 - a failed request is counted, not fatal
                failed += 1
                self.error = repr(e)
                continue
            done = time.perf_counter()
            missed = info.cache_misses > misses
            times[missed].append(done - t)
            ends.append(done - t0)
            decoded += self._tile_length(tid) if missed else 0
            entries += len(idx)
            if attempted == 1 or keep.random() < frac:
                kept.append((idx, ans))
        elapsed = time.perf_counter() - t0
        self.kept = kept
        self.missing = failed
        return {"attempted": attempted, "failed": failed, "entries": entries,
                "elapsed": elapsed, "read_entries_per_s": entries / elapsed,
                "tile_misses": info.cache_misses - stats0[0],
                "evictions": self.svc.cache_stats.evictions - stats0[1],
                "decoded_entries": decoded, "hit_s": times[False], "miss_s": times[True],
                "request_ends_s": ends,
                "gc_collections": sum(g["collections"] for g in gc.get_stats()) - gc0}

    def notes(self, stats: dict) -> dict:
        done = stats["attempted"] - stats["failed"]
        every = stats["hit_s"] + stats["miss_s"]

        def median_ms(xs):
            return 1e3 * float(np.median(xs)) if xs else None

        per_second = np.bincount(np.asarray(stats["request_ends_s"], int),
                                 minlength=int(np.ceil(stats["elapsed"])))
        return {"requests": stats["attempted"], "entries": stats["entries"],
                "window_s": stats["elapsed"], "cache_bytes": self.cache_bytes,
                "tiles": self.n_tiles, "tile_misses": stats["tile_misses"],
                "evictions": stats["evictions"],
                "tile_hit_share": 1 - stats["tile_misses"] / done if done else None,
                "decoded_entries": stats["decoded_entries"],
                "hit_ms_median": median_ms(stats["hit_s"]),
                "miss_ms_median": median_ms(stats["miss_s"]),
                "slowest_ms": 1e3 * max(every) if every else None,
                "requests_over_50ms": sum(t > 0.05 for t in every),
                "requests_each_s": per_second.tolist(),
                "gc_collections": stats["gc_collections"],
                "answers_kept": len(self.kept)}

    def release(self) -> None:
        self.svc.unload(reads.NAME)
        del self.svc

    def check(self):
        return reads.checks(self.payload, self.kept, self.limit, self.missing)
