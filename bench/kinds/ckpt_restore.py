"""Closed loop, one caller: a compressed checkpoint's restore onto the
device, one slab a request.  A request is the next step of
``RestorePlan.steps`` (one slab of ``slab_entries`` consecutive entries of
one leaf, in original order, written into that leaf's device buffer),
waited for before the next is sent.  The plan interleaves the leaves; the
window starts at a step drawn from the seed and wraps.

Every leaf's buffer is allocated in set-up and stays resident; set-up
also runs one slab of each distinct slab program (leaves of one shape and
d' share one), which compiles it.  The notes split set-up into its
phases.  After the window a seeded
``check_fraction`` of each slab's entries is read back from the buffers at
original indices and compared with the reference decode of its leaf.

Traffic parameters: ``slab_entries``, ``check_fraction``.
"""
from __future__ import annotations

import time

import numpy as np

from bench import checkpoints, inputs
from bench.harness import annotate


class Runner:
    def __init__(self, cfg: dict, traffic: dict, seed: int, workdir):
        self.cfg, self.traffic, self.seed, self.workdir = cfg, traffic, seed, workdir
        self.limit = float(cfg["limits"]["read_rms_gap"])
        self.kept: dict = {}
        self.missing = 0

    def setup(self) -> None:
        import jax

        from repro.compress.checkpoint_codec import RestorePlan

        clock = [time.perf_counter()]

        def phase():
            clock.append(time.perf_counter())
            return clock[-1] - clock[-2]

        self.ckpt = checkpoints.Checkpoint(self.cfg, self.seed)
        self.phases = {"setup_payload_s": phase()}
        self.plan = RestorePlan(self.ckpt.payload, slab=int(self.traffic["slab_entries"]))
        self.phases["setup_plan_s"] = phase()
        self.plan.allocate()
        jax.block_until_ready(self.plan.buffers)
        self.phases["setup_allocate_s"] = phase()
        first = {}
        for i, (key, _) in enumerate(self.plan.steps):
            s = self.plan.slabs[key]
            first.setdefault((s.shape, s.d_prime), i)
        for i in first.values():
            self.plan.step(i)
        jax.block_until_ready(self.plan.buffers)
        self.phases["setup_warm_s"] = phase()
        self.phases["slab_programs"] = len(first)
        self.start = int(inputs.host_rng(self.seed, "plan").integers(len(self.plan.steps)))

    def window(self, seconds: float) -> dict:
        rng = inputs.host_rng(self.seed, "check")
        frac = float(self.traffic["check_fraction"])
        picks: dict[str, list] = {}
        restored: dict[str, int] = {}
        attempted, failed, entries, i = 0, 0, 0, self.start
        t0 = time.perf_counter()
        end = t0 + seconds
        while time.perf_counter() < end:
            attempted += 1
            try:
                with annotate("restore_slab"):
                    key, k = self.plan.step(i)
                    self.plan.buffers[key].block_until_ready()
            except Exception as e:  # noqa: BLE001 - a failed slab is counted, not fatal
                failed += 1
                self.error = repr(e)
                i += 1
                continue
            i += 1
            s = self.plan.slabs[key]
            n = s.entries(k)
            entries += n
            restored[key] = restored.get(key, 0) + n
            picks.setdefault(key, []).append(
                k * s.slab + rng.integers(0, n, max(int(n * frac), 1)))
        elapsed = time.perf_counter() - t0
        self.picks = {key: np.concatenate(v) for key, v in picks.items()}
        self.missing = failed
        return {"attempted": attempted, "failed": failed, "entries": entries,
                "elapsed": elapsed, "read_entries_per_s": entries / elapsed,
                "restored": restored}

    def notes(self, stats: dict) -> dict:
        counter = self.plan.metrics.counter("ckpt.restored_entries").value
        return {"slabs": stats["attempted"], "entries": stats["entries"],
                "window_s": stats["elapsed"], "leaves_in_window": len(stats["restored"]),
                "resident_bytes": 4 * self.plan.entries, "restored_entries_counter": counter,
                **self.phases}

    def release(self) -> None:
        """Read back the entries kept for the comparison, then free the
        buffers."""
        import jax.numpy as jnp

        self.kept = {key: (flat, np.asarray(self.plan.buffers[key][jnp.asarray(flat)],
                                            np.float64))
                     for key, flat in self.picks.items()}
        self.plan.buffers = {}

    def check(self):
        return checkpoints.checks(self.ckpt, self.kept, self.limit, self.missing)
