"""Streaming fit: ``NTTDStreamFitter.update`` over row-major slabs of the
configuration's tensor, read through the program's ``DenseSource`` from a
cursor drawn from the seed; the cursor wraps at the end of the stream.

Set-up builds one fitter, gives it weights drawn from the seed, and drives
it through its first three updates (which compile the train step); the
window then goes on with that same object.  Those three updates are what
the plain reference follows: each update's loss, the first moment of
Adam after the first (the gradients as the optimizer got them) and each
leaf's change after the third.

Set-up and window run under ``jax.default_matmul_precision`` of the
configuration's ``precision.fit``, so the train step compiles and runs at
the precision the configuration states.

Traffic parameters: ``slab_entries``, ``steps_per_slab``,
``replay_capacity``, ``replay_fraction`` (passed to the fitter as given).
"""
from __future__ import annotations

import gc
import io
import json
import time

import numpy as np

from bench import inputs, reference
from bench.harness import Check, annotate

FIRST_UPDATES = 3


def fitter_seed(seed: int) -> int:
    """The fitter's own seed (its minibatch draws), kept inside 31 bits."""
    return int(seed) % (2**31 - 1)


def slab(x_flat: np.ndarray, shape, slab_entries: int, cursor: int):
    """Row-major slab ``cursor``: original indices [B, d] and values [B]."""
    start = cursor * slab_entries
    stop = min(start + slab_entries, x_flat.size)
    flat = np.arange(start, stop, dtype=np.int64)
    return np.stack(np.unravel_index(flat, shape), axis=1), x_flat[start:stop]


class Runner:
    def __init__(self, cfg: dict, traffic: dict, seed: int, workdir):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.shape = tuple(cfg["dataset"]["shape"])
        self.limits = cfg["limits"]
        self.readings: dict = {}

    def _opts(self) -> dict:
        t, c = self.traffic, self.cfg
        return dict(lr=c["lr"], batch_size=c["batch_size"], steps_per_slab=t["steps_per_slab"],
                    replay_capacity=t["replay_capacity"], replay_fraction=t["replay_fraction"])

    def _precision(self):
        import jax

        return jax.default_matmul_precision(self.cfg["precision"]["fit"])

    def setup(self) -> None:
        with self._precision():
            self._setup()

    def _setup(self) -> None:
        import jax

        from repro import obs
        from repro.stream import DenseSource
        from repro.stream.fit import NTTDStreamFitter

        c = self.cfg
        self.x = inputs.dataset(c, self.seed)
        self.source = DenseSource(self.x, slab_entries=self.traffic["slab_entries"])
        self.fitter = NTTDStreamFitter(
            self.shape, c["rank"], c["hidden"], c["d_prime"], seed=fitter_seed(self.seed),
            kernel_impl="ref", **self._opts(),
        )
        params0 = inputs.params_for(c, self.seed, "fit_init_scales", "fit_init")
        self.params0 = jax.tree.map(np.asarray, params0)
        self.fitter.params = params0
        self.cursor0 = int(inputs.host_rng(self.seed, "cursor").integers(0, self.source.n_slabs))
        self.cursor = self.cursor0
        log = io.StringIO()
        obs.set_fit_log(log)  # the fitter reports each update's loss to it
        try:
            for k in range(FIRST_UPDATES):
                s = self.source.slab_at(self.cursor)
                self.fitter.update(s.indices, s.values)
                if k == 0:
                    self.readings["mu1"] = reference.leaf_norms(self.fitter._opt_state.mu)
                self._advance()
        finally:
            obs.set_fit_log(None)
        self.readings["losses"] = [json.loads(line)["loss"] for line in log.getvalue().splitlines()]
        delta = jax.tree.map(lambda p, q: np.asarray(p) - q, self.fitter.params, self.params0)
        self.readings["change3"] = reference.leaf_norms(delta)

    def _advance(self) -> None:
        self.cursor = (self.cursor + 1) % self.source.n_slabs

    def window(self, seconds: float) -> dict:
        with self._precision():
            return self._window(seconds)

    def _window(self, seconds: float) -> dict:
        import jax

        updates, failed, entries = 0, 0, 0
        t0 = time.perf_counter()
        end = t0 + seconds
        while time.perf_counter() < end:
            with annotate("slab_at"):
                s = self.source.slab_at(self.cursor)
            updates += 1
            try:
                with annotate("update"):
                    self.fitter.update(s.indices, s.values)
            except Exception as e:  # noqa: BLE001 - a failed update is counted, not fatal
                failed += 1
                self.error = repr(e)
            else:
                entries += len(s.values)
            self._advance()
        with annotate("block_until_ready"):
            jax.block_until_ready(self.fitter.params)
        elapsed = time.perf_counter() - t0
        steps = self.traffic["steps_per_slab"] * self.cfg["batch_size"]
        return {"attempted": updates, "failed": failed, "entries": entries,
                "elapsed": elapsed, "fit_entries_per_s": entries / elapsed,
                "updates": updates - failed, "trained_entries": (updates - failed) * steps}

    def notes(self, stats: dict) -> dict:
        return {"updates": stats["updates"], "slab_entries": stats["entries"],
                "window_s": stats["elapsed"], "start_cursor": self.cursor0}

    def release(self) -> None:
        del self.fitter
        gc.collect()

    # -------------------------------------------------------- correctness
    def reference_run(self, mode: str = "highest", fault: str | None = None) -> dict:
        """The reference's readings over the first three updates."""
        import jax

        ref = reference.FitReference(
            shape=self.shape, d_prime=self.cfg["d_prime"], seed=fitter_seed(self.seed),
            params=self.params0, mode=mode, fault=fault, **self._opts(),
        )
        flat = self.x.reshape(-1)
        cursor, losses, mu1 = self.cursor0, [], None
        n_slabs = -(-flat.size // self.traffic["slab_entries"])
        for k in range(FIRST_UPDATES):
            idx, vals = slab(flat, self.shape, self.traffic["slab_entries"], cursor)
            losses.append(ref.update(idx, vals))
            if k == 0:
                mu1 = reference.leaf_norms(ref.mu)
            cursor = (cursor + 1) % n_slabs
        delta = jax.tree.map(lambda p, q: np.asarray(p, np.float64) - q, ref.params, self.params0)
        return {"losses": losses, "mu1": mu1, "change3": reference.leaf_norms(delta)}

    @staticmethod
    def gaps(got: dict, ref: dict) -> dict:
        grads = ref["mu1"]
        return {
            "fit_loss_gap": max(reference.relative_gap(a, b)
                                for a, b in zip(got["losses"], ref["losses"])),
            "fit_grad_gap": reference.worst_leaf_gap(got["mu1"], ref["mu1"], grads)[0],
            "fit_change_gap": reference.worst_leaf_gap(got["change3"], ref["change3"], grads)[0],
        }

    def check(self):
        ref = self.reference_run()
        if len(self.readings.get("losses", [])) != FIRST_UPDATES:
            return [Check("fit_loss_gap", float("inf"), self.limits["fit_loss_gap"])]
        gaps = self.gaps(self.readings, ref)
        return [Check(k, v, float(self.limits[k])) for k, v in gaps.items()]
