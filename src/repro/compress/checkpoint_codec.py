"""Compressed checkpoints over the unified codec registry.

Large weight tensors are lossily compressed before hitting disk or the
network: embedding tables, MoE expert banks, and any matrix above
``min_elements``.  Any codec registered in ``repro.codecs`` can back the
compression (``CodecCheckpointConfig.codec``); the default is the paper's
NTTD.  Each compressed leaf is fitness-gated — if the fit cannot reach
``min_fitness`` within its budget, the leaf is stored raw instead (no
silent quality cliffs).  Payloads are the self-describing container
format, so a checkpoint written with one codec restores through the
registry without the reader knowing which codec produced it (legacy
headerless NTTD blobs from older checkpoints still load).

This is the deployment story for the paper's technique at 1000-node
scale: checkpoint shipping and cold-start restore are bandwidth-bound, and
a 10-40x smaller payload directly cuts RPO/restore latency.  Exact-restore
training checkpoints should keep ``enabled=False``; the codec path is for
weight DISTRIBUTION (serving fleets, cross-DC sync, archival).
"""
from __future__ import annotations

import dataclasses
import io
import json
import os
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import codecs, obs
from repro.core import nttd


@dataclasses.dataclass
class CodecCheckpointConfig:
    codec: str = "nttd"              # any name in repro.codecs.available()
    min_elements: int = 1 << 16      # only compress leaves at least this big
    min_fitness: float = 0.95        # fitness gate; below -> store raw
    # NTTD fit knobs (ignored by budget-driven codecs)
    rank: int = 8
    hidden: int = 16
    epochs: int = 15
    batch_size: int = 65536
    lr: float = 1e-2
    reorder: bool = False            # reordering off for speed by default
    seed: int = 0
    # budget for non-NTTD codecs: target payload as a fraction of raw bytes
    budget_ratio: float = 0.125
    fit_opts: dict[str, Any] | None = None  # explicit overrides, passed to fit


def _fit_leaf(arr32: np.ndarray, cfg: CodecCheckpointConfig) -> codecs.Encoded:
    codec = codecs.get_codec(cfg.codec)
    if cfg.fit_opts is not None:
        return codec.fit(arr32, **cfg.fit_opts)
    if cfg.codec == "nttd":
        return codec.fit(
            arr32,
            rank=cfg.rank,
            hidden=cfg.hidden,
            epochs=cfg.epochs,
            batch_size=cfg.batch_size,
            lr=cfg.lr,
            init_reorder=cfg.reorder,
            update_reorder=cfg.reorder,
            seed=cfg.seed,
            entries_per_epoch=min(arr32.size, 2_000_000),
        )
    budget = max(int(arr32.nbytes * cfg.budget_ratio), 1024)
    return codec.fit(arr32, budget)


def compress_tree(tree, cfg: CodecCheckpointConfig | None = None):
    """Returns ({key: payload_bytes_or_raw}, stats).  Keys follow
    checkpoint._flatten naming."""
    from repro.train.checkpoint import _flatten

    cfg = cfg or CodecCheckpointConfig()
    out: dict[str, dict[str, Any]] = {}
    stats = {"raw_bytes": 0, "compressed_bytes": 0, "leaves_codec": 0, "leaves_raw": 0}
    for key, leaf in _flatten(tree):
        arr = np.asarray(leaf)
        raw_nbytes = arr.nbytes
        stats["raw_bytes"] += raw_nbytes
        if arr.size >= cfg.min_elements and arr.ndim >= 2:
            arr32 = arr.astype(np.float32)
            try:
                enc = _fit_leaf(arr32, cfg)
            except ValueError:
                enc = None  # budget infeasible for this codec -> store raw
            fit = enc.fitness(arr32) if enc is not None else -np.inf
            if fit >= cfg.min_fitness:
                blob = codecs.save_bytes(enc)
                out[key] = {
                    "kind": cfg.codec,
                    "data": blob,
                    "fitness": fit,
                    "dtype": str(arr.dtype),
                    "shape": list(arr.shape),
                }
                stats["compressed_bytes"] += len(blob)
                stats["leaves_codec"] += 1
                continue
        buf = io.BytesIO()
        np.save(buf, arr)
        out[key] = {"kind": "raw", "data": buf.getvalue()}
        stats["compressed_bytes"] += len(out[key]["data"])
        stats["leaves_raw"] += 1
    stats["ratio"] = stats["raw_bytes"] / max(stats["compressed_bytes"], 1)
    return out, stats


@dataclasses.dataclass
class VersionedCheckpointConfig:
    """Knobs for :class:`VersionedCheckpointer` (delta-coded v4 stores)."""

    codec: str = "nttd"              # any name in repro.codecs.available()
    min_elements: int = 1 << 16      # only delta-code leaves at least this big
    min_fitness: float = 0.95        # chain gate; below -> fresh keyframe
    keyframe_interval: int = 8       # bound on decode-chain depth
    chunk_bytes: int = 1 << 20
    delta_passes: int = 2
    keyframe_opts: dict[str, Any] | None = None  # passed to Codec.fit
    delta_opts: dict[str, Any] | None = None     # passed to the stream fitter


class VersionedCheckpointer:
    """Checkpoint steps as versions of per-leaf delta stores.

    Step ``N+1`` of every large weight tensor is fitted as a residual
    against the reconstruction of step ``N`` (``repro.temporal``) — a
    training run's consecutive checkpoints differ by one optimizer step,
    so the residual is far cheaper to encode than the tensor.  Leaves
    below ``min_elements`` (or below the fitness gate on their very first
    step) are demoted to raw ``.npz`` per step, permanently: a leaf the
    codec cannot represent at step 0 will not start representing it later.

    Layout under ``directory``::

        manifest.json          key -> {kind, file, dtype, shape}; n_steps
        leaf<i>.tcdc           one v4 delta container per codec leaf
        raw_step<k>.npz        all raw leaves of step k

    Every ``save_step`` ends with the stores synced and the manifest
    rewritten, so the directory restores after a crash mid-run.  A
    reopened checkpointer is restore-only: resuming appends against
    existing stores is not supported (writers start fresh files).
    """

    def __init__(self, directory: str, cfg: VersionedCheckpointConfig | None = None):
        from repro.temporal import VersionedStore

        self.directory = directory
        self.cfg = cfg or VersionedCheckpointConfig()
        self._store_cls = VersionedStore
        os.makedirs(directory, exist_ok=True)
        self._stores: dict[str, Any] = {}   # key -> VersionedStore
        self._leaves: dict[str, dict] = {}  # key -> manifest entry
        self._n_steps = 0
        manifest = os.path.join(directory, "manifest.json")
        if os.path.exists(manifest):
            with open(manifest) as f:
                m = json.load(f)
            self._n_steps = m["n_steps"]
            self._leaves = m["leaves"]

    @property
    def n_steps(self) -> int:
        return self._n_steps

    def _open_store(self, key: str, fname: str):
        cfg = self.cfg
        self._stores[key] = self._store_cls(
            os.path.join(self.directory, fname),
            cfg.codec,
            keyframe_interval=cfg.keyframe_interval,
            chunk_bytes=cfg.chunk_bytes,
            keyframe_opts=cfg.keyframe_opts,
            delta_opts=cfg.delta_opts,
            delta_passes=cfg.delta_passes,
            rekey_below=cfg.min_fitness,
        )

    def save_step(self, tree) -> dict:
        """Append one checkpoint step; returns per-step stats."""
        from repro.train.checkpoint import _flatten

        cfg = self.cfg
        step = self._n_steps
        stats = {"step": step, "bytes": 0, "leaves_store": 0, "leaves_raw": 0,
                 "keyframes": 0, "fitness_min": 1.0}
        raw: dict[str, np.ndarray] = {}
        for i, (key, leaf) in enumerate(_flatten(tree)):
            arr = np.asarray(leaf)
            entry = self._leaves.get(key)
            if entry is None:
                if step != 0:
                    raise ValueError(f"leaf {key!r} appeared after step 0")
                eligible = arr.size >= cfg.min_elements and arr.ndim >= 2
                entry = {
                    "kind": "store" if eligible else "raw",
                    "file": f"leaf{i}.tcdc" if eligible else None,
                    "dtype": str(arr.dtype),
                    "shape": list(arr.shape),
                }
                self._leaves[key] = entry
            if entry["kind"] == "store":
                if key not in self._stores:
                    if step > 0:
                        raise ValueError(
                            "reopened VersionedCheckpointer is restore-only; "
                            "start a new directory to keep appending"
                        )
                    self._open_store(key, entry["file"])
                st = self._stores[key].append(arr.astype(np.float32))
                if step == 0 and st["fitness"] < cfg.min_fitness:
                    # below the gate on its FIRST step: the codec cannot
                    # represent this leaf — demote it to raw permanently
                    self._stores.pop(key).close()
                    os.remove(os.path.join(self.directory, entry["file"]))
                    entry.update(kind="raw", file=None)
                else:
                    stats["bytes"] += st["bytes"]
                    stats["leaves_store"] += 1
                    stats["keyframes"] += int(st["keyframe"])
                    stats["fitness_min"] = min(stats["fitness_min"], st["fitness"])
            if entry["kind"] == "raw":
                raw[key.replace("/", "__")] = arr
        if raw:
            path = os.path.join(self.directory, f"raw_step{step}.npz")
            np.savez(path, **raw)
            stats["bytes"] += os.path.getsize(path)
            stats["leaves_raw"] = len(raw)
        self._n_steps = step + 1
        self._write_manifest()
        return stats

    def _write_manifest(self) -> None:
        tmp = os.path.join(self.directory, "manifest.json.tmp")
        with open(tmp, "w") as f:
            json.dump({"n_steps": self._n_steps, "leaves": self._leaves}, f, indent=1)
        os.replace(tmp, os.path.join(self.directory, "manifest.json"))

    def restore_step(self, step: int, template):
        """Rebuild the tree at ``step`` (lossy for store-backed leaves)."""
        from repro.temporal import VersionedStore
        from repro.train.checkpoint import _unflatten_into

        if not 0 <= step < self._n_steps:
            raise ValueError(f"step {step} out of range [0, {self._n_steps})")
        values: dict[str, np.ndarray] = {}
        raw_path = os.path.join(self.directory, f"raw_step{step}.npz")
        raw = np.load(raw_path) if os.path.exists(raw_path) else {}
        for key, entry in self._leaves.items():
            dtype = np.dtype(entry["dtype"])
            if entry["kind"] == "raw":
                values[key] = np.asarray(raw[key.replace("/", "__")])
            else:
                with VersionedStore.open(
                    os.path.join(self.directory, entry["file"])
                ) as reader:
                    values[key] = reader.decode(version=step).astype(dtype)
        return _unflatten_into(template, values)

    def close(self) -> None:
        for store in self._stores.values():
            store.close()
        self._stores.clear()

    def __enter__(self) -> "VersionedCheckpointer":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()


class RestorePlan:
    """The device restore of a compressed checkpoint's codec leaves.

    Each NTTD leaf is decoded into a flat float32 device buffer of its own
    in fixed slabs (``CompressedTensor.dense_slabs``), with no index or
    value crossing the host per slab.  ``steps`` interleaves the slabs of
    every NTTD leaf, ordered by the fraction of its leaf that a slab
    completes, so that all leaves advance together.  Leaves of other
    codecs have no device decode; ``run`` decodes them on the host.

    Spans: ``ckpt.restore`` (``leaves``, ``entries``) around ``run``, and
    ``ckpt.restore_slab`` (``leaf``, ``entries``, ``d_prime``, and the
    split of the leaf's folded indices: ``tail``, the entries of its tail
    table, and ``rows``, the head rows a slab builds; ``nttd.slab_split``)
    around each slab's dispatch.  ``metrics`` counts
    ``ckpt.restored_entries``.
    """

    def __init__(self, payload: dict, slab: int = nttd.SLAB_ENTRIES):
        from repro.codecs.adapters import NTTDEncoded

        self.slabs: dict[str, nttd.DenseSlabs] = {}
        self.host_leaves: dict[str, tuple[codecs.Encoded, str]] = {}
        for key, item in payload.items():
            if item["kind"] == "raw":
                continue
            enc = codecs.load_bytes(item["data"])
            if isinstance(enc, NTTDEncoded):
                self.slabs[key] = enc.ct.dense_slabs(slab)
            else:
                self.host_leaves[key] = (enc, item["dtype"])
        order = sorted(
            ((k + 1) / s.n_slabs, i, k, key)
            for i, (key, s) in enumerate(self.slabs.items())
            for k in range(s.n_slabs)
        )
        self.steps: list[tuple[str, int]] = [(key, k) for _, _, k, key in order]
        self.buffers: dict[str, jax.Array] = {}
        self.metrics = obs.MetricsRegistry()
        self._restored = self.metrics.counter("ckpt.restored_entries")

    @property
    def entries(self) -> int:
        """Entries of the NTTD leaves, each counted once."""
        return sum(s.n for s in self.slabs.values())

    def allocate(self) -> None:
        """A zeroed float32 device buffer for every NTTD leaf."""
        self.buffers = {key: jnp.zeros((s.n,), jnp.float32) for key, s in self.slabs.items()}

    def step(self, i: int) -> tuple[str, int]:
        """Dispatch step ``i`` of the plan (wrapping), asynchronously;
        returns (leaf, slab)."""
        key, k = self.steps[i % len(self.steps)]
        s = self.slabs[key]
        with obs.span("ckpt.restore_slab", leaf=key, entries=s.entries(k),
                      d_prime=s.d_prime, tail=s.tail, rows=s.rows):
            self.buffers[key] = s.write(self.buffers[key], k)
        self._restored.inc(s.entries(k))
        return key, k

    def run(self) -> dict[str, jax.Array]:
        """Every codec leaf restored: the NTTD leaves' flat buffers after
        the whole plan, the other codecs' leaves decoded on the host and
        put on the device in their own shape and dtype."""
        with obs.span("ckpt.restore", leaves=len(self.slabs) + len(self.host_leaves),
                      entries=self.entries):
            self.allocate()
            for i in range(len(self.steps)):
                self.step(i)
            out = dict(self.buffers)
            for key, (enc, dtype) in self.host_leaves.items():
                out[key] = jax.device_put(np.asarray(enc.to_dense()).astype(np.dtype(dtype)))
        return out


def decompress_tree(payload: dict, template):
    """Inverse of compress_tree (lossy for codec leaves), restored onto the
    device: raw leaves by ``device_put``, codec leaves by a
    ``RestorePlan``.  The container's codec-id header drives decoding, so
    `kind` is informational only."""
    from repro.train.checkpoint import _unflatten_into

    plan = RestorePlan(payload)
    restored = plan.run()
    plan.buffers = {}  # each flat buffer goes once its leaf is reshaped
    values = {}
    for key, item in payload.items():
        if item["kind"] == "raw":
            values[key] = jax.device_put(np.load(io.BytesIO(item["data"])))
        else:
            leaf = restored.pop(key)
            values[key] = leaf.reshape(item["shape"]).astype(item["dtype"])
    return _unflatten_into(template, values)
