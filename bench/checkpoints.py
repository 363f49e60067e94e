"""What the restore cells share: a compressed checkpoint drawn from the
seed, stored as ``compress_tree`` stores it, and the comparison that
decides whether a restore is correct."""
from __future__ import annotations

import numpy as np

from bench import inputs, reference
from bench.harness import Check


class Checkpoint:
    """One NTTD payload a leaf of the configuration's ``leaves``, at its
    own d' and the configuration's widths: weights drawn on the device
    from the seed (``payload_scales``) and a random order per mode.
    ``payload`` is ``compress_tree``'s output for those leaves; ``refs``
    holds what ``reference.decode`` needs of each."""

    def __init__(self, cfg: dict, seed: int):
        import jax

        from repro import codecs
        from repro.codecs.adapters import NTTDEncoded
        from repro.core import nttd
        from repro.core.codec import CompressedTensor
        from repro.core.folding import make_folding_spec

        norm = cfg["payload_norm"]
        mean, std = float(norm["mean"]), float(norm["std"])
        ncfg = nttd.NTTDConfig(rank=cfg["rank"], hidden=cfg["hidden"])
        scales = tuple(sorted(cfg["payload_scales"].items()))
        self.payload: dict[str, dict] = {}
        self.refs: dict[str, dict] = {}
        for leaf in cfg["leaves"]:
            key, shape = leaf["key"], tuple(leaf["shape"])
            spec = make_folding_spec(shape, leaf["d_prime"])
            if list(spec.folded_shape) != list(leaf["folded_shape"]):
                raise ValueError(f"{key}: folded shape {spec.folded_shape} != "
                                 f"{leaf['folded_shape']}")
            params = jax.tree.map(np.asarray, inputs.make_params(
                inputs.device_key(seed, f"leaf:{key}"), spec.folded_shape,
                cfg["hidden"], cfg["rank"], scales))
            pi = inputs.mode_orders(shape, inputs.host_rng(seed, f"orders:{key}"))
            ct = CompressedTensor(params, pi, spec, ncfg, mean, std)
            self.payload[key] = {"kind": "nttd", "data": codecs.save_bytes(NTTDEncoded(ct)),
                                 "dtype": "float32", "shape": list(shape)}
            self.refs[key] = {"params": params, "pi": pi, "shape": shape,
                              "d_prime": leaf["d_prime"], "mean": mean, "std": std}


def gaps(ckpt: Checkpoint, kept: dict, mode: str | None = None) -> dict[str, float]:
    """Each leaf's root mean square gap from the reference over its spread:
    of the restored values ``kept[leaf] = (flat indices, values)``, or,
    with ``mode``, of the reference computed at ``mode`` in their place."""
    out = {}
    for key, (flat, values) in kept.items():
        ref_payload = ckpt.refs[key]
        idx = np.stack(np.unravel_index(flat, ref_payload["shape"]), axis=1)
        ref = reference.decode(ref_payload, idx, "highest")
        got = values if mode is None else reference.decode(ref_payload, idx, mode)
        out[key] = reference.rms_gap(got, ref)
    return out


def checks(ckpt: Checkpoint, kept: dict, limit: float, missing: int) -> list[Check]:
    """The worst leaf's ``read_rms_gap``, and the slabs that failed."""
    if not kept:
        return [Check("read_rms_gap", float("inf"), limit),
                Check("answers_missing", float(max(missing, 1)), 0.0)]
    return [Check("read_rms_gap", max(gaps(ckpt, kept).values()), limit),
            Check("answers_missing", float(missing), 0.0)]


def tile_seconds(trace) -> float:
    """Device time of the decode tile (the ``pallas_call`` named
    ``decode_tile``, an operation inside each slab program)."""
    return sum(s for name, s in trace.ops.items()
               if name.startswith("%decode_tile") and "custom-call" in name)
