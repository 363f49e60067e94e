"""What the read cells share: the payload they serve and the comparison
that decides whether the answers are correct."""
from __future__ import annotations

import numpy as np

from bench import inputs, reference
from bench.harness import Check

NAME = "payload"


class Payload:
    """Weights drawn from the seed at the configuration's widths, with a
    random order per mode, written with ``stream.write_chunked`` and
    served untiled by ``CodecService.load_stream``."""

    def __init__(self, cfg: dict, seed: int, workdir):
        import jax

        from repro.codecs.adapters import NTTDEncoded
        from repro.core import nttd
        from repro.core.codec import CompressedTensor
        from repro.core.folding import make_folding_spec
        from repro.stream import write_chunked

        shape = tuple(cfg["dataset"]["shape"])
        params = jax.tree.map(np.asarray, inputs.params_for(cfg, seed, "payload_scales", "payload"))
        pi = inputs.mode_orders(shape, inputs.host_rng(seed, "orders"))
        norm = cfg["payload_norm"]
        self.shape = shape
        self.ref = {"params": params, "pi": pi, "shape": shape, "d_prime": cfg["d_prime"],
                    "mean": float(norm["mean"]), "std": float(norm["std"])}
        spec = make_folding_spec(shape, cfg["d_prime"])
        if tuple(spec.folded_shape) != tuple(cfg["folded_shape"]):
            raise ValueError(f"folded shape {spec.folded_shape} != {cfg['folded_shape']}")
        ct = CompressedTensor(params, pi, spec,
                              nttd.NTTDConfig(rank=cfg["rank"], hidden=cfg["hidden"]),
                              self.ref["mean"], self.ref["std"])
        self.path = str(workdir / "payload.tcdc")
        write_chunked(self.path, NTTDEncoded(ct))

    def serve(self):
        from repro.serve.codec_service import CodecService

        svc = CodecService()
        svc.load_stream(NAME, self.path)
        return svc


def gather(kept: list[tuple[np.ndarray, np.ndarray]]) -> tuple[np.ndarray, np.ndarray]:
    idx = np.concatenate([k[0] for k in kept])
    served = np.concatenate([np.asarray(k[1], np.float64) for k in kept])
    return idx, served


def checks(payload: Payload, kept, limit: float, missing: int) -> list[Check]:
    """The root mean square gap of the served answers from the reference,
    over the reference's spread, across every answer kept from the window.

    Not the widest gap: on a TPU the program's widest gap is set by a few
    entries and swings sevenfold from seed to seed, up to the control's
    own (PERF.md); the mean square is steady, and one answer altered by a
    hundredth of the spread among a million still lifts it past the limit."""
    if not kept:
        return [Check("read_rms_gap", float("inf"), limit),
                Check("answers_missing", float(max(missing, 1)), 0.0)]
    idx, served = gather(kept)
    ref = reference.decode(payload.ref, idx, "highest")
    return [
        Check("read_rms_gap", reference.rms_gap(served, ref), limit),
        Check("answers_missing", float(missing), 0.0),
    ]


def control_gap(payload: Payload, kept, mode: str = "bf16x3") -> float:
    """The same comparison with the reference, computed at ``mode``, in
    the program's place."""
    idx, _ = gather(kept)
    ref = reference.decode(payload.ref, idx, "highest")
    return reference.rms_gap(reference.decode(payload.ref, idx, mode), ref)
