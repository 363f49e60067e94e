"""Bring-up smoke of TensorCodec's main path on one TPU chip.

    python3 chip_smoke.py [--seed N]

One process, through the entry points a user calls:

1. data   — ``synthetic_tensors.load("pems_sf", mini=False)``: the paper's
            Table II shape, 963 x 144 x 440 (61,016,320 f32 entries),
            generated from the seed;
2. fit    — ``get_codec("nttd").fit`` at the ``MEDIUM`` widths of
            ``configs/tensorcodec_paper.py`` (rank 10, hidden 18, batch
            8192, lr 1e-2, TSP init).  Only the work is cut: a few epochs
            over a capped number of entries each;
3. write  — ``stream.write_chunked`` into ``chip_smoke_out/``;
4. serve  — ``CodecService.load_stream`` untiled and with
            ``tile_entries``, and a 2-instance in-process ``FleetFrontend``
            over the same file, each via ``decode_at`` and via
            ``submit``/``flush``, with tracing on;
5. check  — every answer against the plain f32 oracle
            (``kernels/ref.py:nttd_decode_tile`` under
            ``jax.default_matmul_precision("highest")``) at the same folded
            positions of the fitted params; no failed ticket and no
            excluded instance; and, on the chip, the fleet bit for bit
            against the single service and the ``kernel_decode`` span on
            the Pallas kernel.

Each measurement goes on its own line; the last line is one JSON object,
``{"ok": true, "device": {...}}``.  Without a TPU the script exits
non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import obs  # noqa: E402
from repro.codecs import get_codec  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs.tensorcodec_paper import MEDIUM  # noqa: E402
from repro.core import nttd  # noqa: E402
from repro.data import synthetic_tensors  # noqa: E402
from repro.fleet import FleetFrontend  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.serve.codec_service import CodecService  # noqa: E402
from repro.stream import write_chunked  # noqa: E402

DATASET = "pems_sf"
#: cuts of work (never of width) that keep the run inside its time limit
EPOCHS = 4
ENTRIES_PER_EPOCH = 1 << 23
BATCH_SIZES = (1, 1000, 65536)
TILE_ENTRIES = 65536
CHUNK_BYTES = 4096  # several chunks, so both fleet instances own some
#: max |served - reference| over the tensor's std (the payload's norm_std)
TOL = 1e-4
OUT_DIR = ROOT / "chip_smoke_out"

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _say(key: str, value) -> None:
    print(f"{key}: {value}", flush=True)


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


@contextlib.contextmanager
def _compile_clock():
    """Count XLA backend compiles (JAX's monitoring event) inside the block;
    yields {"count", "seconds"}, filled as compiles happen."""
    clock = {"count": 0, "seconds": 0.0}

    def on_duration(event: str, duration: float, **_kw) -> None:
        if event == _COMPILE_EVENT:
            clock["count"] += 1
            clock["seconds"] += duration

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        yield clock
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)


def fit(x: np.ndarray, seed: int):
    """The NTTD fit at MEDIUM widths; returns (encoded, report)."""
    t0 = time.perf_counter()
    with _compile_clock() as compiles:
        enc = get_codec("nttd").fit(
            x, rank=MEDIUM.rank, hidden=MEDIUM.hidden, batch_size=MEDIUM.batch_size,
            lr=MEDIUM.lr, init_reorder=MEDIUM.init_reorder, epochs=EPOCHS,
            entries_per_epoch=ENTRIES_PER_EPOCH, seed=seed,
        )
    seconds = time.perf_counter() - t0
    log = enc.log
    per_epoch = min(ENTRIES_PER_EPOCH, x.size)
    bsz = min(MEDIUM.batch_size, per_epoch)
    trained = log.epochs_run * (per_epoch // bsz) * bsz
    return enc, {
        "fit_seconds": seconds,
        "fit_compile_seconds": compiles["seconds"],
        "tsp_init_seconds": log.seconds_init_order,
        "train_seconds": log.seconds_train,
        "entries_trained": trained,
        "train_entries_per_s": trained / log.seconds_train,
        "epochs_run": log.epochs_run,
        "loss_first": log.loss_history[0],
        "loss_last": log.loss_history[-1],
        "fitness_first": log.fitness_history[0],
        "fitness_last": log.fitness_history[-1],
    }


def _folded(ct, indices: np.ndarray) -> jax.Array:
    pos = np.stack([ct.inv_pi[j][indices[:, j]] for j in range(indices.shape[1])], 1)
    return ct.spec.fold_indices(jnp.asarray(pos, jnp.int32))


def _unnormalize(ct, vals) -> np.ndarray:
    return np.asarray(vals, np.float64) * ct.norm_std + ct.norm_mean


def reference(ct, indices: np.ndarray) -> np.ndarray:
    """Plain f32 oracle of the whole decode chain at full matmul precision."""
    operands = nttd.fused_decode_inputs(ct.params, ct.spec, ct.cfg)
    with jax.default_matmul_precision("highest"):
        vals = jax.jit(ref.nttd_decode_tile)(_folded(ct, indices), *operands)
    return _unnormalize(ct, vals)


def decode_direct(ct, indices: np.ndarray, interpret: bool) -> np.ndarray:
    """The fused decode tile called straight (under the Pallas interpreter
    with ``interpret``)."""
    operands = nttd.fused_decode_inputs(ct.params, ct.spec, ct.cfg)
    return _unnormalize(
        ct, ops.nttd_decode_tile(_folded(ct, indices), *operands, interpret=interpret))


def serve(path: str, batches: list[np.ndarray]) -> tuple[dict, dict]:
    """Answer every batch through each serving surface; returns
    ({surface: [answers per batch]}, counters)."""
    untiled, tiled = CodecService(), CodecService()
    untiled.load_stream("t", path)
    tiled.load_stream("t", path, tile_entries=TILE_ENTRIES)
    fleet = FleetFrontend(2)
    fleet.load_stream("t", path)
    answers: dict[str, list[np.ndarray]] = {}
    seconds: dict[str, float] = {}
    failed = 0
    try:
        for label, svc in (("untiled", untiled), ("tiled", tiled), ("fleet", fleet)):
            t0 = time.perf_counter()
            answers[f"{label}.decode_at"] = [svc.decode_at("t", b) for b in batches]
            tickets = [svc.submit("t", b) for b in batches]
            out = svc.flush()
            failed += len(svc.failed)
            answers[f"{label}.submit"] = [out.get(t) for t in tickets]
            seconds[label] = time.perf_counter() - t0
        excluded = sorted(fleet.excluded)
    finally:
        fleet.close()
        untiled.unload("t")
        tiled.unload("t")
    return answers, {
        "serve_seconds": seconds, "failed_tickets": failed, "excluded_instances": excluded,
    }


def decode_rates(ct, indices: np.ndarray, interpret: bool) -> dict[str, float]:
    """Warm entries/s of the decode tile and of the XLA oracle at full
    precision, on device-resident operands (host clock, median of 3 calls
    after one warm-up)."""
    folded = _folded(ct, indices)
    operands = nttd.fused_decode_inputs(ct.params, ct.spec, ct.cfg)
    with jax.default_matmul_precision("highest"):
        oracle = jax.jit(ref.nttd_decode_tile)
        fns = {
            "tile": lambda: ops.nttd_decode_tile(folded, *operands, interpret=interpret),
            "xla_oracle": lambda: oracle(folded, *operands),
        }
        rates = {}
        for name, fn in fns.items():
            fn().block_until_ready()
            times = []
            for _ in range(3):
                t0 = time.perf_counter()
                fn().block_until_ready()
                times.append(time.perf_counter() - t0)
            rates[name] = len(indices) / float(np.median(times))
    return rates


def run(
    seed: int = 0, *, mini: bool = False, interpret: bool = False, out_dir: Path = OUT_DIR
) -> dict:
    """Every phase once; raises on the first failed check and returns the
    report.  ``mini`` takes the dataset's CPU-sized shape, and
    ``interpret`` runs the directly called decode tile under the Pallas
    interpreter."""
    rep: dict = {}
    t0 = time.perf_counter()
    x = synthetic_tensors.load(DATASET, mini=mini, seed=seed)
    rep["data_shape"] = x.shape
    rep["data_seconds"] = time.perf_counter() - t0

    enc, fit_rep = fit(x, seed)
    rep.update(fit_rep)
    _check(fit_rep["loss_last"] < fit_rep["loss_first"], "fit loss did not fall")
    _check(fit_rep["fitness_last"] > fit_rep["fitness_first"], "fitness did not rise")

    out_dir.mkdir(parents=True, exist_ok=True)
    path = str(out_dir / f"{DATASET}_seed{seed}.tcdc")
    rep["file_bytes"] = write_chunked(path, enc, chunk_bytes=CHUNK_BYTES)

    rng = np.random.default_rng(seed)
    batches = [
        np.stack([rng.integers(0, n, size=b) for n in x.shape], axis=1) for b in BATCH_SIZES
    ]
    was_tracing = obs.enabled()
    obs.enable_tracing()
    obs.get_recorder().clear()
    try:
        with _compile_clock() as compiles:
            answers, counters = serve(path, batches)
        spans = obs.get_recorder().drain()
    finally:
        if not was_tracing:
            obs.disable_tracing()
    rep.update(counters)
    rep["serve_compiles"] = compiles["count"]
    rep["serve_compile_seconds"] = compiles["seconds"]
    # host-clock seconds per span name; spans nest, so these overlap
    span_seconds: dict[str, float] = {}
    for s in spans:
        span_seconds[s.name] = span_seconds.get(s.name, 0.0) + (s.t_end - s.t_start)
    rep["span_seconds"] = span_seconds
    rep["served_impls"] = sorted(
        {s.attrs.get("impl") for s in spans if s.name == "kernel_decode"}
    )
    _check(counters["failed_tickets"] == 0, f"{counters['failed_tickets']} failed tickets")
    _check(not counters["excluded_instances"], f"excluded {counters['excluded_instances']}")

    ct = enc.ct
    refs = [reference(ct, b) for b in batches]
    answers["direct.tile"] = [decode_direct(ct, b, interpret) for b in batches]
    errors = {}
    for surface, outs in answers.items():
        _check(all(o is not None for o in outs), f"{surface}: a ticket went unanswered")
        errors[surface] = max(
            float(np.max(np.abs(np.asarray(o, np.float64) - r))) / ct.norm_std
            for o, r in zip(outs, refs)
        )
    rep["decode_entries_per_s"] = decode_rates(ct, batches[-1], interpret)
    rep["max_err_over_std"] = errors
    rep["tolerance"] = TOL
    worst = max(errors, key=errors.get)
    _check(errors[worst] <= TOL, f"{worst}: error/std {errors[worst]} > {TOL}")
    # entries where the fleet's answer is not bit-identical to the single
    # service's.  XLA:CPU's dots can round differently for different batch
    # sizes, and the fleet splits batches by owner, so main() (on the chip)
    # holds this to zero and a CPU run only reports it
    rep["fleet_bit_mismatches"] = sum(
        int(np.sum(f != u))
        for kind in ("decode_at", "submit")
        for f, u in zip(answers[f"fleet.{kind}"], answers[f"untiled.{kind}"])
    )
    return rep


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0, help="seed of data and requests")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}", file=sys.stderr)
        return 1
    enable_compile_cache()
    shape = synthetic_tensors.DATASETS[DATASET].shape
    _say("cut", f"epochs {MEDIUM.epochs} -> {EPOCHS}")
    _say("cut", f"entries_per_epoch {int(np.prod(shape))} -> {ENTRIES_PER_EPOCH}")
    rep = run(args.seed)
    for key, value in rep.items():
        _say(key, value)
    _check(rep["served_impls"] == ["pallas"], f"kernel_decode ran {rep['served_impls']}")
    _check(rep["fleet_bit_mismatches"] == 0, "fleet answers differ from the single service")
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
