"""TensorCodec: the end-to-end compressor (paper Alg. 1).

Alternating optimization:
  1. init pi (2-approx metric TSP, §IV-D) and theta (NTTD, §IV-B)
  2. minibatch-Adam epochs on theta over entries of the reordered, folded
     tensor
  3. every ``reorder_every`` epochs: Alg. 3 pi refinement, then Adam state
     re-initialization (paper: the loss surface changed)
  4. stop when fitness converges

The training step is a single pjit-able program (data-parallel over
sampled entries); ``shard_batch`` hooks it onto a mesh when one is active.

Prefer the unified API for new code — this module is the NTTD backend
behind ``repro.codecs.get_codec("nttd").fit(x, budget)``, which also
handles serialization and budget-matched comparisons against the other
registered codecs.
"""
from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.codecs.indexing import flat_to_multi
from repro.core import nttd, reorder
from repro.core.folding import FoldingSpec, make_folding_spec
from repro.kernels import ops
from repro.optim import optimizers


@dataclasses.dataclass
class CodecConfig:
    rank: int = 8
    hidden: int = 16
    d_prime: int | None = None
    epochs: int = 60
    batch_size: int = 16384
    lr: float = 5e-3
    init_reorder: bool = True      # TSP init (off => TensorCodec-T ablation)
    update_reorder: bool = True    # Alg.3 refinement (off => TensorCodec-R)
    reorder_every: int = 5         # epochs between Alg.3 sweeps
    reorder_warmup: int = 5        # epochs of theta fitting before first sweep
    reorder_samples: int = 4096    # sampled entries per slice for delta-loss
    normalize: bool = True         # standardize input (2 floats in payload)
    seed: int = 0
    entries_per_epoch: int | None = None  # cap for very large tensors
    tol: float = 1e-4              # fitness convergence tolerance
    patience: int = 3
    eval_batch: int = 65536
    verbose: bool = False


@dataclasses.dataclass
class CompressedTensor:
    """The compressed payload D = (theta, pi) plus folding/norm metadata."""

    params: nttd.Params
    pi: list[np.ndarray]
    spec: FoldingSpec
    cfg: nttd.NTTDConfig
    norm_mean: float = 0.0
    norm_std: float = 1.0

    @functools.cached_property
    def inv_pi(self) -> list[np.ndarray]:
        """Per-mode inverse permutations (original index -> position).

        The argsort is O(N_k log N_k) per mode; computed once and reused by
        ``decode``, ``to_dense``, and the serve-layer decode path.
        """
        return [np.argsort(p) for p in self.pi]

    # -- reconstruction ------------------------------------------------------
    def decode(self, indices: np.ndarray) -> np.ndarray:
        """Approximate entries at ORIGINAL indices [B, d] -> [B].

        The fused decode tile (``ops.nttd_decode_tile``) answers, and the
        training chain (``nttd.apply``) where the tile cannot (see
        ``_decode_operands``).  Spans: ``payload.orig_to_pos`` (the host
        gather), ``nttd.fold`` (upload and the eager fold),
        ``nttd.operands``, then the tile's ``kernel_decode``, and
        ``payload.device_wait`` (the host blocked on the answer)."""
        with obs.span("payload.decode", entries=len(indices)):
            with obs.span("payload.orig_to_pos"):
                pos = self._orig_to_pos(indices)
            with obs.span("nttd.fold"):
                folded = self.spec.fold_indices(jnp.asarray(pos, jnp.int32))
            operands = self._decode_operands()
            if operands is None:
                vals = nttd.apply(self.params, folded, self.spec, self.cfg)
            else:
                vals = ops.nttd_decode_tile(folded, *operands)
            with obs.span("payload.device_wait"):
                vals = np.asarray(vals)
            return vals * self.norm_std + self.norm_mean

    def _decode_operands(self) -> tuple[jax.Array, ...] | None:
        """The fused decode's operands (``nttd.fused_decode_inputs``), on
        the device and stacked once per parameter set; ``None`` for a
        folding of fewer than two steps, which the tile refuses (only an
        explicit ``d_prime=1`` over short modes makes one).

        Kept beside the ``params`` object they were built from, outside
        the dataclass fields, and rebuilt when ``self.params`` is another
        object (an ``id`` may be reused once the old tree is collected).
        The ``nttd.operands`` span wraps the lookup; its ``built`` is 1
        when this call stacked them and 0 when it reused them."""
        if self.spec.d_prime < 2:
            return None
        params = self.params  # one read: the pair stored must match
        built_from, operands = getattr(self, "_operands", (None, None))
        built = built_from is not params
        with obs.span("nttd.operands", built=int(built)):
            if built:
                operands = jax.device_put(
                    nttd.fused_decode_inputs(params, self.spec, self.cfg)
                )
                self._operands = (params, operands)
        return operands

    def dense_slabs(self, slab: int = nttd.SLAB_ENTRIES) -> nttd.DenseSlabs:
        """The dense decode in ORIGINAL index order, on the device, slab by
        slab (``nttd.DenseSlabs``)."""
        return nttd.DenseSlabs(self.params, self.spec, self.cfg, self.inv_pi,
                               self.norm_mean, self.norm_std, slab)

    def to_dense(self) -> np.ndarray:
        """Full reconstruction in ORIGINAL index order, as a host array."""
        return np.asarray(self.dense_slabs().dense())

    def fitness(self, x: np.ndarray) -> float:
        """1 - ||x - approx|| / ||x||, the squared error summed on the
        device slab by slab (the approximation never reaches the host)."""
        norm = float(np.linalg.norm(np.asarray(x, np.float64)))
        err = np.sqrt(self.dense_slabs().sq_err(x))
        return 1.0 - err / max(norm, 1e-30)

    def _orig_to_pos(self, indices: np.ndarray) -> np.ndarray:
        inv = self.inv_pi
        pos = np.empty_like(indices)
        for j in range(indices.shape[-1]):
            pos[..., j] = inv[j][indices[..., j]]
        return pos

    # -- payload accounting (paper §V-A conventions) ---------------------------
    def payload_bits(self, bytes_per_param: int = 8) -> int:
        return nttd_payload_bits(
            nttd.count_params(self.params), self.spec.shape, bytes_per_param
        )

    def payload_bytes(self, bytes_per_param: int = 8) -> int:
        return (self.payload_bits(bytes_per_param) + 7) // 8


def nttd_payload_bits(
    n_params: int, shape: tuple[int, ...], bytes_per_param: int = 8
) -> int:
    """Paper §V-A: theta at ``bytes_per_param``, pi at ceil(log2 N_k) bits
    per index, plus the two normalization floats."""
    theta_bits = n_params * bytes_per_param * 8
    pi_bits = sum(
        n * max(int(np.ceil(np.log2(n))), 1) if n > 1 else 0 for n in shape
    )
    norm_bits = 2 * bytes_per_param * 8
    return theta_bits + pi_bits + norm_bits


@dataclasses.dataclass
class CompressionLog:
    fitness_history: list[float]
    loss_history: list[float]
    reorder_stats: list[list[reorder.SwapStats]]
    seconds_init_order: float = 0.0
    seconds_train: float = 0.0
    seconds_reorder: float = 0.0
    epochs_run: int = 0


def _make_train_epoch(spec: FoldingSpec, cfg: nttd.NTTDConfig, opt):
    """Whole-epoch jitted step: lax.scan over minibatches.

    One device round-trip per epoch instead of per minibatch — this is both
    the CPU-speed fix and the shape the pjit program takes on the mesh
    (positions/values sharded on the batch axis).
    """

    def loss_fn(params, positions, values):
        preds = nttd.apply_at_positions(params, positions, spec, cfg)
        return jnp.sum(jnp.square(preds - values))

    @jax.jit
    def epoch(params, opt_state, positions, values):
        # positions: [S, B, d] int32; values: [S, B]
        def body(carry, xs):
            # a stable name for the step's operations in a device trace
            with jax.named_scope("train_epoch"):
                params, opt_state = carry
                pos, val = xs
                loss, grads = jax.value_and_grad(loss_fn)(params, pos, val)
                updates, opt_state = opt.update(grads, opt_state, params)
                params = optimizers.apply_updates(params, updates)
                return (params, opt_state), loss

        (params, opt_state), losses = jax.lax.scan(
            body, (params, opt_state), (positions, values)
        )
        return params, opt_state, jnp.sum(losses)

    return epoch


def compress(
    x: np.ndarray, config: CodecConfig | None = None
) -> tuple[CompressedTensor, CompressionLog]:
    config = config or CodecConfig()
    rng = np.random.default_rng(config.seed)
    x = np.asarray(x, dtype=np.float32)
    d = x.ndim
    spec = make_folding_spec(x.shape, config.d_prime)
    cfg = nttd.NTTDConfig(rank=config.rank, hidden=config.hidden)

    mean, std = 0.0, 1.0
    if config.normalize:
        mean = float(x.mean())
        std = float(x.std()) or 1.0
    xn = (x - mean) / std

    log = CompressionLog([], [], [])

    # ---- pi init ------------------------------------------------------------
    t0 = time.time()
    if config.init_reorder:
        pi = reorder.tsp_init(xn)
    else:
        pi = reorder.identity_orders(x.shape)
    log.seconds_init_order = time.time() - t0

    # ---- theta init ------------------------------------------------------------
    key = jax.random.PRNGKey(config.seed)
    params = nttd.init_params(key, spec, cfg)
    opt = optimizers.adam(config.lr)
    opt_state = opt.init(params)
    train_epoch = _make_train_epoch(spec, cfg, opt)
    predict_jit = nttd.make_predict(spec, cfg)

    n_entries = int(np.prod(x.shape))
    per_epoch = min(config.entries_per_epoch or n_entries, n_entries)
    bsz = min(config.batch_size, per_epoch)
    steps = max(per_epoch // bsz, 1)

    def epoch_positions() -> np.ndarray:
        if per_epoch == n_entries:
            flat = rng.permutation(n_entries)[: steps * bsz]
        else:
            flat = rng.integers(0, n_entries, size=steps * bsz)
        return flat_to_multi(flat, x.shape)  # [steps*bsz, d]

    def values_at(pos: np.ndarray) -> np.ndarray:
        orig = np.empty_like(pos)
        for j in range(d):
            orig[:, j] = pi[j][pos[:, j]]
        return xn[tuple(orig[:, j] for j in range(d))]

    # fitness in position space: ||X_pi - approx|| == ||X - approx_orig||
    eval_n = min(n_entries, 4_000_000)
    eval_exhaustive = eval_n == n_entries

    def eval_fitness() -> float:
        if eval_exhaustive:
            flat = np.arange(n_entries, dtype=np.int64)
        else:
            flat = rng.integers(0, n_entries, size=eval_n)
        err2 = 0.0
        norm2 = 0.0
        for s in range(0, eval_n, config.eval_batch):
            pos = flat_to_multi(flat[s : s + config.eval_batch], x.shape)
            truth = values_at(pos).astype(np.float64)
            pad = config.eval_batch - pos.shape[0]
            if pad:
                pos = np.pad(pos, ((0, pad), (0, 0)))
            preds = np.asarray(
                predict_jit(params, jnp.asarray(pos, jnp.int32))
            ).astype(np.float64)[: truth.shape[0]]
            # fitness is defined on the RAW tensor: un-normalize both sides
            err2 += float(((preds - truth) ** 2).sum()) * std * std
            norm2 += float(((truth * std + mean) ** 2).sum())
        return 1.0 - np.sqrt(err2) / max(np.sqrt(norm2), 1e-30)

    best_fit = -np.inf
    best_snapshot = None
    stall = 0
    for epoch in range(config.epochs):
        t0 = time.time()
        pos_all = epoch_positions()
        vals_all = values_at(pos_all)
        params, opt_state, total_loss = train_epoch(
            params,
            opt_state,
            jnp.asarray(pos_all.reshape(steps, bsz, d), jnp.int32),
            jnp.asarray(vals_all.reshape(steps, bsz)),
        )
        total_loss = float(total_loss)
        log.seconds_train += time.time() - t0
        log.loss_history.append(total_loss)
        log.epochs_run = epoch + 1

        # ---- Alg. 3 reorder + Adam reinit ------------------------------------
        if (
            config.update_reorder
            and epoch + 1 >= config.reorder_warmup
            and (epoch + 1) % config.reorder_every == 0
            and epoch != config.epochs - 1
        ):
            t0 = time.time()
            pi, stats = reorder.update_orders(
                xn, params, pi, spec, cfg, rng, config.reorder_samples,
                predict_fn=predict_jit,
            )
            log.reorder_stats.append(stats)
            opt_state = opt.init(params)  # paper: reinit optimizer after reorder
            log.seconds_reorder += time.time() - t0
            # the loss surface changed: restart the convergence tracker so a
            # transient post-reorder dip is not mistaken for a stall
            stall = 0
            best_fit = -np.inf

        fit = eval_fitness()
        log.fitness_history.append(fit)
        if config.verbose:
            print(f"epoch {epoch}: loss={total_loss:.5g} fitness={fit:.5f}")
        if best_snapshot is None or fit > best_snapshot[0]:
            best_snapshot = (fit, params, [p.copy() for p in pi])
        if fit > best_fit + config.tol:
            best_fit = fit
            stall = 0
        else:
            stall += 1
            if stall >= config.patience:
                break

    # return the best state seen (reorder sweeps can transiently regress)
    _, params, pi = best_snapshot
    return CompressedTensor(params, pi, spec, cfg, mean, std), log
