"""Plain reference of the NTTD decode and of the streaming fit's first updates.

Written from the paper (arXiv 2309.10310, Alg. 2) and the container's
documented semantics, in straightforward ``jax.numpy`` and float32.  It
imports nothing of the program under test: the folding recipe, the
forward pass, the loss, Adam and the stream fitter's minibatch recipe
are all restated here.  The program's parameter layout (the pytree keys
``embed_<m>``, ``lstm``, ``head_first``/``head_mid``/``head_last``) is
the one interface the two share, because the benchmark makes the weights
and hands the same ones to both.

Every dense contraction goes through :func:`dot`, whose ``mode`` is the
precision of the whole computation:

- ``"highest"``: float32 operands at ``Precision.HIGHEST``; the reference.
- ``"bf16x3"``: the three-pass bfloat16 product that ``Precision.HIGH``
  means on a TPU, written out explicitly so that it means the same on any
  backend.  The control of a program that states float32 at ``highest``.
- ``"high"``: ``Precision.HIGH`` as the backend implements it (three
  bfloat16 passes on a TPU, float32 on a CPU); a calibration reading.
- ``"bf16"``: parameters, inputs and arithmetic in bfloat16; the control
  of a program that states float32 at the default precision.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

MAX_FACTOR = 5  # paper: folding factors are integers of at most 5
#: the control of a configuration's precision: the nearest one below it
CONTROL = {"highest": "bf16x3", "default": "bf16"}
BLOCK_ROWS = 1 << 16


# ---------------------------------------------------------------- folding
def fold_factors(dim: int, d_prime: int) -> list[int]:
    """The paper's folding recipe (Eq. 4): d' factors in [1, 5] whose
    product covers ``dim``.  Start from all 2s, raise the smallest
    (leftmost of ties) until the product covers ``dim``, then lower 2s to
    1 from the right while it still does."""
    factors = [2] * d_prime
    prod = 2**d_prime
    while prod < dim:
        j = min(range(d_prime), key=lambda t: (factors[t], t))
        if factors[j] >= MAX_FACTOR:
            raise ValueError(f"d'={d_prime} cannot fold a mode of length {dim}")
        prod = prod // factors[j] * (factors[j] + 1)
        factors[j] += 1
    for j in reversed(range(d_prime)):
        if factors[j] == 2 and prod // 2 >= dim:
            factors[j] = 1
            prod //= 2
    return factors


class Folding:
    """Maps original indices [B, d] to folded indices [B, d'] (big-endian
    mixed-radix digits of each mode, recomposed per folded mode)."""

    def __init__(self, shape: tuple[int, ...], d_prime: int):
        self.shape = tuple(int(s) for s in shape)
        self.d_prime = int(d_prime)
        self.factors = np.array(
            [fold_factors(n, self.d_prime) for n in self.shape], np.int64
        )
        d = len(self.shape)
        self.strides = np.ones((d, self.d_prime), np.int64)
        for j in range(self.d_prime - 2, -1, -1):
            self.strides[:, j] = self.strides[:, j + 1] * self.factors[:, j + 1]
        self.fstrides = np.ones((d, self.d_prime), np.int64)
        for k in range(d - 2, -1, -1):
            self.fstrides[k] = self.fstrides[k + 1] * self.factors[k + 1]
        self.folded_shape = tuple(int(m) for m in self.factors.prod(axis=0))

    def fold(self, idx: np.ndarray) -> np.ndarray:
        idx = np.asarray(idx, np.int64)
        digits = (idx[:, :, None] // self.strides) % self.factors
        return (digits * self.fstrides).sum(axis=1).astype(np.int32)


# ---------------------------------------------------------------- forward
def dot(a: jax.Array, b: jax.Array, mode: str) -> jax.Array:
    if mode == "highest":
        return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)
    if mode == "high":
        return jnp.dot(a, b, precision=jax.lax.Precision.HIGH)
    if mode == "bf16x3":
        def split(x):
            # reduce_precision, not a bfloat16 round trip: a TPU compiler
            # may drop an f32 -> bf16 -> f32 pair, and lo with it
            hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
            lo = jax.lax.reduce_precision(x - hi, exponent_bits=8, mantissa_bits=7)
            return hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)

        (a_hi, a_lo), (b_hi, b_lo) = split(a), split(b)

        def mm(x, y):
            return jnp.dot(x, y, preferred_element_type=jnp.float32)

        return mm(a_hi, b_hi) + (mm(a_hi, b_lo) + mm(a_lo, b_hi))
    if mode == "bf16":
        return jnp.dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16))
    raise ValueError(f"unknown precision mode {mode!r}")


def _cast(params, mode: str):
    dtype = jnp.bfloat16 if mode == "bf16" else jnp.float32
    return jax.tree.map(lambda p: jnp.asarray(p).astype(dtype), params)


def forward(params, folded: jax.Array, folded_shape: tuple[int, ...], mode: str):
    """NTTD at folded indices [B, d'] -> [B]: per-step embedding lookup
    (one table per folded mode length), a one-layer LSTM with gates
    (i, f, g, o), TT-core heads, and the chain T_1 T_2 ... T_d'."""
    p = _cast(params, mode)
    dt = jnp.bfloat16 if mode == "bf16" else jnp.float32
    hid = p["lstm"]["wi"].shape[0]
    rank = p["head_first"]["b"].shape[0]
    bsz = folded.shape[0]
    h = jnp.zeros((bsz, hid), dt)
    c = jnp.zeros((bsz, hid), dt)
    v = None
    steps = len(folded_shape)
    for t, m in enumerate(folded_shape):
        x = p[f"embed_{m}"][folded[:, t]]
        gates = dot(x, p["lstm"]["wi"], mode) + dot(h, p["lstm"]["wh"], mode)
        gates = (gates + p["lstm"]["b"]).astype(dt)
        i = jax.nn.sigmoid(gates[:, :hid])
        f = jax.nn.sigmoid(gates[:, hid : 2 * hid])
        g = jnp.tanh(gates[:, 2 * hid : 3 * hid])
        o = jax.nn.sigmoid(gates[:, 3 * hid :])
        c = f * c + i * g
        h = o * jnp.tanh(c)
        if t == 0:
            v = (dot(h, p["head_first"]["w"], mode) + p["head_first"]["b"]).astype(dt)
        elif t == steps - 1:
            last = (dot(h, p["head_last"]["w"], mode) + p["head_last"]["b"]).astype(dt)
            return jnp.sum(v * last, axis=-1)
        else:
            core = (dot(h, p["head_mid"]["w"], mode) + p["head_mid"]["b"]).astype(dt)
            v = jnp.sum(v[:, :, None] * core.reshape(bsz, rank, rank), axis=1)
    raise ValueError("NTTD needs d' >= 2")


@functools.partial(jax.jit, static_argnames=("folded_shape", "mode"))
def _forward_block(params, folded, folded_shape, mode):
    return forward(params, folded, folded_shape, mode).astype(jnp.float32)


def decode(payload: dict, indices: np.ndarray, mode: str = "highest") -> np.ndarray:
    """Entries at ORIGINAL indices [B, d] -> [B] float64, in blocks of
    rows so that any B fits.  ``payload`` holds ``params``, ``pi`` (per
    mode, pi[k][position] = original index), ``shape``, ``d_prime``,
    ``mean`` and ``std``."""
    shape = tuple(payload["shape"])
    folding = Folding(shape, payload["d_prime"])
    inv = [np.argsort(np.asarray(p)) for p in payload["pi"]]
    idx = np.asarray(indices, np.int64)
    pos = np.stack([inv[k][idx[:, k]] for k in range(len(shape))], axis=1)
    folded = folding.fold(pos)
    out = np.empty(len(idx), np.float64)
    block = min(BLOCK_ROWS, max(len(idx), 1))
    for s in range(0, len(idx), block):
        part = folded[s : s + block]
        n = len(part)
        if n < block:  # pad the tail: one compiled program for every block
            part = np.concatenate([part, np.zeros((block - n, part.shape[1]), np.int32)])
        vals = _forward_block(payload["params"], jnp.asarray(part), folding.folded_shape, mode)
        out[s : s + n] = np.asarray(vals, np.float64)[:n]
    return out * payload["std"] + payload["mean"]


# ------------------------------------------------------------------- fit
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


@functools.partial(jax.jit, static_argnames=("folded_shape", "mode", "lr"))
def _adam_step(params, mu, nu, step, folded, values, folded_shape, mode, lr):
    def loss_fn(p):
        preds = forward(p, folded, folded_shape, mode).astype(jnp.float32)
        return jnp.sum(jnp.square(preds - values))

    loss, grads = jax.value_and_grad(loss_fn)(params)
    step = step + 1
    t = step.astype(jnp.float32)
    mu = jax.tree.map(lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g.astype(jnp.float32), mu, grads)
    nu = jax.tree.map(
        lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * jnp.square(g.astype(jnp.float32)), nu, grads
    )
    mhat, vhat = 1.0 / (1.0 - ADAM_B1**t), 1.0 / (1.0 - ADAM_B2**t)
    params = jax.tree.map(
        lambda p, m, v: (p - lr * (m * mhat) / (jnp.sqrt(v * vhat) + ADAM_EPS)).astype(p.dtype),
        params, mu, nu,
    )
    return params, mu, nu, step, loss


class FitReference:
    """The stream fitter's semantics, restated: per slab, normalize by the
    first slab's mean and std, draw ``steps_per_slab`` minibatches of
    ``batch_size`` that mix fresh slab entries with a reservoir replay
    sample, take one Adam step on the summed squared error of each, then
    insert the slab into the reservoir (Algorithm R).  Mode orders stay
    the identity, so positions are the original indices.

    ``fault="half_batch"`` plants a fault for calibration: each step sees
    only the first half of its minibatch, counted twice (the mean over the
    rest, at the full batch's scale)."""

    def __init__(self, *, shape, d_prime, lr, batch_size, steps_per_slab,
                 replay_capacity, replay_fraction, seed, params, mode="highest",
                 fault: str | None = None):
        self.shape = tuple(int(s) for s in shape)
        self.folding = Folding(self.shape, d_prime)
        self.lr = float(lr)
        self.batch_size = int(batch_size)
        self.steps = int(steps_per_slab)
        self.replay_fraction = float(replay_fraction)
        self.seed = int(seed)
        self.mode = mode
        self.fault = fault
        self.params = _cast(params, mode)
        zeros = lambda p: jnp.zeros(p.shape, jnp.float32)  # noqa: E731
        self.mu = jax.tree.map(zeros, self.params)
        self.nu = jax.tree.map(zeros, self.params)
        self.step = jnp.zeros((), jnp.int32)
        cap = int(replay_capacity)
        self._rpos = np.zeros((cap, len(self.shape)), np.int64)
        self._rval = np.zeros((cap,), np.float32)
        self._rfill = 0
        self.entries_seen = 0
        self.slabs_seen = 0
        self._mean: float | None = None
        self._std = 1.0

    def update(self, indices: np.ndarray, values: np.ndarray) -> float:
        """One slab; returns the summed loss of its steps."""
        idx = np.asarray(indices, np.int64)
        vals = np.asarray(values, np.float32).ravel()
        if self._mean is None:
            self._mean = float(vals.mean())
            self._std = float(vals.std()) or 1.0
        vn = (vals - self._mean) / self._std
        rng = np.random.default_rng((self.seed * 1_000_003 + self.slabs_seen) * 131 + 29)
        n_replay = int(self.batch_size * self.replay_fraction) if self._rfill else 0
        n_fresh = self.batch_size - n_replay
        fresh = rng.integers(0, len(vn), size=(self.steps, n_fresh))
        pos, val = idx[fresh], vn[fresh]
        if n_replay:
            rep = rng.integers(0, self._rfill, size=(self.steps, n_replay))
            pos = np.concatenate([pos, self._rpos[rep]], axis=1)
            val = np.concatenate([val, self._rval[rep]], axis=1)
        if self.fault == "half_batch":
            half = self.batch_size // 2
            pos = np.concatenate([pos[:, :half], pos[:, :half]], axis=1)
            val = np.concatenate([val[:, :half], val[:, :half]], axis=1)
        total = 0.0
        for s in range(self.steps):
            folded = jnp.asarray(self.folding.fold(pos[s]))
            self.params, self.mu, self.nu, self.step, loss = _adam_step(
                self.params, self.mu, self.nu, self.step, folded,
                jnp.asarray(val[s], jnp.float32), self.folding.folded_shape,
                self.mode, self.lr,
            )
            total += float(loss)
        self._reservoir_insert(idx, vn, rng)
        self.entries_seen += len(vn)
        self.slabs_seen += 1
        return total

    def _reservoir_insert(self, idx, vn, rng) -> None:
        cap = self._rval.shape[0]
        take = min(cap - self._rfill, len(vn))
        if take:
            self._rpos[self._rfill : self._rfill + take] = idx[:take]
            self._rval[self._rfill : self._rfill + take] = vn[:take]
            self._rfill += take
        if take < len(vn):
            t = self.entries_seen + 1 + np.arange(take, len(vn), dtype=np.int64)
            slots = (rng.random(len(t)) * t).astype(np.int64)
            keep = slots < cap
            self._rpos[slots[keep]] = idx[take:][keep]
            self._rval[slots[keep]] = vn[take:][keep]


# ------------------------------------------------------------ comparisons
def leaf_norms(tree) -> dict[str, float]:
    """L2 norm of every leaf, keyed by its path (``lstm/wi``)."""
    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        key = "/".join(str(getattr(k, "key", k)) for k in path)
        out[key] = float(np.linalg.norm(np.asarray(leaf, np.float64)))
    return out


def worst_leaf_gap(program: dict[str, float], reference: dict[str, float],
                   ref_grads: dict[str, float]) -> tuple[float, str]:
    """Largest |program norm - reference norm| over a leaf, measured
    against the larger of that leaf's reference norm and the median
    leaf's.  Leaves whose reference gradient is under a thousandth of the
    median leaf's move under Adam by round-off alone and are left out."""
    gmed = float(np.median(list(ref_grads.values())))
    keep = [k for k in reference if ref_grads[k] >= 1e-3 * gmed]
    med = float(np.median([reference[k] for k in keep]))
    gaps = {k: abs(program[k] - reference[k]) / max(reference[k], med, 1e-30) for k in keep}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def rms_gap(served: np.ndarray, reference: np.ndarray) -> float:
    """Root mean square of served - reference, over the reference's
    spread (std)."""
    served = np.asarray(served, np.float64)
    reference = np.asarray(reference, np.float64)
    scale = float(np.std(reference)) or 1.0
    return float(np.sqrt(np.mean(np.square(served - reference)))) / scale


def relative_gap(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)
