"""Inputs made from ``--seed``: weights on the device, mode orders and
request draws on the host.

Weights are drawn in one jitted call, in the dtype they are served in,
with the program's parameter layout and the configuration's widths.
"""
from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

DATASETS_DIR = Path(__file__).resolve().parent / "datasets"


def host_rng(seed: int, stream: str) -> np.random.Generator:
    """A host generator per named stream, so that adding a draw to one
    stream never shifts another.  Any whole number is a valid seed."""
    return np.random.default_rng([int(seed) % 2**64, *stream.encode()])


def device_key(seed: int, stream: str) -> jax.Array:
    """A JAX key from any whole seed.  ``PRNGKey`` keeps only the low 32
    bits of a large seed, so the seed is hashed down first."""
    word = np.random.SeedSequence([int(seed) % 2**64, *stream.encode()]).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


@functools.partial(jax.jit, static_argnames=("folded_shape", "hidden", "rank", "scales"))
def make_params(key, folded_shape: tuple[int, ...], hidden: int, rank: int,
                scales: tuple[tuple[str, float], ...]):
    """NTTD parameters at the given widths.

    ``scales`` (a tuple of (name, value) pairs, from the configuration's
    file) sets the spread of each group: ``embed`` is the std of the
    embedding rows; ``lstm`` and ``head`` multiply a 1/sqrt(H) std for the
    LSTM and head weights; ``lstm_bias`` is the std of the gate bias.
    Head biases start where the program's do (first/last 1/sqrt(R), middle
    the identity), so the chain product stays O(1) for any d'.
    """
    s = dict(scales)
    h, r = hidden, rank
    k = jax.random.split(key, 6)
    normal = jax.random.normal
    params = {
        f"embed_{m}": normal(jax.random.fold_in(k[0], m), (m, h)) * s["embed"]
        for m in sorted(set(folded_shape))
    }
    w = 1.0 / np.sqrt(h)
    params["lstm"] = {
        "wi": normal(k[1], (h, 4 * h)) * (s["lstm"] * w),
        "wh": normal(k[2], (h, 4 * h)) * (s["lstm"] * w),
        "b": normal(k[3], (4 * h,)) * s["lstm_bias"],
    }
    kf, km, kl = jax.random.split(k[4], 3)
    ones = jnp.ones((r,), jnp.float32) / np.sqrt(r)
    params["head_first"] = {"w": normal(kf, (h, r)) * (s["head"] * w), "b": ones}
    params["head_mid"] = {
        "w": normal(km, (h, r * r)) * (s["head"] * w),
        "b": jnp.eye(r, dtype=jnp.float32).reshape(r * r),
    }
    params["head_last"] = {"w": normal(kl, (h, r)) * (s["head"] * w), "b": ones}
    return params


def params_for(cfg: dict, seed: int, scales_key: str, stream: str):
    """Parameters of a configuration, on the device."""
    return make_params(
        device_key(seed, stream), tuple(cfg["folded_shape"]), cfg["hidden"],
        cfg["rank"], tuple(sorted(cfg[scales_key].items())),
    )


def mode_orders(shape: tuple[int, ...], rng: np.random.Generator) -> list[np.ndarray]:
    """One random order per mode: pi[k][position] = original index."""
    return [rng.permutation(int(n)).astype(np.int64) for n in shape]


def dataset(cfg: dict, seed: int) -> np.ndarray:
    """The configuration's tensor, from ``datasets/<name>.py``."""
    name = cfg["dataset"]["name"]
    path = DATASETS_DIR / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_dataset_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    x = mod.generate(tuple(cfg["dataset"]["shape"]), device_key(seed, "dataset"))
    return np.asarray(x, np.float32)
