"""Seeded replica of the NYC taxi tensor (origin x destination x time x
day, Table II of arXiv 2309.10310), drawn on the device.

Counts are Poisson around a hub near zone 0.4 of both the origin and the
destination axis, with a periodic profile over the time axis; the same
recipe as the program's own replica (``repro.data.synthetic_tensors``),
restated here so the benchmark's inputs do not depend on it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("shape",))
def generate(shape: tuple[int, ...], key: jax.Array) -> jax.Array:
    g = [jnp.linspace(0.0, 1.0, n) for n in shape]
    hub = jnp.exp(-((g[0][:, None] - 0.4) ** 2 + (g[1][None, :] - 0.4) ** 2) * 8)
    daily = jnp.exp(jnp.sin(2 * jnp.pi * g[2]) * 1.5)
    lam = hub[:, :, None] * daily[None, None, :] * 0.35
    lam = jnp.broadcast_to(lam[..., None], shape)
    return jax.random.poisson(key, lam, shape).astype(jnp.float32)
