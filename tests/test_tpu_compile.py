"""Compile the main path for a described TPU v5e with no chip attached.

Interpret mode runs a Pallas kernel's body as plain XLA, so it cannot
see what Mosaic refuses (unsupported reshapes, output layouts that do
not match XLA's).  These cases hand the real TPU compiler the kernels
at the paper's widths and the NTTD train epoch at ``MEDIUM``, and check
that each kernel lowers to a ``tpu_custom_call``.  Nothing runs.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library at a time, and every
test worker imports this file.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # can never be read back without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree
    )


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


# granite's widest leaf (``tok/embed``): d' 17 at rank 8, hidden 16
GRANITE_EMBED = ((100352, 4096), 17)


def _fused_operands(rank, hidden, shape=(963, 144, 440), d_prime=None):
    from repro.core import nttd
    from repro.core.folding import make_folding_spec

    spec = make_folding_spec(shape, d_prime)
    cfg = nttd.NTTDConfig(rank=rank, hidden=hidden)
    params = jax.eval_shape(lambda k: nttd.init_params(k, spec, cfg), jax.random.PRNGKey(0))
    ops = jax.eval_shape(lambda p: nttd.fused_decode_inputs(p, spec, cfg), params)
    return spec, ops


def _has_decode_tile(compiled) -> bool:
    """The kernel is there under the name both roofline readers key on: a
    ``tpu_custom_call`` instruction named ``decode_tile``."""
    return re.search(r"%decode_tile(\.\d+)? = .*custom_call_target=\"tpu_custom_call\"",
                     compiled.as_text()) is not None


@pytest.mark.parametrize("batch", [1, 100, 256, 300, 4096])
@pytest.mark.parametrize("rank,hidden", [(6, 12), (10, 18), (8, 16)])
def test_decode_tile_compiles(one_chip, rank, hidden, batch):
    """The fused decode tile at the paper's SMALL and MEDIUM widths, over
    the pems_sf folding, and at granite's over its d' 17 leaf, through
    the wrapper's batch padding."""
    from repro.kernels import ops

    fold = GRANITE_EMBED if (rank, hidden) == (8, 16) else ((963, 144, 440), None)
    spec, operands = _fused_operands(rank, hidden, *fold)
    idx = jax.ShapeDtypeStruct((batch, spec.d_prime), jnp.int32, sharding=one_chip)
    compiled = _compile(
        lambda i, *w: ops.nttd_decode_tile(i, *w, impl="pallas"),
        idx, *_shapes(operands, one_chip),
    )
    assert _has_decode_tile(compiled)


def test_restore_slab_compiles(one_chip, monkeypatch):
    """One slab program of the device restore at ``SLAB_ENTRIES``, over
    granite's d' 17 leaf: the tile inside it keeps its name.  The slab
    asks ``jax.default_backend()`` for its decode path, which sees this
    host's CPU, so the test answers for the described chip."""
    from repro.core import nttd

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    shape, d_prime = GRANITE_EMBED
    spec, operands = _fused_operands(8, 16, shape, d_prime)
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)  # noqa: E731
    compiled = nttd.restore_slab.lower(
        sds((spec.n_entries,), jnp.float32), sds((), jnp.int32),
        _shapes(operands, one_chip), tuple(sds((m,), jnp.int32) for m in shape),
        sds((), jnp.float32), sds((), jnp.float32),
        shape=shape, factors=tuple(tuple(int(f) for f in row) for row in spec.factors),
        slab=nttd.SLAB_ENTRIES,
    ).compile()
    assert _has_decode_tile(compiled)


@pytest.mark.parametrize("batch", [1, 300, 4096])
def test_tt_contract_compiles(one_chip, batch):
    from repro.kernels import ops

    r, k = 10, 8
    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)  # noqa: E731
    compiled = _compile(
        lambda f, m, last: ops.tt_contract(f, m, last, impl="pallas"),
        sds(batch, r), sds(batch, k, r, r), sds(batch, r),
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("batch", [1, 300, 4096])
def test_lstm_scan_compiles(one_chip, batch):
    from repro.kernels import ops

    t, h = 10, 18
    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)  # noqa: E731
    compiled = _compile(
        lambda x, wi, wh, b: ops.lstm_scan(x, wi, wh, b, impl="pallas"),
        sds(batch, t, h), sds(h, 4 * h), sds(h, 4 * h), sds(4 * h),
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_train_epoch_compiles(one_chip):
    """The fit program at MEDIUM over the full pems_sf shape: plain XLA
    (training keeps the ref impl, the tile has no VJP), and it must fit
    the chip's 16 GB."""
    from repro.configs.tensorcodec_paper import MEDIUM
    from repro.core import codec, nttd
    from repro.core.folding import make_folding_spec
    from repro.optim import optimizers

    shape, steps = (963, 144, 440), 64
    spec = make_folding_spec(shape)
    cfg = nttd.NTTDConfig(rank=MEDIUM.rank, hidden=MEDIUM.hidden, kernel_impl="ref")
    opt = optimizers.adam(MEDIUM.lr)
    params = jax.eval_shape(lambda k: nttd.init_params(k, spec, cfg), jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(opt.init, params)
    epoch = codec._make_train_epoch(spec, cfg, opt)
    bsz = MEDIUM.batch_size
    compiled = epoch.lower(
        _shapes(params, one_chip),
        _shapes(opt_state, one_chip),
        jax.ShapeDtypeStruct((steps, bsz, len(shape)), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((steps, bsz), jnp.float32, sharding=one_chip),
    ).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes
    assert 0 < used < 16 * 2**30
