"""Compile the main path for a described TPU v5e with no chip attached.

Interpret mode runs a Pallas kernel's body as plain XLA, so it cannot
see what Mosaic refuses (unsupported reshapes, output layouts that do
not match XLA's).  These cases hand the real TPU compiler the decode
tile and the NTTD train epoch at the configurations' widths, and check
that the tile lowers to a ``tpu_custom_call`` and the train epoch to
none.  Nothing runs.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library at a time, and every
test worker imports this file.
"""
from __future__ import annotations

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
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
def test_decode_tile_compiles(one_chip, monkeypatch, rank, hidden, batch):
    """The fused decode tile at the paper's SMALL and MEDIUM widths, over
    the pems_sf folding, and at granite's over its d' 17 leaf, through
    the wrapper's batch padding.  The wrapper asks
    ``jax.default_backend()`` for its path, which sees this host's CPU, so
    the test answers for the described chip."""
    from repro.kernels import ops

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fold = GRANITE_EMBED if (rank, hidden) == (8, 16) else ((963, 144, 440), None)
    spec, operands = _fused_operands(rank, hidden, *fold)
    idx = jax.ShapeDtypeStruct((batch, spec.d_prime), jnp.int32, sharding=one_chip)
    compiled = _compile(ops.nttd_decode_tile, idx, *_shapes(operands, one_chip))
    assert _has_decode_tile(compiled)


#: each preset over the shape of the benchmark configuration that uses it
PRESET_SHAPES = {"SMALL": (265, 265, 28, 35), "MEDIUM": (963, 144, 440)}


@pytest.mark.parametrize("batch", [1, 300, 4096])
@pytest.mark.parametrize("preset", list(PRESET_SHAPES))
def test_served_decode_compiles_to_the_tile(one_chip, monkeypatch, preset, batch):
    """A payload's served decode (``CompressedTensor.decode``), with the
    backend answering as the chip: the program it calls, with the
    arguments it passes, compiles for the v5e and holds ``%decode_tile``."""
    from repro.configs import tensorcodec_paper
    from repro.core import nttd
    from repro.core.codec import CompressedTensor
    from repro.core.folding import make_folding_spec
    from repro.kernels import decode_tile

    widths, shape = getattr(tensorcodec_paper, preset), PRESET_SHAPES[preset]
    spec = make_folding_spec(shape)
    cfg = nttd.NTTDConfig(rank=widths.rank, hidden=widths.hidden)
    params = nttd.init_params(jax.random.PRNGKey(0), spec, cfg)
    ct = CompressedTensor(params, [np.arange(n) for n in shape], spec, cfg)
    program, calls = decode_tile.decode_tile, []

    def record(idx, *operands, **static):
        calls.append(((idx, *operands), static))
        return jnp.zeros((idx.shape[0],), operands[0].dtype)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(decode_tile, "decode_tile", record)
    rng = np.random.default_rng(batch)
    ct.decode(np.stack([rng.integers(0, n, batch) for n in shape], axis=1))
    [(args, static)] = calls
    assert static["interpret"] is False
    compiled = program.lower(*_shapes(args, one_chip), **static).compile()
    assert _has_decode_tile(compiled)


#: granite leaves of the restore's slab programs: the widest d', the
#: narrowest tail (72 entries) and the longest (d' 15, 16,768 entries)
RESTORE_LEAVES = {"tok/embed": GRANITE_EMBED, "router": ((1, 10, 4096, 72), 12),
                  "in_proj": ((1, 9, 4096, 16768), 15)}


def _gathers_of(compiled, elements: int) -> list[str]:
    """The optimised program's gather instructions whose result holds at
    least ``elements`` values."""
    found = []
    for m in re.finditer(r"%\S+ = \w+\[([\d,]*)\]\S* gather\(", compiled.as_text()):
        if int(np.prod([int(x) for x in m.group(1).split(",") if x])) >= elements:
            found.append(m.group(0))
    return found


@pytest.mark.parametrize("leaf", list(RESTORE_LEAVES))
def test_restore_slab_compiles(one_chip, monkeypatch, leaf):
    """One slab program of the device restore at ``SLAB_ENTRIES`` over a
    granite leaf: the tile inside it keeps its name, and the folded
    indices come from the head rows and the tail table, with no gather
    per entry.  The slab asks ``jax.default_backend()`` for its decode
    path, which sees this host's CPU, so the test answers for the
    described chip."""
    from repro.core import nttd

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    shape, d_prime = RESTORE_LEAVES[leaf]
    spec, operands = _fused_operands(8, 16, shape, d_prime)
    slab = min(nttd.SLAB_ENTRIES, spec.n_entries)
    t, w, _ = nttd.slab_split(shape, slab)
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)  # noqa: E731
    compiled = nttd.restore_slab.lower(
        sds((spec.n_entries,), jnp.float32), sds((), jnp.int32),
        _shapes(operands, one_chip), tuple(sds((m,), jnp.int32) for m in shape[:t]),
        sds((spec.d_prime, w), jnp.int32), sds((), jnp.float32), sds((), jnp.float32),
        shape=shape, factors=tuple(tuple(int(f) for f in row) for row in spec.factors),
        slab=slab,
    ).compile()
    assert _has_decode_tile(compiled)
    assert not _gathers_of(compiled, slab)


#: (shape, d') of the fit program at each width: SMALL over nyc, MEDIUM
#: over pems_sf, granite's over its d' 17 leaf
FIT_CASES = {"SMALL": ((265, 265, 28, 35), None), "MEDIUM": ((963, 144, 440), None),
             "granite": GRANITE_EMBED}


@pytest.mark.parametrize("case", list(FIT_CASES))
def test_train_epoch_compiles(one_chip, case):
    """The fit program over a configuration's shape: plain XLA, no Pallas
    call (training runs the jnp chain, the tile has no VJP), and it must
    fit the chip's 16 GB."""
    from repro.configs.tensorcodec_paper import MEDIUM, SMALL
    from repro.core import codec, nttd
    from repro.core.folding import make_folding_spec
    from repro.optim import optimizers

    widths = {"SMALL": SMALL, "MEDIUM": MEDIUM,
              "granite": dataclasses.replace(MEDIUM, rank=8, hidden=16)}[case]
    (shape, d_prime), steps, bsz = FIT_CASES[case], 64, widths.batch_size
    spec = make_folding_spec(shape, d_prime)
    cfg = nttd.NTTDConfig(rank=widths.rank, hidden=widths.hidden)
    opt = optimizers.adam(widths.lr)
    params = jax.eval_shape(lambda k: nttd.init_params(k, spec, cfg), jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(opt.init, params)
    epoch = codec._make_train_epoch(spec, cfg, opt)
    compiled = epoch.lower(
        _shapes(params, one_chip),
        _shapes(opt_state, one_chip),
        jax.ShapeDtypeStruct((steps, bsz, len(shape)), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((steps, bsz), jnp.float32, sharding=one_chip),
    ).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes
    assert 0 < used < 16 * 2**30
