"""Restoring a compressed checkpoint onto the device: granite-4.0-h-small's
SMOKE chip share through ``decompress_tree`` and ``RestorePlan``, checked
against the plain reference decode (``bench/reference.py``) at every
original index; and the dense decode that ``to_dense`` and ``fitness``
now share."""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import checkpoints, reference  # noqa: E402
from repro import obs  # noqa: E402
from repro.compress import checkpoint_codec as cc  # noqa: E402
from repro.configs import granite_4_0_h_small as granite  # noqa: E402
from repro.core import codec, nttd  # noqa: E402
from repro.core.folding import make_folding_spec  # noqa: E402
from repro.dist import sharding  # noqa: E402
from repro.dist.sharding import ParamSpec  # noqa: E402
from repro.models import model  # noqa: E402
from repro.train.checkpoint import _flatten  # noqa: E402

SEED = 3_000_000_019
MIN_ENTRIES = 1024  # small enough that the SMOKE share has NTTD leaves of every kind
#: root mean square gap over the reference's spread: float32 decodes of
#: the same weights in another summation order differ by a few ulps
RMS_LIMIT = 3e-6


def _share_specs():
    specs = granite.chip_share(granite.SMOKE, chips=4)
    return specs, jax.tree_util.tree_leaves_with_path(
        specs, is_leaf=lambda s: isinstance(s, ParamSpec))


@pytest.fixture(scope="module")
def checkpoint():
    """The SMOKE share's tree, and its checkpoint: per-layer matrices of at
    least MIN_ENTRIES as NTTD payloads drawn from the seed (as the restore
    cell draws them), every other leaf raw."""
    import io

    share, leaves = _share_specs()
    coded = []
    for path, s in leaves:
        if int(np.prod(s.shape)) >= MIN_ENTRIES and sum(a != "layers" for a in s.axes) >= 2:
            spec = make_folding_spec(s.shape)
            key = "/".join(str(getattr(p, "key", p)) for p in path)
            coded.append({"key": key, "shape": list(s.shape), "d_prime": spec.d_prime,
                          "folded_shape": list(spec.folded_shape)})
    cfg = {"rank": 8, "hidden": 16, "leaves": coded,
           "payload_scales": {"embed": 1.0, "lstm": 1.0, "lstm_bias": 0.5, "head": 0.5},
           "payload_norm": {"mean": 0.0, "std": 0.02}}
    ckpt = checkpoints.Checkpoint(cfg, SEED)
    tree = sharding.materialize(jax.random.PRNGKey(0), share, jnp.float32)
    payload = dict(ckpt.payload)
    for key, leaf in _flatten(tree):
        if key not in payload:
            buf = io.BytesIO()
            np.save(buf, np.asarray(leaf))
            payload[key] = {"kind": "raw", "data": buf.getvalue()}
    return tree, payload, ckpt


def _all_indices(shape):
    return np.stack(np.unravel_index(np.arange(int(np.prod(shape))), shape), axis=1)


def _gap(ckpt, key, values):
    ref = ckpt.refs[key]
    want = reference.decode(ref, _all_indices(ref["shape"]), "highest")
    return reference.rms_gap(np.asarray(values, np.float64).reshape(-1), want)


def test_the_share_holds_leaves_of_every_kind(checkpoint):
    _, payload, ckpt = checkpoint
    keys = set(ckpt.refs)
    assert {"tok/embed", "blocks/attn/wq", "blocks/mamba/in_proj", "blocks/moe/w_up",
            "blocks/moe/shared/w_gate", "blocks/moe/router"} <= keys
    assert any(1 in ckpt.refs[k]["shape"] for k in keys)  # stacked leaves: a length-1 mode
    assert any(item["kind"] == "raw" for item in payload.values())


def test_decompress_tree_restores_every_leaf_on_the_device(checkpoint):
    tree, payload, ckpt = checkpoint
    restored = cc.decompress_tree(payload, tree)
    for key, leaf in _flatten(restored):
        assert isinstance(leaf, jax.Array)
        if key in ckpt.refs:
            assert leaf.shape == tuple(ckpt.refs[key]["shape"]) and leaf.dtype == jnp.float32
            assert _gap(ckpt, key, leaf) < RMS_LIMIT, key
        else:  # raw leaves come back bit-identical
            want = dict(_flatten(tree))[key]
            assert leaf.dtype == want.dtype
            np.testing.assert_array_equal(np.asarray(leaf), np.asarray(want))


@pytest.mark.parametrize("slab", [1000, 4096, nttd.SLAB_ENTRIES])
def test_a_plan_in_slabs_with_tails_matches_the_reference(checkpoint, slab):
    _, payload, ckpt = checkpoint
    plan = cc.RestorePlan(payload, slab=slab)
    if slab == 1000:  # no leaf is a multiple of it: every leaf ends in an overlapped tail
        assert all(s.n % s.slab for s in plan.slabs.values() if s.n > s.slab)
    restored = plan.run()
    assert set(restored) == set(ckpt.refs)
    for key, buf in restored.items():
        assert _gap(ckpt, key, buf) < RMS_LIMIT, key


def test_the_plan_advances_every_leaf_together(checkpoint):
    _, payload, _ = checkpoint
    plan = cc.RestorePlan(payload, slab=1000)
    assert sorted(plan.steps) == sorted(
        (key, k) for key, s in plan.slabs.items() for k in range(s.n_slabs))
    done = dict.fromkeys(plan.slabs, 0)
    for key, k in plan.steps:
        assert k == done[key]  # each leaf in order
        done[key] += 1
        frac = {key: done[key] / s.n_slabs for key, s in plan.slabs.items()}
        lead = max(frac.values())
        for other, s in plan.slabs.items():
            assert lead - frac[other] <= 1.0 / s.n_slabs + 1e-12


def test_the_restore_records_its_spans_and_counts_its_entries(checkpoint):
    _, payload, _ = checkpoint
    plan = cc.RestorePlan(payload, slab=4096)
    obs.enable_tracing()
    try:
        obs.get_recorder().clear()
        plan.run()
        spans = obs.get_recorder().drain()
    finally:
        obs.disable_tracing()
    roots = [s for s in spans if s.name == "ckpt.restore"]
    slabs = [s for s in spans if s.name == "ckpt.restore_slab"]
    assert len(roots) == 1 and roots[0].attrs["entries"] == plan.entries
    assert roots[0].attrs["leaves"] == len(plan.slabs)
    assert len(slabs) == len(plan.steps)
    assert sum(s.attrs["entries"] for s in slabs) == plan.entries
    assert {s.attrs["leaf"] for s in slabs} == set(plan.slabs)
    assert all(s.attrs["d_prime"] >= 2 for s in slabs)
    assert plan.metrics.counter("ckpt.restored_entries").value == plan.entries
    for s in slabs:  # the split of each leaf's folded indices, by the rule
        leaf = plan.slabs[s.attrs["leaf"]]
        runs = [int(np.prod(leaf.shape[k:])) for k in range(len(leaf.shape))]
        tail = max([p for p in runs if p <= 2**16], default=leaf.shape[-1])
        assert s.attrs["tail"] == tail, s.attrs
        # rows of the tail that cover any slab, no more than the leaf has
        assert s.attrs["rows"] == min(-(-(leaf.slab + tail - 1) // tail), leaf.n // tail)


def _old_to_dense(ct):
    """``to_dense`` as it was: positions decoded by the payload's own
    kernel in 65,536-entry batches on the host, then permuted."""
    spec = ct.spec
    flat = np.arange(spec.n_entries)
    pos = np.stack(np.unravel_index(flat, spec.shape), axis=1)
    approx = np.asarray(nttd.apply_at_positions(ct.params, jnp.asarray(pos, jnp.int32),
                                                spec, ct.cfg)).reshape(spec.shape)
    return (approx * ct.norm_std + ct.norm_mean)[np.ix_(*ct.inv_pi)]


@pytest.mark.parametrize("shape", [(6, 5, 4), (1, 33, 7), (40, 1, 1, 9)])
def test_to_dense_keeps_its_values(shape):
    spec = make_folding_spec(shape)
    cfg = nttd.NTTDConfig(rank=4, hidden=8)
    params = nttd.init_params(jax.random.PRNGKey(1), spec, cfg)
    rng = np.random.default_rng(2)
    ct = codec.CompressedTensor(params, [rng.permutation(n) for n in shape], spec, cfg,
                                0.25, 3.0)
    got = ct.to_dense()
    assert isinstance(got, np.ndarray) and got.shape == shape and got.dtype == np.float32
    np.testing.assert_allclose(got, _old_to_dense(ct), rtol=1e-5, atol=1e-5)
    # slabs of another size (an overlapped tail): the same decode, up to the
    # ulps that the CPU's batched products change with the batch size
    np.testing.assert_allclose(np.asarray(ct.dense_slabs(slab=7).dense()), got,
                               rtol=1e-6, atol=1e-6)


def test_fitness_sums_the_error_on_the_device():
    shape = (12, 9, 10)
    spec = make_folding_spec(shape)
    cfg = nttd.NTTDConfig(rank=4, hidden=8)
    params = nttd.init_params(jax.random.PRNGKey(3), spec, cfg)
    rng = np.random.default_rng(4)
    ct = codec.CompressedTensor(params, [rng.permutation(n) for n in shape], spec, cfg)
    x = (ct.to_dense() + rng.normal(0, 0.05, shape)).astype(np.float32)
    dense = ct.to_dense().astype(np.float64)
    want = 1.0 - np.linalg.norm(x - dense) / np.linalg.norm(x.astype(np.float64))
    assert ct.fitness(x) == pytest.approx(want, abs=1e-6)
    assert ct.fitness(ct.to_dense()) == pytest.approx(1.0, abs=1e-6)
    slabs = ct.dense_slabs(slab=100)  # a tail that overlaps its neighbour: counted once
    assert slabs.sq_err(x) == pytest.approx(np.sum((x - dense) ** 2), rel=1e-5)


def test_the_chip_share_holds_a_slice_of_the_experts_and_all_else():
    full = model.param_specs(granite.CONFIG)["blocks"]
    stage = model.param_specs(dataclasses.replace(granite.CONFIG, n_layers=10))["blocks"]
    specs = granite.chip_share()["blocks"]
    assert specs["moe"]["w_up"].shape == (1, 10, 8, 4096, 768)
    assert specs["moe"]["w_down"].shape == (1, 10, 8, 768, 4096)
    assert specs["moe"]["router"].shape == (1, 10, 4096, 72)
    assert specs["moe"]["shared"]["w_up"].shape == (1, 10, 4096, 1536)
    assert specs["attn"]["wq"].shape == (1, 1, 4096, 32, 128)
    assert specs["mamba"]["in_proj"].shape == (1, 9, 4096, 16768)
    assert full["mamba"]["in_proj"].shape == (4, 9, 4096, 16768)
    # all but the three expert banks is the stage's own
    is_spec = lambda s: isinstance(s, ParamSpec)  # noqa: E731
    banks = {"w_gate", "w_up", "w_down"}
    for (path, s), t in zip(jax.tree_util.tree_leaves_with_path(specs, is_leaf=is_spec),
                            jax.tree_util.tree_leaves(stage, is_leaf=is_spec)):
        cut = path[0].key == "moe" and path[1].key in banks
        assert s.shape == (t.shape[:2] + (t.shape[2] // 9,) + t.shape[3:] if cut else t.shape)
