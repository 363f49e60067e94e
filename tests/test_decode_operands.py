"""The fused decode's operands, stacked once per parameter set and kept
beside the payload: answers bit-identical to ``nttd.apply`` stacking them
itself, one ``nttd.operands`` span with ``built=1`` a parameter set and
``built=0`` on every reuse, a rebuild when ``params`` is assigned a new
tree, nothing built off the fused path, and a payload whose fields, bytes
and repr do not see the cache."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import nttd, serialization
from repro.core.codec import CompressedTensor
from repro.core.folding import make_folding_spec

SHAPE = (24, 16, 12)
FIELDS = ["params", "pi", "spec", "cfg", "norm_mean", "norm_std"]


def payload_bytes():
    spec = make_folding_spec(SHAPE, None)
    cfg = nttd.NTTDConfig(rank=3, hidden=6)
    params = jax.tree.map(np.asarray, nttd.init_params(jax.random.PRNGKey(7), spec, cfg))
    rng = np.random.default_rng(3)
    pi = [rng.permutation(n) for n in SHAPE]
    return serialization.save_bytes(CompressedTensor(params, pi, spec, cfg, 0.5, 2.0))


def load(monkeypatch, impl):
    monkeypatch.setenv("REPRO_DECODE_IMPL", impl)
    return serialization.load_bytes(payload_bytes())


@pytest.fixture()
def ct(monkeypatch):
    """A loaded payload on the fused path (the jitted oracle on the CPU)."""
    return load(monkeypatch, "fused")


@pytest.fixture()
def recorder():
    rec = obs.enable_tracing()
    rec.clear()
    yield rec
    obs.disable_tracing()
    rec.clear()


def request(seed=0, n=500):
    rng = np.random.default_rng(seed)
    return np.stack([rng.integers(0, s, n) for s in SHAPE], axis=1)


def restacked(ct, idx, params=None):
    """What ``decode`` answered before: ``nttd.apply`` without operands."""
    folded = ct.spec.fold_indices(jnp.asarray(ct._orig_to_pos(idx), jnp.int32))
    vals = nttd.apply(ct.params if params is None else params, folded, ct.spec, ct.cfg)
    return np.asarray(vals) * ct.norm_std + ct.norm_mean


def built_attrs(recorder):
    return [s.attrs["built"] for s in recorder.snapshot() if s.name == "nttd.operands"]


@pytest.mark.parametrize("sizes", [(1, 37, 500), (2048, 5, 2048, 300)])
def test_answers_are_bitwise_those_of_restacking(ct, sizes):
    assert nttd.uses_fused_decode(ct.spec, ct.cfg)
    for k, n in enumerate(sizes):
        idx = request(k, n)
        np.testing.assert_array_equal(ct.decode(idx), restacked(ct, idx))


def test_operands_are_built_once_and_reused(ct, recorder):
    n = 6
    for k in range(n):
        ct.decode(request(k, 100 + k))
    assert built_attrs(recorder) == [1] + [0] * (n - 1)
    spans = recorder.snapshot()
    by_id = {s.span_id: s for s in spans}
    assert {by_id[s.parent_id].name for s in spans if s.name == "nttd.operands"} == \
        {"payload.decode"}


def test_a_new_params_tree_is_rebuilt_and_answered(ct, recorder):
    idx = request(1)
    old = ct.decode(idx)
    new_params = jax.tree.map(lambda a: a * 1.25, ct.params)
    ct.params = new_params
    new = ct.decode(idx)
    ct.decode(idx)
    assert built_attrs(recorder) == [1, 1, 0]
    np.testing.assert_array_equal(new, restacked(ct, idx, new_params))
    assert not np.array_equal(new, old)


def test_the_cache_keys_on_the_params_object_not_its_id(ct):
    """An equal-valued but distinct tree is another parameter set."""
    first = ct._decode_operands()
    assert ct._decode_operands() is first
    ct.params = jax.tree.map(lambda a: a, ct.params)
    assert ct._decode_operands() is not first


def test_a_ref_payload_builds_nothing(monkeypatch, recorder):
    ct = load(monkeypatch, "ref")
    idx = request(2)
    np.testing.assert_array_equal(ct.decode(idx), restacked(ct, idx))
    assert ct._decode_operands() is None
    assert "_operands" not in vars(ct)
    assert built_attrs(recorder) == []


def test_the_payload_does_not_see_the_cache(ct):
    before = serialization.save_bytes(ct)
    text, size = repr(ct), ct.payload_bytes()
    ct.decode(request(3))
    assert "_operands" in vars(ct)
    assert serialization.save_bytes(ct) == before
    assert (repr(ct), ct.payload_bytes()) == (text, size)
    assert [f.name for f in dataclasses.fields(CompressedTensor)] == FIELDS
    copy = dataclasses.replace(ct)
    assert "_operands" not in vars(copy)
    idx = request(4)
    np.testing.assert_array_equal(copy.decode(idx), ct.decode(idx))
