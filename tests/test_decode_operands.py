"""The fused decode's operands, stacked once per parameter set and kept
beside the payload: answers bit-identical to the tile over freshly
stacked operands, one ``nttd.operands`` span with ``built=1`` a
parameter set and ``built=0`` on every reuse, a rebuild when ``params``
is assigned a new tree, nothing built for a folding the tile cannot
serve, and a payload whose fields, bytes and repr do not see the cache.
And every route to a payload decodes alike."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.codecs.adapters import NTTDEncoded
from repro.core import codec, nttd, serialization
from repro.core.codec import CompressedTensor
from repro.core.folding import make_folding_spec
from repro.kernels import ops
from repro.serve.codec_service import CodecService
from repro.stream import write_chunked

SHAPE = (24, 16, 12)
FIELDS = ["params", "pi", "spec", "cfg", "norm_mean", "norm_std"]


def payload_bytes(shape=SHAPE, d_prime=None):
    spec = make_folding_spec(shape, d_prime)
    cfg = nttd.NTTDConfig(rank=3, hidden=6)
    params = jax.tree.map(np.asarray, nttd.init_params(jax.random.PRNGKey(7), spec, cfg))
    rng = np.random.default_rng(3)
    pi = [rng.permutation(n) for n in shape]
    return serialization.save_bytes(CompressedTensor(params, pi, spec, cfg, 0.5, 2.0))


@pytest.fixture()
def ct():
    """A loaded payload (its tile runs as the jitted oracle on the CPU)."""
    return serialization.load_bytes(payload_bytes())


@pytest.fixture()
def recorder():
    rec = obs.enable_tracing()
    rec.clear()
    yield rec
    obs.disable_tracing()
    rec.clear()


def request(seed=0, n=500, shape=SHAPE):
    rng = np.random.default_rng(seed)
    return np.stack([rng.integers(0, s, n) for s in shape], axis=1)


def folded(ct, idx):
    return ct.spec.fold_indices(jnp.asarray(ct._orig_to_pos(idx), jnp.int32))


def restacked(ct, idx, params=None):
    """The tile over operands stacked afresh from ``params``."""
    params = ct.params if params is None else params
    vals = ops.nttd_decode_tile(folded(ct, idx),
                                *nttd.fused_decode_inputs(params, ct.spec, ct.cfg))
    return np.asarray(vals) * ct.norm_std + ct.norm_mean


def built_attrs(recorder):
    return [s.attrs["built"] for s in recorder.snapshot() if s.name == "nttd.operands"]


@pytest.mark.parametrize("sizes", [(1, 37, 500), (2048, 5, 2048, 300)])
def test_answers_are_bitwise_those_of_restacking(ct, sizes):
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


def test_a_ref_payload_builds_nothing(recorder):
    """A payload folded to one step, which the tile refuses, decodes on the
    jnp chain (``nttd.apply``) and stacks no operands."""
    shape = (4, 5, 3)
    ct = serialization.load_bytes(payload_bytes(shape, d_prime=1))
    assert ct.spec.d_prime == 1
    idx = request(2, shape=shape)
    chain = nttd.apply(ct.params, folded(ct, idx), ct.spec, ct.cfg)
    np.testing.assert_array_equal(ct.decode(idx),
                                  np.asarray(chain) * ct.norm_std + ct.norm_mean)
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


@pytest.fixture(scope="module")
def compressed():
    """A payload straight out of ``compress()``."""
    x = np.random.default_rng(4).random(SHAPE).astype(np.float32)
    ct, _ = codec.compress(x, codec.CodecConfig(rank=3, hidden=6, epochs=1,
                                                batch_size=512, eval_batch=1024))
    return ct


def by_load_bytes(ct, tmp_path, idx):
    return serialization.load_bytes(serialization.save_bytes(ct)).decode(idx)


def by_load_stream(ct, tmp_path, idx):
    path = str(tmp_path / "payload.tcdc")
    write_chunked(path, NTTDEncoded(ct))
    svc = CodecService()
    svc.load_stream("p", path)
    return svc.decode_at("p", idx)


ROUTES = {"compress": lambda ct, tmp_path, idx: ct.decode(idx),
          "load_bytes": by_load_bytes, "load_stream": by_load_stream}


@pytest.mark.parametrize("route", list(ROUTES))
def test_every_route_to_a_payload_decodes_alike(compressed, route, tmp_path, recorder):
    """The same payload from ``compress()``, from ``load_bytes`` and from an
    untiled ``load_stream``: the tile answers on every route, bit for bit."""
    idx = request(5, 700)
    got = ROUTES[route](compressed, tmp_path, idx)
    spans = [s for s in recorder.snapshot() if s.name == "kernel_decode"]
    assert [(s.attrs["impl"], s.attrs["b"]) for s in spans] == [("oracle", len(idx))]
    np.testing.assert_array_equal(got, restacked(compressed, idx))
