"""Spans inside the served decode and the streaming fit: which spans a
served ``decode_at`` and an ``NTTDStreamFitter.update`` record, and under
which parent; answers and fitted parameters bit-identical with tracing on
and off; no ``Span`` allocated with tracing off; and every live span in
the profiler's host plane, nested as in the recorder."""
import glob
import os

import jax
import numpy as np
import pytest

from repro import obs
from repro.codecs.adapters import NTTDEncoded
from repro.core import nttd
from repro.core.codec import CompressedTensor
from repro.core.folding import make_folding_spec
from repro.serve.codec_service import CodecService
from repro.stream import write_chunked
from repro.stream.fit import NTTDStreamFitter

SHAPE = (24, 16, 12)
READ_SPANS = {
    # span: its parent
    "payload.decode": "decode_at",
    "payload.orig_to_pos": "payload.decode",
    "nttd.fold": "payload.decode",
    "nttd.operands": "payload.decode",
    "kernel_decode": "payload.decode",
    "payload.device_wait": "payload.decode",
}
FIT_SPANS = {"fit.sample": "fit.update", "fit.dispatch": "fit.update",
             "fit.reservoir": "fit.update"}


@pytest.fixture()
def service(tmp_path):
    """A CodecService serving a small NTTD payload."""
    spec = make_folding_spec(SHAPE, None)
    cfg = nttd.NTTDConfig(rank=3, hidden=6)
    params = jax.tree.map(np.asarray, nttd.init_params(jax.random.PRNGKey(7), spec, cfg))
    rng = np.random.default_rng(3)
    pi = [rng.permutation(n) for n in SHAPE]
    path = str(tmp_path / "payload.tcdc")
    write_chunked(path, NTTDEncoded(CompressedTensor(params, pi, spec, cfg, 0.5, 2.0)))
    svc = CodecService()
    svc.load_stream("p", path)
    return svc


def request(seed=0, n=500):
    rng = np.random.default_rng(seed)
    return np.stack([rng.integers(0, s, n) for s in SHAPE], axis=1)


def slab(seed, n=300):
    rng = np.random.default_rng(seed)
    return request(seed, n), rng.random(n).astype(np.float32)


def fit(updates=2):
    fitter = NTTDStreamFitter(SHAPE, rank=2, hidden=4, steps_per_slab=2, batch_size=64,
                              replay_capacity=128, seed=5)
    for k in range(updates):
        fitter.update(*slab(10 + k))
    return jax.tree.map(np.asarray, fitter.params)


@pytest.fixture()
def recorder():
    rec = obs.enable_tracing()
    rec.clear()
    yield rec
    obs.disable_tracing()
    rec.clear()


def parent_names(spans):
    by_id = {(s.trace_id, s.span_id): s for s in spans}
    return {s.name: by_id[(s.trace_id, s.parent_id)].name
            for s in spans if s.parent_id}


def test_a_served_read_records_its_layers_under_decode_at(service, recorder):
    idx = request()
    service.decode_at("p", idx)
    spans = recorder.snapshot()
    (root,) = [s for s in spans if s.name == "decode_at"]
    assert root.parent_id == 0 and root.attrs["entries"] == len(idx)
    mine = [s for s in spans if s.trace_id == root.trace_id]
    assert {k: v for k, v in parent_names(mine).items() if k in READ_SPANS} == READ_SPANS
    (decode,) = [s for s in mine if s.name == "payload.decode"]
    assert decode.attrs["entries"] == len(idx)
    (kernel,) = [s for s in mine if s.name == "kernel_decode"]
    assert kernel.attrs["b"] == len(idx) and kernel.attrs["padded"] >= len(idx)
    for s in mine:
        assert root.t_start <= s.t_start <= s.t_end <= root.t_end


def test_an_update_records_its_phases_under_fit_update(recorder):
    fitter = NTTDStreamFitter(SHAPE, rank=2, hidden=4, steps_per_slab=2, batch_size=64,
                              replay_capacity=128)
    fitter.update(*slab(1))
    spans = recorder.snapshot()
    (root,) = [s for s in spans if s.name == "fit.update"]
    assert root.parent_id == 0 and root.attrs["entries"] == 300
    assert parent_names(spans) == FIT_SPANS


def test_answers_and_fitted_parameters_are_bitwise_equal_on_and_off(service):
    rec = obs.get_recorder()
    obs.disable_tracing()
    before = rec.span_allocs
    off_read = service.decode_at("p", request(1))
    off_fit = fit()
    assert rec.span_allocs == before  # the off path made no Span
    obs.enable_tracing()
    try:
        on_read = service.decode_at("p", request(1))
        on_fit = fit()
        assert rec.span_allocs > before
    finally:
        obs.disable_tracing()
        rec.clear()
    np.testing.assert_array_equal(on_read, off_read)
    for a, b in zip(jax.tree.leaves(on_fit), jax.tree.leaves(off_fit)):
        np.testing.assert_array_equal(a, b)


def _host_events(trace_dir):
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    profile = jax.profiler.ProfileData.from_file(path)
    wanted = {"decode_at", "payload.decode", "nttd.fold"}
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                      for ev in line.events if ev.name in wanted]
            if events:
                return events
    return []


def test_spans_sit_nested_in_the_profilers_host_plane(service, recorder, tmp_path):
    service.decode_at("p", request(2))  # compiled before the trace
    with jax.profiler.trace(str(tmp_path / "trace")):
        service.decode_at("p", request(2))
    events = _host_events(str(tmp_path / "trace"))
    by_name = {}
    for name, a, b in events:
        by_name.setdefault(name, []).append((a, b))
    assert {k: len(v) for k, v in by_name.items()} == \
        {"decode_at": 1, "payload.decode": 1, "nttd.fold": 1}
    (outer,), (mid,), (inner,) = (by_name[k] for k in ("decode_at", "payload.decode",
                                                        "nttd.fold"))
    assert outer[0] <= mid[0] <= inner[0] <= inner[1] <= mid[1] <= outer[1]


def test_ingested_spans_open_no_annotation(recorder, monkeypatch):
    from repro.obs import trace

    def refuse(name):
        raise AssertionError(f"annotation opened for {name}")

    monkeypatch.setattr(trace, "_annotation", lambda: refuse)
    recorder.ingest([obs.Span("remote", 9, 1, 0, 0.0, 1.0, {})], clock_offset=2.0,
                    instance="w0")
    (s,) = recorder.snapshot()
    assert (s.name, s.t_start, s.instance) == ("remote", 2.0, "w0")
