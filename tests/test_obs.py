"""repro.obs: span recorder semantics (disabled path allocates nothing,
enabled path bounds memory), histogram percentiles (all-time buckets vs
exact window, empty -> None), Chrome trace export + report CLI, fit
telemetry JSONL, and the headline end-to-end property — one traced
``FleetFrontend.decode_at`` over a two-worker socket fleet yields a
single stitched trace holding frontend, transport, worker service-stage,
and kernel spans."""
import io
import json

import numpy as np
import pytest

from repro import obs
from repro.codecs import get_codec
from repro.fleet import FleetFrontend, SocketTransport
from repro.fleet.metrics import collect
from repro.obs import report
from repro.obs.trace import TraceRecorder
from repro.stream import write_chunked


@pytest.fixture()
def recorder():
    """A clean, enabled global recorder; restored to disabled after."""
    rec = obs.enable_tracing()
    rec.clear()
    yield rec
    obs.disable_tracing()
    rec.clear()


# ---------------------------------------------------------------------------
# recorder
# ---------------------------------------------------------------------------
def test_disabled_recorder_allocates_no_spans():
    rec = obs.get_recorder()
    obs.disable_tracing()
    before = rec.span_allocs
    for _ in range(100):
        with obs.span("hot", k=1):
            pass
    assert rec.span_allocs == before  # zero Span objects on the off path
    assert len(rec) == 0 or rec.snapshot()[-1].name != "hot"
    # the disabled context manager is one shared object, not per-call
    assert obs.span("a") is obs.span("b")


def test_enabled_recorder_records_nested_parentage(recorder):
    with obs.span("outer", stage="o") as outer:
        with obs.span("inner") as inner:
            pass
    spans = recorder.snapshot()[-2:]
    by_name = {s.name: s for s in spans}
    assert by_name["inner"].trace_id == by_name["outer"].trace_id
    assert by_name["inner"].parent_id == by_name["outer"].span_id
    assert by_name["outer"].parent_id == 0  # root
    assert by_name["inner"].t_start >= by_name["outer"].t_start
    assert by_name["inner"].t_end <= by_name["outer"].t_end
    assert outer.attrs == {"stage": "o"}
    assert inner.duration >= 0.0


def test_ring_capacity_bounds_memory_and_counts_drops():
    rec = TraceRecorder(capacity=4)
    rec.enabled = True
    for k in range(10):
        with rec.span(f"s{k}"):
            pass
    assert len(rec) == 4
    assert rec.dropped == 6
    assert [s.name for s in rec.snapshot()] == ["s6", "s7", "s8", "s9"]


def test_span_records_exception_and_reraises(recorder):
    with pytest.raises(ValueError):
        with obs.span("boom"):
            raise ValueError("x")
    s = recorder.snapshot()[-1]
    assert s.name == "boom" and s.attrs["error"] == "ValueError"


def test_ingest_rebases_clock_and_labels_instance(recorder):
    remote = TraceRecorder(capacity=8)
    remote.enabled = True
    with remote.span("w"):
        pass
    (w,) = remote.drain()
    recorder.ingest([w], clock_offset=100.0, instance="w3")
    got = recorder.snapshot()[-1]
    assert got.instance == "w3"
    assert got.t_start == pytest.approx(w.t_start + 100.0)
    assert got.duration == pytest.approx(w.duration)


def test_remote_context_adopts_parent(recorder):
    with obs.remote_context((42, 7)):
        with obs.span("adopted"):
            pass
    s = recorder.snapshot()[-1]
    assert (s.trace_id, s.parent_id) == (42, 7)
    # and the ambient context is restored
    assert obs.current_context() is None


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------
def test_histogram_empty_percentiles_are_none_not_crash():
    h = obs.Histogram("lat", ())
    assert h.percentile(50) is None
    assert h.percentile(99) is None
    assert h.window_percentile(50) is None
    assert h.mean is None


def test_histogram_window_percentiles_are_exact():
    h = obs.Histogram("lat", (), window=100)
    vals = [0.001 * k for k in range(1, 101)]
    for v in vals:
        h.observe(v)
    assert h.window_percentile(50) == pytest.approx(np.percentile(vals, 50))
    assert h.window_percentile(99) == pytest.approx(np.percentile(vals, 99))
    assert h.count == 100 and h.min == vals[0] and h.max == vals[-1]


def test_histogram_alltime_survives_window_wrap():
    h = obs.Histogram("lat", (), window=4)
    for _ in range(100):
        h.observe(0.001)  # old regime
    for _ in range(10):
        h.observe(1.0)  # recent regime fills the whole window
    assert h.window_percentile(50) == pytest.approx(1.0)
    # all-time view still remembers the 100 fast samples
    assert h.percentile(50) == pytest.approx(0.001, rel=1.0)
    assert h.count == 110


def test_registry_get_or_create_remove_and_as_dict():
    reg = obs.MetricsRegistry()
    c = reg.counter("requests", instance="i0")
    assert reg.counter("requests", instance="i0") is c
    c.inc(3)
    g = reg.gauge("peak", instance="i0")
    g.set_max(10)
    g.set_max(5)  # peak keeps the high-water mark
    reg.histogram("lat", instance="i0").observe(0.5)
    d = reg.as_dict()
    assert d["counters"] == [
        {"name": "requests", "labels": {"instance": "i0"}, "value": 3}
    ]
    assert d["gauges"][0]["value"] == 10
    assert d["histograms"][0]["count"] == 1
    assert d["histograms"][0]["window_p99"] == pytest.approx(0.5)
    reg.remove("lat", instance="i0")
    assert reg.as_dict()["histograms"] == []


# ---------------------------------------------------------------------------
# export + report
# ---------------------------------------------------------------------------
def test_chrome_trace_export_is_valid_and_loadable(tmp_path, recorder):
    with obs.span("stage_a", payload="p"):
        with obs.span("stage_b"):
            pass
    path = str(tmp_path / "trace.json")
    n = obs.export_chrome_trace(path, metrics={"fleet": None, "instances": {}})
    assert n == 2
    doc = json.load(open(path))
    assert isinstance(doc["traceEvents"], list)
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    ms = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert {e["name"] for e in xs} == {"stage_a", "stage_b"}
    assert ms[0]["name"] == "process_name"
    for e in xs:  # required Chrome trace-event fields
        assert {"name", "ph", "ts", "dur", "pid", "tid"} <= set(e)
        assert e["ts"] >= 0 and e["dur"] >= 0
    assert doc["repro_metrics"]["instances"] == {}


def test_report_cli_renders_breakdown(tmp_path, recorder, capsys):
    with obs.span("decode_at", payload="p"):
        with obs.span("tile_decode", tiles=3):
            pass
    path = str(tmp_path / "trace.json")
    obs.export_chrome_trace(path)
    assert report.main([path, "--top", "3"]) == 0
    out = capsys.readouterr().out
    assert "decode_at" in out and "tile_decode" in out
    assert "stage" in out and "share" in out


def test_report_cli_rejects_non_trace_file(tmp_path, capsys):
    bad = tmp_path / "not_trace.json"
    bad.write_text('{"foo": 1}')
    assert report.main([str(bad)]) == 1
    assert "traceEvents" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# fit telemetry
# ---------------------------------------------------------------------------
def test_jsonl_event_log_and_fit_event_hook():
    buf = io.StringIO()
    log = obs.set_fit_log(obs.JsonlEventLog(buf))
    try:
        assert obs.fit_telemetry_enabled()
        obs.fit_event("fit_slab", step=1, loss=0.5)
        obs.fit_event("version_append", version=0, keyframe=True)
        assert log.events_written == 2
    finally:
        obs.set_fit_log(None)
    assert not obs.fit_telemetry_enabled()
    obs.fit_event("dropped")  # no sink: must be a silent no-op
    recs = [json.loads(line) for line in buf.getvalue().splitlines()]
    assert [r["event"] for r in recs] == ["fit_slab", "version_append"]
    assert recs[0]["loss"] == 0.5 and "t" in recs[0]


def test_stream_fit_emits_slab_events(tmp_path):
    from repro.stream.fit import NTTDStreamFitter

    path = tmp_path / "fit.jsonl"
    obs.set_fit_log(str(path))
    try:
        rng = np.random.default_rng(0)
        shape = (8, 6, 4)
        fitter = NTTDStreamFitter(
            shape, rank=2, hidden=4, steps_per_slab=2, batch_size=64,
            replay_capacity=128,
        )
        idx = np.stack(
            [rng.integers(0, s, 200) for s in shape], axis=1
        )
        fitter.update(idx, rng.random(200).astype(np.float32))
        fitter.update(idx, rng.random(200).astype(np.float32))
    finally:
        obs.set_fit_log(None)
    recs = [json.loads(line) for line in path.read_text().splitlines()]
    slabs = [r for r in recs if r["event"] == "fit_slab"]
    assert len(slabs) == 2
    for r in slabs:
        assert r["codec"] == "nttd"
        assert isinstance(r["loss"], float)
        assert r["entries"] == 200
        assert r["entries_per_sec"] > 0
        assert 0 < r["reservoir_fill"] <= r["reservoir_capacity"] == 128
    assert slabs[0]["step"] == 0 and slabs[1]["step"] == 1


def test_versioned_store_emits_rekey_events(tmp_path):
    from repro.temporal import VersionedStore

    path = tmp_path / "fit.jsonl"
    obs.set_fit_log(str(path))
    try:
        rng = np.random.default_rng(3)
        base = rng.random((12, 10)).astype(np.float32)
        with VersionedStore.create(
            str(tmp_path / "v.tcdc"), "ttd", keyframe_interval=4,
            keyframe_opts={"max_rank": 4}, delta_opts={"max_rank": 2},
        ) as store:
            for k in range(3):
                store.append(base + 0.01 * k)
    finally:
        obs.set_fit_log(None)
    recs = [json.loads(line) for line in path.read_text().splitlines()]
    vas = [r for r in recs if r["event"] == "version_append"]
    assert [r["version"] for r in vas] == [0, 1, 2]
    assert vas[0]["keyframe"] is True and vas[1]["keyframe"] is False
    for r in vas:
        assert r["bytes"] > 0 and 0 <= r["fitness"] <= 1 + 1e-9
        assert r["rekeyed"] is False


# ---------------------------------------------------------------------------
# end-to-end: one stitched cross-process trace
# ---------------------------------------------------------------------------
def _nttd_payload(tmp_path) -> tuple[str, tuple[int, ...]]:
    rng = np.random.default_rng(1)
    shape = (16, 12, 8)
    x = rng.random(shape).astype(np.float32)
    enc = get_codec("nttd").fit(
        x, rank=4, hidden=8, epochs=1, init_reorder=False,
        update_reorder=False, batch_size=2048, eval_batch=2048,
    )
    path = str(tmp_path / "nttd.tcdc")
    write_chunked(path, enc, chunk_bytes=2048)
    return path, shape


def test_socket_fleet_decode_is_one_stitched_trace(tmp_path, recorder,
                                                   monkeypatch):
    # a served decode runs ops.nttd_decode_tile, so the trace must
    # contain kernel_decode spans; spawned workers inherit the env
    # (REPRO_TRACE) from this process
    monkeypatch.setenv("REPRO_TRACE", "1")
    path, shape = _nttd_payload(tmp_path)
    fleet = FleetFrontend(
        ["w0", "w1"],
        transport_factory=lambda iid: SocketTransport.spawn(iid, timeout=60.0),
    )
    try:
        fleet.load_stream("nttd", path, tile_entries=96)
        recorder.clear()  # only the query's spans, not load-time ones
        rng = np.random.default_rng(5)
        idx = np.stack([rng.integers(0, s, 300) for s in shape], axis=1)
        fleet.decode_at("nttd", idx)
        metrics = collect(fleet).as_dict()
    finally:
        fleet.close()

    spans = recorder.snapshot()
    root = [s for s in spans if s.name == "fleet.decode_at"]
    assert len(root) == 1
    trace = [s for s in spans if s.trace_id == root[0].trace_id]
    names = {s.name for s in trace}
    # frontend + transport + worker service stages + kernel, ONE trace id
    assert {
        "fleet.decode_at", "fleet.submit", "fleet.flush", "transport.flush",
        "decode_at", "coalesce_flush", "tile_decode", "kernel_decode",
    } <= names
    instances = {s.instance for s in trace}
    assert "frontend" in instances
    assert instances & {"w0", "w1"}  # worker spans stitched in
    # worker spans were re-based onto the frontend timeline: every span
    # nests inside the root's window (small slack for clock-offset error)
    slack = 0.05
    for s in trace:
        assert s.t_start >= root[0].t_start - slack
        assert s.t_end <= root[0].t_end + slack
    # kernel spans parent under a worker-side stage of the same trace
    kid = next(s for s in trace if s.name == "kernel_decode")
    assert kid.parent_id in {s.span_id for s in trace}

    # export renders it as a loadable Chrome trace with the metrics riding
    out = str(tmp_path / "trace.json")
    obs.export_chrome_trace(out, spans=trace, metrics=metrics)
    doc = json.load(open(out))
    pids = {
        e["args"]["name"]
        for e in doc["traceEvents"]
        if e["ph"] == "M" and e["name"] == "process_name"
    }
    assert "frontend" in pids and pids & {"w0", "w1"}
    assert report.main([out]) == 0
