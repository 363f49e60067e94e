"""The span arithmetic (``bench/spans.py``) and the per-layer readers of
the program's own spans, on hand-made span lists and on the spans of a
CPU-sized window of each cell."""
import pytest
from bench import harness, spans
from bench_testing import mini_cell

from repro import obs
from repro.obs import Span

SEED = 3_000_000_019
SPAN_METRICS = {
    "read.host_ms": "nyc-small.bulk_reads",
    "read.fold_ms": "nyc-small.bulk_reads",
    "read.operands_ms": "nyc-small.bulk_reads",
    "fit.host_ms": "nyc-small.stream_fit",
    "fit.dispatch_ms": "nyc-small.stream_fit",
}


def sp(name, trace, sid, parent, t0, t1):
    return Span(name, trace, sid, parent, t0, t1, {})


def read_request(trace, base, t0, wait):
    """decode_at > payload.decode > (nttd.fold, nttd.operands,
    payload.device_wait), 10 ms long, with ``wait`` seconds of waiting."""
    return [
        sp("decode_at", trace, base, 0, t0, t0 + 0.010),
        sp("payload.decode", trace, base + 1, base, t0, t0 + 0.009),
        sp("nttd.fold", trace, base + 2, base + 1, t0, t0 + 0.002),
        sp("nttd.operands", trace, base + 3, base + 1, t0 + 0.002, t0 + 0.003),
        sp("payload.device_wait", trace, base + 4, base + 1, t0 + 0.009 - wait, t0 + 0.009),
    ]


def fit_update(trace, base, t0, dispatch):
    return [
        sp("fit.update", trace, base, 0, t0, t0 + 0.006),
        sp("fit.sample", trace, base + 1, base, t0, t0 + 0.003),
        sp("fit.dispatch", trace, base + 2, base, t0 + 0.003, t0 + 0.003 + dispatch),
        sp("fit.reservoir", trace, base + 3, base, t0 + 0.005, t0 + 0.006),
    ]


def read(metric, span_list):
    ctx = harness.Context({}, {}, {}, None, {}, span_list)
    return harness.reader_of(metric)(ctx)


def test_under_finds_nested_descendants_of_its_own_trace_only():
    a = read_request(1, 10, 0.0, 0.004)
    b = read_request(2, 10, 1.0, 0.001)   # same span ids, another trace
    kids = spans.children(a + b)
    (wait,) = spans.under(kids, a[0], "payload.device_wait")
    assert wait is a[4]
    assert spans.under(kids, a[0], "fit.dispatch") == []


def test_a_span_nested_in_one_of_its_name_is_not_counted_twice():
    outer = sp("payload.device_wait", 1, 2, 1, 0.0, 0.004)
    inner = sp("payload.device_wait", 1, 3, 2, 0.001, 0.002)
    root = sp("decode_at", 1, 1, 0, 0.0, 0.010)
    assert spans.under(spans.children([root, outer, inner]), root,
                       "payload.device_wait") == [outer]
    assert spans.mean_less_ms([root, outer, inner], "decode_at",
                              "payload.device_wait") == pytest.approx(6.0)


def test_host_time_subtracts_the_nested_wait_of_each_request():
    got = read("read.host_ms", read_request(1, 10, 0.0, 0.004) + read_request(2, 20, 1.0, 0.002))
    assert got == pytest.approx(((10 - 4) + (10 - 2)) / 2)


def test_fold_and_operands_divide_by_requests_not_by_spans():
    reqs = read_request(1, 10, 0.0, 0.004) + read_request(2, 20, 1.0, 0.004)
    # a request that reached no fold (say, an empty one) still counts
    reqs.append(sp("decode_at", 3, 30, 0, 2.0, 2.001))
    assert read("read.fold_ms", reqs) == pytest.approx(2 * 2.0 / 3)
    assert read("read.operands_ms", reqs) == pytest.approx(2 * 1.0 / 3)


def test_fit_readers_take_the_mean_update_and_its_dispatch():
    ups = fit_update(1, 10, 0.0, 0.001) + fit_update(2, 10, 1.0, 0.0015)
    assert read("fit.host_ms", ups) == pytest.approx(6.0)
    assert read("fit.dispatch_ms", ups) == pytest.approx(1.25)


def test_a_fold_outside_a_request_is_not_read():
    stray = [sp("nttd.fold", 1, 5, 0, 0.0, 0.5)]   # a decode not served
    reqs = read_request(2, 10, 1.0, 0.004)
    assert read("read.fold_ms", stray + reqs) == pytest.approx(2.0)


@pytest.mark.parametrize("metric", sorted(SPAN_METRICS))
def test_a_reader_reads_nothing_without_its_spans(metric):
    assert read(metric, []) is None
    # the service's own span alone, as a program without the inner spans
    # records it, is not enough either
    assert read(metric, [sp("decode_at", 1, 1, 0, 0.0, 0.01)]) is None
    other = fit_update(1, 10, 0.0, 0.001) if metric.startswith("read.") else \
        read_request(1, 10, 0.0, 0.004)
    assert read(metric, other) is None


def _window_spans(cell_name, work_dir):
    """The program's spans over a short CPU window of a mini cell."""
    cfg, traffic = mini_cell(cell_name)
    runner = harness.runner_of(traffic["kind"])(cfg, traffic, SEED, work_dir)
    runner.setup()
    rec = obs.enable_tracing()
    rec.clear()
    try:
        runner.window(0.4)
        got = rec.drain()
    finally:
        obs.disable_tracing()
        runner.release()
    return cfg, traffic, got


@pytest.mark.parametrize("cell", sorted(set(SPAN_METRICS.values())))
def test_the_readers_read_a_window_of_their_cell(cell, fused_decode, work_dir):
    """The five metrics, listed for their cells as BENCHMARK.json would
    list them, each read a value from the program's spans."""
    cfg, traffic, got = _window_spans(cell, work_dir)
    bench = harness.load_benchmark()
    moves = {"nyc-small.bulk_reads": "read_entries_per_s",
             "nyc-small.stream_fit": "fit_entries_per_s"}[cell]
    bench["per_layer"] = [
        {"name": m, "unit": "ms", "better": "lower", "source": "host_clock",
         "layer": "host", "moves": moves, "workloads": [c]}
        for m, c in SPAN_METRICS.items()
    ]
    ctx = harness.Context(cfg, traffic, {}, None, {}, got)
    metrics, unread = harness.layer_metrics(bench, cell, ctx)
    assert unread == []
    assert set(metrics) == {m for m, c in SPAN_METRICS.items() if c == cell}
    assert all(v["value"] > 0 for v in metrics.values()), metrics
    if cell == "nyc-small.bulk_reads":
        whole = 1e3 * max(s.duration for s in got if s.name == "decode_at")
        assert metrics["read.fold_ms"]["value"] + metrics["read.operands_ms"]["value"] \
            < metrics["read.host_ms"]["value"] <= whole
    else:
        assert metrics["fit.dispatch_ms"]["value"] < metrics["fit.host_ms"]["value"]

