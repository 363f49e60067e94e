"""The restore cell and the tiled Zipf cell through the harness at a
CPU-sized shape; the faults and the lower-precision control that the
restore's comparison has to catch; the chip share that the restore
configuration states; and both cells' per-layer readers.

The tiled Zipf cell is held back from ``BENCHMARK.json``: over seeds its
rate spreads by about half the bound of ``read_entries_per_s`` in a
10 s window (PERF.md §7).  ``ZIPF_CELL`` and ``ZIPF_METRICS`` are its
entries, for the harness here and for the change that lists it."""
import time

import jax
import numpy as np
import pytest
from bench import checkpoints, harness, reference, trace_reduce
from bench_testing import mini_config

SEED = 3_000_000_023
WINDOW_S = 0.6
RESTORE = "granite4h_small-ep9.ckpt_restore"
ZIPF = "pems_sf-medium.tiled_zipf"
ZIPF_CELL = {
    "name": ZIPF, "config": "pems_sf-medium", "traffic": "tiled_zipf", "chips": 1,
    "why": "closed loop, one caller, 1,024 entries in one tile a request, tiles Zipf(0.99), "
           "working set 4x cache_bytes: the decode-tile LRU and a 65,536-entry decode per miss"}
ZIPF_METRICS = [
    {"name": "tile_cache.hit_share", "unit": "%", "better": "higher",
     "source": "program_counter", "layer": "tile cache", "moves": "read_entries_per_s",
     "workloads": [ZIPF]},
    {"name": "tiled.decode_tile_roofline", "unit": "%", "better": "higher",
     "source": "device_trace", "layer": "kernel", "moves": "read_entries_per_s",
     "workloads": [ZIPF]},
]


def zipf_bench() -> dict:
    """BENCHMARK.json with the tiled Zipf cell listed: its own entries, and
    the cell appended to ``read_entries_per_s`` and ``device_idle.read_rate``."""
    bench = harness.load_benchmark()
    bench["workloads"].append(ZIPF_CELL)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("read_entries_per_s", "device_idle.read_rate"):
            m["workloads"].append(ZIPF)
    bench["per_layer"] += ZIPF_METRICS
    return bench


def share_leaves(min_entries: int) -> list[dict]:
    """The NTTD leaves of granite-4.0-h-small's chip share: per-layer
    matrices of at least ``min_entries``, at their default d'."""
    from repro.configs import granite_4_0_h_small as granite
    from repro.core.folding import make_folding_spec
    from repro.dist.sharding import ParamSpec

    def leaves(specs):
        for path, s in jax.tree_util.tree_leaves_with_path(
                specs, is_leaf=lambda s: isinstance(s, ParamSpec)):
            if int(np.prod(s.shape)) >= min_entries and sum(a != "layers" for a in s.axes) >= 2:
                spec = make_folding_spec(s.shape)
                yield {"key": "/".join(str(getattr(p, "key", p)) for p in path),
                       "shape": list(s.shape), "d_prime": spec.d_prime,
                       "folded_shape": list(spec.folded_shape)}

    share = granite.chip_share() if min_entries >= 65536 else \
        granite.chip_share(granite.SMOKE, chips=4)
    return list(leaves(share))


def mini_restore() -> tuple[dict, dict]:
    """The restore cell at the SMOKE share's leaves, in 4,096-entry slabs."""
    bench = harness.load_benchmark()
    cfg = harness.config_of(bench, "granite4h_small-ep9")
    traffic = harness.traffic_of("ckpt_restore")
    return dict(cfg, leaves=share_leaves(1024)), dict(traffic, slab_entries=4096)


def mini_zipf() -> tuple[dict, dict]:
    return mini_config("pems_sf-medium"), dict(harness.traffic_of("tiled_zipf"),
                                               tile_entries=4096)


def run(cell, cfg, traffic):
    bench = zipf_bench() if cell == ZIPF else harness.load_benchmark()
    return harness.run_cell(bench, cell, SEED, WINDOW_S, False,
                            time.perf_counter(), jax.devices()[0], cfg=cfg, traffic=traffic)


def drive(cfg, traffic, work_dir):
    d = harness.runner_of(traffic["kind"])(cfg, traffic, SEED, work_dir)
    d.setup()
    stats = d.window(WINDOW_S)
    d.release()
    return d, stats


def test_the_config_file_states_the_chip_share():
    cfg = harness.config_of(harness.load_benchmark(), "granite4h_small-ep9")
    assert cfg["leaves"] == share_leaves(65536)
    total = sum(int(np.prod(leaf["shape"])) for leaf in cfg["leaves"])
    assert total == cfg["entries"] == 2_320_081_920 and len(cfg["leaves"]) == 15
    assert cfg["num_local_experts"] * cfg["deployment"]["expert_parallel"] == \
        cfg["published"]["num_local_experts"]
    router = next(leaf for leaf in cfg["leaves"] if leaf["key"] == "blocks/moe/router")
    assert router["shape"][-1] == cfg["published"]["num_local_experts"]
    assert cfg["layer_types"][5] == "attention" and cfg["layer_types"].count("attention") == 1


@pytest.mark.parametrize("cell", [RESTORE, ZIPF])
def test_cell_runs_and_is_correct(cell, fused_decode, work_dir):
    cfg, traffic = mini_restore() if cell == RESTORE else mini_zipf()
    result = run(cell, cfg, traffic)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"setup_s", "read_entries_per_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert list(result)[-1] == "checks"


def test_the_restore_covers_slabs_of_many_leaves(work_dir):
    cfg, traffic = mini_restore()
    d, stats = drive(cfg, traffic, work_dir)
    assert len(stats["restored"]) >= 2
    assert sum(stats["restored"].values()) == stats["entries"]
    for key, (flat, values) in d.kept.items():
        assert len(flat) == len(values) > 0
        assert flat.max() < np.prod(next(x["shape"] for x in cfg["leaves"] if x["key"] == key))


def test_a_restore_without_the_inverse_order_is_not_correct(work_dir, monkeypatch):
    from repro.core import codec, nttd

    def unordered(self, slab=nttd.SLAB_ENTRIES):
        return nttd.DenseSlabs(self.params, self.spec, self.cfg, None,
                               self.norm_mean, self.norm_std, slab)

    monkeypatch.setattr(codec.CompressedTensor, "dense_slabs", unordered)
    cfg, traffic = mini_restore()
    result = run(RESTORE, cfg, traffic)
    assert not result["correct"], result["checks"]


def test_a_restore_at_the_lower_precision_is_not_correct(work_dir):
    """The control: the reference at three-pass bfloat16 in the program's place."""
    cfg, traffic = mini_restore()
    d, _ = drive(cfg, traffic, work_dir)
    assert all(c.ok for c in d.check())
    control = reference.CONTROL[cfg["precision"]["decode"]]
    d.kept = {key: (flat, reference.decode(
        d.ckpt.refs[key], np.stack(np.unravel_index(flat, d.ckpt.refs[key]["shape"]), axis=1),
        control)) for key, (flat, _) in d.kept.items()}
    gap = next(c for c in d.check() if c.name == "read_rms_gap")
    assert not gap.ok, gap
    assert max(checkpoints.gaps(d.ckpt, d.kept).values()) == pytest.approx(gap.value)


def test_the_tiled_cell_starts_with_a_full_cache_and_misses_in_the_window(fused_decode,
                                                                          work_dir):
    cfg, traffic = mini_zipf()
    d = harness.runner_of(traffic["kind"])(cfg, traffic, SEED, work_dir)
    d.setup()
    cs = d.svc.cache_stats
    assert cs.evictions > 0 and cs.resident_bytes <= d.cache_bytes
    stats = d.window(WINDOW_S)
    assert 0 < stats["tile_misses"] < stats["attempted"]
    notes = d.notes(stats)
    assert 0 < notes["tile_hit_share"] < 1
    # a miss decodes its whole tile (here every tile is whole); every
    # request is timed, hit or miss
    assert d.n % 4096 == 0 and stats["decoded_entries"] == stats["tile_misses"] * 4096
    assert len(stats["miss_s"]) == stats["tile_misses"]
    assert len(stats["hit_s"]) + len(stats["miss_s"]) == stats["attempted"]
    assert sum(notes["requests_each_s"]) == stats["attempted"]
    assert notes["miss_ms_median"] > 0 and notes["slowest_ms"] >= notes["miss_ms_median"]
    d.release()
    assert all(c.ok for c in d.check())


def test_the_restore_set_up_compiles_every_slab_program_once(work_dir):
    """Set-up runs one slab of each distinct program (leaves of one shape
    and d' share one); the window then compiles nothing."""
    cfg, traffic = mini_restore()
    d = harness.runner_of(traffic["kind"])(cfg, traffic, SEED, work_dir)
    d.setup()
    programs = {(tuple(leaf["shape"]), leaf["d_prime"]) for leaf in cfg["leaves"]}
    assert d.phases["slab_programs"] == len(programs) < len(cfg["leaves"])
    with harness.compile_clock() as compiles:
        stats = d.window(WINDOW_S)
    assert compiles["count"] == 0
    assert len(stats["restored"]) >= 2
    notes = d.notes(stats)
    assert all(notes[k] > 0 for k in ("setup_payload_s", "setup_plan_s", "setup_warm_s"))
    d.release()


def test_the_tiled_readers():
    bench = zipf_bench()
    cfg = harness.config_of(bench, "pems_sf-medium")
    trace = trace_reduce.TraceSummary(
        window_s=1.0, busy_s=0.25, n_devices=1,
        programs={"jit_decode_tile(7)": (30, 0.2)}, ops={}, idle_gaps={})
    stats = {"attempted": 101, "failed": 1, "tile_misses": 30, "entries": 100 * 1024,
             "decoded_entries": 30 * 65536, "elapsed": 1.0}
    ctx = harness.Context(cfg, {}, stats, trace, harness.peak_of("TPU v5 lite"), [])
    metrics, unread = harness.layer_metrics(bench, ZIPF, ctx)
    assert unread == []
    assert metrics["tile_cache.hit_share"]["value"] == pytest.approx(70.0)
    assert metrics["device_idle.read_rate"]["value"] == pytest.approx(75.0)
    from bench import flops

    work = 30 * 65536 * flops.decode_flops_per_entry(cfg["d_prime"], cfg["hidden"], cfg["rank"])
    assert metrics["tiled.decode_tile_roofline"]["value"] == pytest.approx(
        100.0 * work / ctx.peak["flops_per_s"] / 0.2)
    # a window with no miss decoded nothing: the tile's share reads nothing
    ctx.stats = dict(stats, tile_misses=0, decoded_entries=0)
    _, unread = harness.layer_metrics(bench, ZIPF, ctx)
    assert unread == ["tiled.decode_tile_roofline"]


def test_the_restore_readers():
    bench = harness.load_benchmark()
    cfg = harness.config_of(bench, "granite4h_small-ep9")
    tile = "%decode_tile.1 = f32[4194304,1]{1,0:T(8,128)} custom-call(%copy.6)"
    trace = trace_reduce.TraceSummary(
        window_s=1.0, busy_s=0.9, n_devices=1,
        programs={"jit_restore_slab(123)": (3, 0.9), "jit_other": (1, 0.01)},
        ops={tile: 0.84, "%fusion.1 = s32[4194304]": 0.05}, idle_gaps={})

    class S:
        def __init__(self, name, duration):
            self.name, self.duration = name, duration

    spans = [S("ckpt.restore_slab", 0.002), S("ckpt.restore_slab", 0.004), S("other", 1.0)]
    stats = {"entries": 3 * 4194304, "elapsed": 1.0,
             "restored": {"tok/embed": 2 * 4194304, "blocks/moe/w_up": 4194304}}
    ctx = harness.Context(cfg, {}, stats, trace, harness.peak_of("TPU v5 lite"), spans)
    metrics, unread = harness.layer_metrics(bench, RESTORE, ctx)
    assert unread == []
    assert metrics["device_idle.restore"]["value"] == pytest.approx(10.0)
    assert metrics["restore.slab_device_ms"]["value"] == pytest.approx(1e3 * 0.06 / 3)
    assert metrics["restore.host_ms"]["value"] == pytest.approx(3.0)
    from bench import flops

    work = 2 * 4194304 * flops.decode_flops_per_entry(17, 16, 8) + \
        4194304 * flops.decode_flops_per_entry(12, 16, 8)
    want = 100.0 * work / ctx.peak["flops_per_s"] / 0.84
    assert metrics["restore.decode_tile_roofline"]["value"] == pytest.approx(want)
    assert metrics["restore.read_mfu"]["value"] == pytest.approx(
        100.0 * work / ctx.peak["flops_per_s"])
    # a trace without the tile reads nothing
    bare = trace_reduce.TraceSummary(1.0, 0.5, 1, {"jit_other": (1, 0.5)}, {}, {})
    ctx = harness.Context(cfg, {}, stats, bare, harness.peak_of("TPU v5 lite"), [])
    _, unread = harness.layer_metrics(bench, RESTORE, ctx)
    assert set(unread) == {"restore.decode_tile_roofline", "restore.slab_device_ms",
                           "restore.host_ms"}
