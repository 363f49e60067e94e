"""Each cell's path through the harness's own functions (traffic, window,
comparison, metric arithmetic) at a CPU-sized shape, and the faults and
the lower-precision control that the comparison has to catch."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from bench import harness, reads
from bench_testing import mini_cell, mini_config

CELLS = ["pems_sf-medium.bulk_reads", "nyc-small.stream_fit", "nyc-small.bulk_reads"]
#: configurations and mixes kept for cells that a later change adds
LATER = [("pems_sf-medium", "point_reads")]
SEED = 3_000_000_017  # above 32 bits' reach of a signed int
WINDOW_S = 0.6


def run(cell, **kw):
    cfg, traffic = mini_cell(cell)
    return harness.run_cell(harness.load_benchmark(), cell, SEED, WINDOW_S, False,
                            time.perf_counter(), jax.devices()[0], cfg=cfg,
                            traffic=traffic, **kw)


def drive(config, mix, work_dir):
    """Set-up, window and check of a configuration under a traffic mix."""
    traffic = harness.traffic_of(mix)
    d = harness.runner_of(traffic["kind"])(mini_config(config), traffic, SEED, work_dir)
    d.setup()
    stats = d.window(WINDOW_S)
    d.release()
    return stats, d.check()


@pytest.mark.parametrize("config, mix", LATER)
def test_later_mixes_run_and_are_correct(config, mix, fused_decode, work_dir):
    stats, checks = drive(config, mix, work_dir)
    assert stats["attempted"] > 0 and stats["failed"] == 0
    assert all(c.ok for c in checks), checks


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_is_correct(cell, fused_decode, work_dir):
    result = run(cell)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    bench = harness.load_benchmark()
    expected = {m["name"] for m in harness.end_to_end_of(bench, cell)}
    assert set(result["metrics"]) == expected
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert list(result)[-1] == "checks"
    for c in result["checks"].values():
        assert c["value"] <= c["limit"]


def test_an_answer_altered_where_it_is_produced_is_not_correct(fused_decode, work_dir,
                                                               monkeypatch):
    from repro.codecs import adapters

    decode_at = adapters.NTTDEncoded.decode_at

    def altered(self, indices):
        out = np.array(decode_at(self, indices))
        out[0] += 0.1 * self.ct.norm_std
        return out

    monkeypatch.setattr(adapters.NTTDEncoded, "decode_at", altered)
    assert not run("pems_sf-medium.bulk_reads")["correct"]
    _, checks = drive("pems_sf-medium", "point_reads", work_dir)
    assert not all(c.ok for c in checks)


def test_reads_at_the_lower_precision_are_not_correct(fused_decode, work_dir):
    """The control: the reference at three-pass bfloat16 in the program's place."""
    from bench import reference

    cfg, traffic = mini_cell("pems_sf-medium.bulk_reads")
    d = harness.runner_of(traffic["kind"])(cfg, traffic, SEED, work_dir)
    d.setup()
    d.window(WINDOW_S)
    d.release()
    assert all(c.ok for c in d.check())
    d.kept = [(idx, reference.decode(d.payload.ref, idx, "bf16x3")) for idx, _ in d.kept]
    gap = next(c for c in d.check() if c.name == "read_rms_gap")
    assert not gap.ok, gap
    assert reads.control_gap(d.payload, d.kept) == pytest.approx(gap.value)


def _broken_epoch(kind):
    from repro.core import codec as codec_lib

    make = codec_lib._make_train_epoch

    def broken(spec, cfg, opt):
        epoch = make(spec, cfg, opt)
        if kind == "unchanged":
            return lambda params, state, pos, val: (params, state, jnp.zeros((), jnp.float32))

        def half(params, state, pos, val):
            h = pos.shape[1] // 2
            pos = jnp.concatenate([pos[:, :h], pos[:, :h]], axis=1)
            val = jnp.concatenate([val[:, :h], val[:, :h]], axis=1)
            return epoch(params, state, pos, val)

        return half

    return broken


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_a_broken_train_step_is_not_correct(fault, work_dir, monkeypatch):
    from repro.core import codec as codec_lib

    monkeypatch.setattr(codec_lib, "_make_train_epoch", _broken_epoch(fault))
    result = run("nyc-small.stream_fit")
    assert not result["correct"], result["checks"]


def test_fit_at_the_lower_precision_is_not_correct(work_dir):
    """The control: the reference at the precision below the configuration's
    (three-pass bfloat16 below ``highest``) in the program's place."""
    from bench import reference

    cfg, traffic = mini_cell("nyc-small.stream_fit")
    d = harness.runner_of(traffic["kind"])(cfg, traffic, SEED, work_dir)
    d.setup()
    d.release()
    ref = d.reference_run()
    sound = d.gaps(d.readings, ref)
    control = d.gaps(d.reference_run(reference.CONTROL[cfg["precision"]["fit"]]), ref)
    assert all(v <= cfg["limits"][k] for k, v in sound.items()), sound
    assert any(v > cfg["limits"][k] for k, v in control.items()), control
