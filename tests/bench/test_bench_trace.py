"""The trace reduction: busy and idle time, program time by name, and idle
gaps charged to the harness's host annotations."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest
from bench import trace_reduce


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def fake_profile():
    """A 1,000 ns window: two programs, three overlapping ops, host
    annotations around a request and an arrival wait."""
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_decode_tile(1)", 100, 300),
                                       ev("jit_fold(2)", 600, 100)]),
        NS(name="XLA Ops", events=[ev("fusion.1", 100, 200), ev("custom-call", 250, 150),
                                   ev("fusion.2", 600, 100), ev("outside", 2000, 50)]),
    ])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.window", 0, 1000), ev("bench.decode_at", 50, 700),
        ev("bench.wait_arrival", 750, 250), ev("other", 0, 10)])])
    return NS(planes=[NS(name="/host:metadata", lines=[]), host, device])


def test_reduction_of_a_hand_made_trace():
    s = trace_reduce.reduce(fake_profile())
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx(400e-9)        # [100, 400) and [600, 700)
    assert s.program_seconds("decode_tile") == pytest.approx(300e-9)
    assert s.program_count("fold") == 1
    assert s.ops["custom-call"] == pytest.approx(150e-9)
    assert "outside" not in s.ops                    # after the window
    # idle: [0,100) [400,600) [700,1000)
    assert s.idle_gaps["(none)"] == pytest.approx(50e-9)
    assert s.idle_gaps["bench.decode_at"] == pytest.approx(50e-9 + 200e-9 + 50e-9)
    assert s.idle_gaps["bench.wait_arrival"] == pytest.approx(250e-9)
    assert sum(s.idle_gaps.values()) == pytest.approx(s.window_s - s.busy_s)
    b = s.breakdown()
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(200e-9)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_a_trace_without_the_window_or_a_device_is_refused():
    p = fake_profile()
    p.planes[1].lines[0].events = p.planes[1].lines[0].events[1:]
    with pytest.raises(ValueError, match="bench.window"):
        trace_reduce.reduce(p)
    p = fake_profile()
    p.planes = p.planes[:2]
    with pytest.raises(ValueError, match="TPU"):
        trace_reduce.reduce(p)


def test_reduction_of_a_trace_recorded_on_the_chip():
    """A 0.1 s window of ``pems_sf-medium.bulk_reads`` on one TPU v5e
    (``calibrate.py trace``): five requests, each one decode-tile program
    among the eager fold's, the device idle about three quarters."""
    import jax

    path = Path(__file__).parent / "data" / "pems_bulk_reads.xplane.pb"
    s = trace_reduce.reduce(jax.profiler.ProfileData.from_file(str(path)))
    assert s.n_devices == 1
    assert s.window_s == pytest.approx(0.104, abs=0.001)
    assert 0 < s.busy_s < s.window_s
    assert s.idle_percent() == pytest.approx(75.39, abs=0.01)
    assert s.program_count("jit_decode_tile") == 5
    assert s.program_seconds("jit_decode_tile") == pytest.approx(0.0222, abs=0.0001)
    assert s.program_seconds("decode_tile") < s.busy_s
    assert sum(s.idle_gaps.values()) == pytest.approx(s.window_s - s.busy_s)
    assert max(s.idle_gaps, key=s.idle_gaps.get) == "bench.decode_at"
