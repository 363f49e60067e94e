"""Reduce a profiler trace (``.xplane.pb``) to device busy time, program
and op times, and idle gaps attributed to the harness's host annotations.

Read with ``jax.profiler.ProfileData`` and nothing else.  Device planes
are those named ``/device:TPU:<n>``.  On each, the ``XLA Ops`` line holds
one event per operation run on the device, and the ``XLA Modules`` line
one per compiled program (``jit_<name>``).  Busy time is the union of the
op intervals inside the traced window; the window is the harness's
``bench.window`` annotation on the host plane.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.window"
HOST_PREFIX = "bench."


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                        # averaged over the device planes
    n_devices: int
    programs: dict[str, tuple[int, float]]   # module name -> (count, seconds)
    ops: dict[str, float]                # op name -> seconds, all devices
    idle_gaps: dict[str, float]          # host annotation -> idle seconds

    def program_seconds(self, fragment: str) -> float:
        return sum(s for name, (_, s) in self.programs.items() if fragment in name)

    def program_count(self, fragment: str) -> int:
        return sum(n for name, (n, _) in self.programs.items() if fragment in name)

    def idle_percent(self) -> float | None:
        """Share of the window in which no operation ran on the device."""
        if self.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_gaps.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": [[k, v] for k, v in gaps]}


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def reduce(profile) -> TraceSummary:
    """Summarize a ``jax.profiler.ProfileData``."""
    host_spans: list[tuple[float, float, str]] = []
    window = None
    device_lines = []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            device_lines.append((lines.get(OPS_LINE), lines.get(MODULES_LINE)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if not ev.name.startswith(HOST_PREFIX):
                        continue
                    span = (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                    if ev.name == WINDOW:
                        window = span
                    else:
                        host_spans.append(span)
    if window is None:
        raise ValueError(f"trace holds no {WINDOW!r} annotation")
    if not device_lines:
        raise ValueError("trace holds no /device:TPU plane")
    lo, hi = window[0], window[1]
    programs: dict[str, list] = defaultdict(lambda: [0, 0.0])
    ops: dict[str, float] = defaultdict(float)
    busy_total = 0.0
    gaps_by_host: dict[str, float] = defaultdict(float)
    for ops_line, mod_line in device_lines:
        intervals = []
        if ops_line is not None:
            for ev in ops_line.events:
                a, b = ev.start_ns, ev.start_ns + ev.duration_ns
                if b <= lo or a >= hi:
                    continue
                intervals.append((a, b))
                ops[ev.name] += (min(b, hi) - max(a, lo)) * 1e-9
        if mod_line is not None:
            for ev in mod_line.events:
                a, b = ev.start_ns, ev.start_ns + ev.duration_ns
                if b <= lo or a >= hi:
                    continue
                rec = programs[ev.name]
                rec[0] += 1
                rec[1] += (min(b, hi) - max(a, lo)) * 1e-9
        busy = _union(_clip(intervals, lo, hi))
        busy_total += sum(b - a for a, b in busy) * 1e-9
        _attribute_gaps(busy, lo, hi, host_spans, gaps_by_host)
    n = len(device_lines)
    return TraceSummary(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_total / n,
        n_devices=n,
        programs={k: (v[0], v[1]) for k, v in programs.items()},
        ops=dict(ops),
        idle_gaps={k: v / n for k, v in gaps_by_host.items()},
    )


def _host_segments(host_spans, lo, hi) -> list[tuple[float, float, str]]:
    """Cut [lo, hi] into stretches, each named by the innermost (latest
    started) harness annotation open on the host, "(none)" where none is."""
    marks = sorted(
        [(s, 1, i) for i, (s, _, _) in enumerate(host_spans)]
        + [(e, 0, i) for i, (_, e, _) in enumerate(host_spans)]
    )
    active: dict[int, float] = {}
    segments = []
    prev = lo
    for x, is_start, i in marks:
        if x > prev and prev < hi:
            name = host_spans[max(active, key=active.get)][2] if active else "(none)"
            segments.append((prev, min(x, hi), name))
        prev = max(prev, x)
        if is_start:
            active[i] = host_spans[i][0]
        else:
            active.pop(i, None)
    if prev < hi:
        segments.append((prev, hi, "(none)"))
    return [(max(a, lo), b, n) for a, b, n in segments if b > lo]


def _attribute_gaps(busy, lo, hi, host_spans, out) -> None:
    """Charge each idle stretch of the device to the host annotation open
    over it."""
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    segments = _host_segments(host_spans, lo, hi)
    j = 0
    for a, b in idle:
        while j < len(segments) and segments[j][1] <= a:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < b:
            s, e, name = segments[k]
            out[name] += (min(b, e) - max(a, s)) * 1e-9
            k += 1


def reduce_dir(trace_dir: str) -> TraceSummary:
    import jax

    return reduce(jax.profiler.ProfileData.from_file(find_xplane(trace_dir)))
