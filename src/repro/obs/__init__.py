"""``repro.obs`` — low-overhead tracing + metrics for the serving stack.

The paper's headline serving property (logarithmic per-entry
reconstruction, §4.4) only matters operationally if you can SEE where a
request spends its time.  This package threads spans through the whole
pipeline — ``FleetFrontend.decode_at`` → ``Transport`` wire →
``repro.fleet.worker`` → ``CodecService`` stages (``chunk_read``,
``materialize``, ``tile_decode``, ``prefetch_wait``, ``coalesce_flush``)
→ ``CompressedTensor.decode`` (``payload.decode``, ``payload.orig_to_pos``,
``nttd.fold``, ``nttd.operands``, ``payload.device_wait``) → the fused
``kernel_decode`` — stitches worker spans back into one cross-process
trace, and exports Chrome trace-event JSON that Perfetto loads directly.
``nttd.operands`` wraps the lookup of the fused decode's operands, which
a payload stacks once per parameter set: its ``built`` is 1 when that
call stacked them and 0 when it reused them.  ``kernel_decode``, on every
served decode, times the enqueue of an asynchronous call; its ``impl``
names the path taken (``pallas`` on a TPU, ``pallas_interpret``,
``oracle`` elsewhere), its ``tile`` the kernel's lanes a grid step.  The
kernel's device time, the batch pad and transpose with it, is
``jit_decode_tile`` in a profiler trace.
The streaming fit records ``fit.update`` with ``fit.sample``,
``fit.dispatch`` and ``fit.reservoir`` inside it.  Every live span is
also a ``jax.profiler.TraceAnnotation``, so a profiler trace holds the
spans on its device events' clock.

    from repro import obs

    obs.enable_tracing()                      # or REPRO_TRACE=1
    fleet.decode_at("embed", idx)             # answers unchanged, bit-exact
    obs.export_chrome_trace("trace.json")
    # python -m repro.obs.report trace.json   # per-stage breakdown

Design contract: tracing and metrics are OBSERVATIONAL ONLY — answers
and every cache counter are bit-identical with tracing off or on, and a
disabled recorder allocates nothing per span (both asserted in CI).

Fit-time telemetry rides the same package: ``REPRO_FIT_LOG=fit.jsonl``
(or :func:`set_fit_log`) streams per-slab fit events (step, loss,
entries/sec, reservoir occupancy) and ``VersionedStore`` rekey decisions
as JSONL.
"""
from repro.obs.events import clear_events, emit_event, events
from repro.obs.export import (
    JsonlEventLog,
    chrome_trace_events,
    export_chrome_trace,
    fit_event,
    fit_log,
    fit_telemetry_enabled,
    set_fit_log,
)
from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    default_latency_buckets,
)
from repro.obs.trace import (
    Span,
    TraceRecorder,
    current_context,
    disable_tracing,
    enable_tracing,
    enabled,
    get_recorder,
    remote_context,
    span,
)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "JsonlEventLog",
    "MetricsRegistry",
    "Span",
    "TraceRecorder",
    "chrome_trace_events",
    "clear_events",
    "current_context",
    "default_latency_buckets",
    "disable_tracing",
    "emit_event",
    "enable_tracing",
    "enabled",
    "events",
    "export_chrome_trace",
    "fit_event",
    "fit_log",
    "fit_telemetry_enabled",
    "get_recorder",
    "remote_context",
    "set_fit_log",
    "span",
]
