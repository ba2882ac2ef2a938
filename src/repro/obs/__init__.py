"""repro.obs — unified telemetry for the serving stack.

::

    metrics  ── process-global registry: counters/gauges/histograms with
    │           labeled series; JSON snapshot + Prometheus text exposition
    tracing  ── nestable spans in wall-clock AND virtual decode-step time
    │           (gateway tick and publish, admission, prefill, decode
    │           chunk, row reads, park/restore), recorded host-side
    │           between compiled calls, each also a profiler annotation
    export   ── Chrome/Perfetto trace_event JSON + snapshot writers
    cycles   ── per-op-family predicted-vs-measured cycle ledger hooked
                into ``CPMProgram.steps_report()`` (model drift metric)

Contract (the PR-6 trace-safety rule extended to telemetry): all
recording is host-side Python between compiled calls — instrumented
serving code compiles **byte-identically** to uninstrumented code (same
program cache keys, same pallas launch counts, jaxpr-asserted in
``tests/test_obs.py``), and ``REPRO_OBS=0`` reduces every span/ledger
record to one env lookup while the metric instruments keep functioning
(the serving layers' ``stats()`` dicts are thin views over them).
"""

from . import cycles, export, live, metrics, promparse, slo, tracing
from .cycles import LEDGER, audit, drift_table
from .export import (chrome_trace, iter_trace_chunks, validate_chrome_trace,
                     write_metrics, write_trace, write_trace_stream)
from .live import TraceRing
from .metrics import (REGISTRY, counter, enabled, gauge, histogram,
                      prometheus_text, snapshot)
from .slo import BurnWindow, FlightRecorder, SloMonitor, allocator_state
from .tracing import TRACER, instant, span

__all__ = [
    "cycles", "export", "live", "metrics", "promparse", "slo", "tracing",
    "LEDGER", "audit", "drift_table",
    "chrome_trace", "iter_trace_chunks", "validate_chrome_trace",
    "write_metrics", "write_trace", "write_trace_stream",
    "TraceRing", "BurnWindow", "FlightRecorder", "SloMonitor",
    "allocator_state",
    "REGISTRY", "counter", "enabled", "gauge", "histogram",
    "prometheus_text", "snapshot",
    "TRACER", "instant", "span",
]
