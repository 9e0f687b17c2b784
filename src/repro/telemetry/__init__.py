"""Flight-recorder telemetry: spans/counters/gauges/histograms/events,
Chrome-trace + Prometheus export, oracle reconciliation of compiled rounds
(ISSUE 6), and the mission-control layer (ISSUE 9): route-provenance
audits and self-describing run reports.

Quick use::

    from repro import telemetry

    with telemetry.record_scope(tracing=True) as rec:
        ... run FL rounds ...
        telemetry.write_trace("trace.json", rec)        # -> Perfetto
        print(telemetry.metrics_snapshot(rec)["counters"])
        telemetry.write_report("mission", rec)          # -> .md + .json

Counters, gauges, and histograms are default-on (host-side dict/bisect
work, zero device syncs); spans and events are kept only under
``tracing=True``, and every span is also a ``jax.profiler`` annotation on
the profiler's host plane (nothing syncs with the device: per-round device
time comes from the profiler's device plane);
``reconcile=True`` verifies every newly compiled round/window against the
static collective oracles. :func:`audit_window_programs` replays a planned
window sequence hop by hop and returns a structured verdict.
"""

from repro.telemetry.audit import (
    AuditError,
    AuditReport,
    AuditViolation,
    PayloadTrail,
    audit_recorder,
    audit_window_programs,
    expected_sink_weights,
)
from repro.telemetry.export import (
    chrome_trace,
    metrics_snapshot,
    prometheus_text,
    trace_scope,
    write_metrics,
    write_prometheus,
    write_trace,
)
from repro.telemetry.metrics import (
    Histogram,
    get_gauge,
    get_histogram,
    histograms_summary,
    observe,
    ratio_gauge,
    set_gauge,
)
from repro.telemetry.report import (
    mission_report,
    render_markdown,
    write_report,
)
from repro.telemetry.reconcile import (
    ReconcileReport,
    ReconciliationError,
    check_compiled,
    compare,
    compile_and_check,
    compiled_collective_counts,
    expected_hierarchical_collectives,
    expected_tdm_collectives,
)
from repro.telemetry.recorder import (
    Event,
    Recorder,
    Span,
    counters_snapshot,
    get_recorder,
    record_scope,
    set_reconcile,
    set_tracing,
    tracing_enabled,
)

__all__ = [
    "AuditError",
    "AuditReport",
    "AuditViolation",
    "Event",
    "Histogram",
    "PayloadTrail",
    "Recorder",
    "ReconcileReport",
    "ReconciliationError",
    "Span",
    "audit_recorder",
    "audit_window_programs",
    "check_compiled",
    "chrome_trace",
    "compare",
    "compile_and_check",
    "compiled_collective_counts",
    "counters_snapshot",
    "expected_hierarchical_collectives",
    "expected_sink_weights",
    "expected_tdm_collectives",
    "get_gauge",
    "get_histogram",
    "get_recorder",
    "histograms_summary",
    "metrics_snapshot",
    "mission_report",
    "observe",
    "prometheus_text",
    "ratio_gauge",
    "record_scope",
    "render_markdown",
    "set_gauge",
    "set_reconcile",
    "set_tracing",
    "trace_scope",
    "tracing_enabled",
    "write_metrics",
    "write_prometheus",
    "write_report",
    "write_trace",
]
