"""repro.obs — end-to-end request tracing, telemetry export, and
measured-latency feedback into the planner.

Three pieces:

* :mod:`repro.obs.trace` — ``span``, the one primitive at every layer
  boundary (a profiler annotation ``repro.<layer>.<stage>``, optional
  wall/CPU-time counters in a ``MetricsRegistry``, and a child span in
  the current request traces), and ``Tracer`` / ``Trace`` / ``Span``:
  one structured trace per served request, clocked by
  ``runtime.clock`` (deterministic under ``VirtualClock``).
* :mod:`repro.obs.export` — registry snapshots + drained spans as
  JSON and Prometheus text exposition.
* :mod:`repro.obs.feedback` — ``PlanFeedback``: per-(bucket, plan)
  execute-latency EWMAs that ``plan.autoplan.choose_plan`` consults
  before the modeled ``DeviceModel`` costs.
"""

from repro.obs.export import (
    render_prometheus,
    render_traces_json,
    traces_to_dicts,
    write_metrics_json,
    write_prometheus,
    write_traces_json,
)
from repro.obs.feedback import (
    PlanFeedback,
    bucket_key,
    plan_key,
    plan_key_from_plan,
)
from repro.obs.trace import (
    Span,
    SpanEvent,
    Trace,
    Tracer,
    current_span,
    current_spans,
    engine_batch_info,
    install_ledger_listener,
    plan_attributes,
    span,
    use_span,
    use_spans,
)

__all__ = [
    "Span",
    "SpanEvent",
    "Trace",
    "Tracer",
    "span",
    "current_span",
    "current_spans",
    "use_span",
    "use_spans",
    "plan_attributes",
    "engine_batch_info",
    "install_ledger_listener",
    "PlanFeedback",
    "bucket_key",
    "plan_key",
    "plan_key_from_plan",
    "traces_to_dicts",
    "render_traces_json",
    "write_traces_json",
    "write_metrics_json",
    "render_prometheus",
    "write_prometheus",
]
