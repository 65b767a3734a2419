"""``repro.obs`` — observability for the S2M3 serving stack.

Four layers, threaded through ``serving/{engine,scheduler,decode}``
and surfaced on the ``s2m3.Deployment`` facade:

* **Tracing** (``obs.trace``): ``Span``/``Tracer`` with an injectable
  monotonic clock.  The engine and the serving scheduler emit spans for
  admission wait, batch formation, encoder launches (tagged with their
  cross-task composition), prefill, and every paged-decode tick, keyed
  by request id so one request's life is one trace tree.
  A decode tick also records its four host phases (``tick.form``,
  ``tick.dispatch``, ``tick.sample``, ``tick.commit``) once, under the
  rid and root span of its first live row: that request's tree and
  Chrome track carry the batch's phases, which the other rows share.
  ``Trace.to_chrome_trace()`` exports Chrome/Perfetto JSON.
* **Compile spans** (``obs.compiles``): JAX's trace, lower and backend
  compile events as spans on one process-level tracer; importing
  ``repro.s2m3`` installs the listener.
* **Metrics** (``obs.metrics``): a lock-safe counter/gauge/histogram
  registry.  The scheduler, the decode streams and the ``PagePool``
  register instruments on it; ``stats_dict()`` remains as a
  compatibility view.  ``obs.summary.slo_summary`` renders per-task
  p50/p99 and SLO-deadline attainment from the histograms.
* **Drift** (``obs.drift``): ``Deployment.compare(workload)`` runs
  ``simulate()`` and ``serve()`` on the same ``Request`` objects and
  reports predicted-vs-measured per-module latency ratios, route
  divergences, and queue-model error — the ROADMAP's
  "sim routes == real devices" invariant, checked continuously.

CLI: ``python -m repro.obs trace out.json`` (demo trace export),
``python -m repro.obs drift`` (demo drift report),
``python -m repro.obs --self-test`` (span nesting, metrics thread
safety, instrument-lock lint — wired into ``python -m repro.analysis
--self``).
"""

from repro.obs.drift import DriftReport, compare_deployment
from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.summary import format_slo_summary, slo_summary
from repro.obs.trace import Span, Trace, Tracer

__all__ = [
    "Counter", "DriftReport", "Gauge", "Histogram", "MetricsRegistry",
    "Span", "Trace", "Tracer", "compare_deployment",
    "format_slo_summary", "slo_summary",
]
