"""Observability layer: span tracing, metrics, and text reports.

The paper's evidence is *measured* runtime behaviour — emitter utilisation,
per-worker queue occupancy, weighted-load balance (Fig. 13/14) and the
NP/NAP decision statistics (Fig. 15).  This package is the unified way the
repo's three runtimes expose that data:

  :mod:`repro.obs.trace`    — thread-safe span tracer; exports Chrome
                              trace-event JSON loadable in Perfetto
                              (https://ui.perfetto.dev).
  :mod:`repro.obs.metrics`  — process-wide registry of labelled counters,
                              gauges and histograms.
  :mod:`repro.obs.report`   — text summary renderer (phase breakdowns,
                              queued-weight timelines, latency histograms).

Instrumented producers: the supervised farm (:mod:`repro.core.farm`), the
SPMD frontier engine (:func:`repro.core.frontier.build`: named phases in
its fused program, per-superstep totals in the registry, profiler
annotations around its host work), the serving engine
(:mod:`repro.serve.engine`) and the heartbeat plane
(:mod:`repro.train.elastic`).  Enabled spans also land in a JAX profiler
trace.  Everything is zero-cost when tracing is disabled: the default
:data:`repro.obs.trace.NULL` tracer short-circuits every call.
"""

from repro.obs.metrics import REGISTRY, Registry  # noqa: F401
from repro.obs.trace import NULL, Tracer  # noqa: F401
