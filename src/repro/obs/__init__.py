"""``repro.obs`` — stack-wide observability.

Three pillars (see README "Observability"):

* **Virtual-time tracing** (:mod:`repro.obs.trace`): per-op causal spans
  in *simulated* time.  The oracle samples stage boundaries between its
  event yields; the fast engine reconstructs the identical boundaries
  from its batched delay columns — span-level agreement is a
  differential axis on top of the existing latency checks.
* **Metrics registry** (:mod:`repro.obs.metrics`): typed
  Counter/Gauge/Histogram instruments behind stable dotted names,
  snapshot/diff-able, near-zero overhead when disabled.
* **Host timing** (:func:`walltime`, :func:`span`): the one sanctioned
  wall-clock, and named host spans that land both in a
  ``jax.profiler`` trace and in the seconds a sweep reports.

CLI: ``python -m repro.obs {summarize,diff,flamegraph} trace.json``.
"""
from .clock import timed, walltime
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      NULL_INSTRUMENT, format_snapshot)
from .spans import span
from .trace import BOUNDARY_FIELDS, STAGES, TraceSet

__all__ = [
    "BOUNDARY_FIELDS", "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "NULL_INSTRUMENT", "STAGES", "TraceSet", "format_snapshot", "span",
    "timed", "walltime",
]
