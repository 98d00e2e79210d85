"""Named host spans of the program, on the device trace's clock.

:func:`span` times a stretch of host code twice over: as a
``jax.profiler.TraceAnnotation`` of the same name, which lands in the
profiler's host plane when a trace is being recorded (so an idle gap of
the device is named by the host step it waited on), and as
:func:`~repro.obs.clock.walltime` seconds added into a dict the caller
returns (``SweepResult.info["spans"]``).  An annotation costs about half
a microsecond when no profiler runs; neither half waits for the device.
"""
from __future__ import annotations

from contextlib import contextmanager
from typing import Dict, Iterator

from .clock import walltime


@contextmanager
def span(name: str, into: Dict[str, float]) -> Iterator[None]:
    """Annotate the ``with`` body as ``name`` and add its wall seconds
    to ``into[name]`` (a span entered twice sums)."""
    # imported here so that importing repro.obs does not load JAX
    from jax.profiler import TraceAnnotation

    t0 = walltime()
    try:
        with TraceAnnotation(name):
            yield
    finally:
        into[name] = into.get(name, 0.0) + walltime() - t0
