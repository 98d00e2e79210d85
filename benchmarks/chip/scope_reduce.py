"""Device seconds of the closed round's named stages in a profiler trace.

The closed fixed point runs each stage of its round under a
``jax.named_scope`` (``closed.arrival``, ``closed.order``, ``closed.lru``,
``closed.to_grid``, ``closed.depart``, ``closed.from_grid``,
``closed.completion``, ``closed.converge``; the replay of the converged
round under ``closed.replay``).  A device op's ``tf_op`` (read by
:mod:`xspace`) is its name stack, so each op belongs to the innermost
``closed.*`` component of it: a stage's seconds hold its rounds and its
share of the replay.  Ops outside every scope are ``unscoped``.

The ops are the ones :func:`trace_reduce.reduce_profile` counts as busy:
per chip, the leaf ops (control flow left out) inside the window of the
harness's ``sweep`` spans, clipped to it, averaged over the chips.  On
one core leaf ops do not overlap, so the scopes add up to busy.
"""
from __future__ import annotations

import gzip
from pathlib import Path
from typing import Dict, Tuple

import trace_reduce
import xspace

SCOPE = "closed."
UNSCOPED = "unscoped"


def scope_of(tf_op: str) -> str:
    """The innermost ``closed.*`` component of a ``tf_op`` name stack."""
    for part in reversed(tf_op.split("/")):
        if part.startswith(SCOPE):
            return part
    return UNSCOPED


def load(path) -> Tuple[object, Dict[str, Dict[str, str]]]:
    """A trace (``.xplane.pb``, or gzipped) as a ``ProfileData`` and the
    ``tf_op`` of every event metadata per plane."""
    from jax.profiler import ProfileData
    raw = Path(path).read_bytes()
    if str(path).endswith(".gz"):
        raw = gzip.decompress(raw)
    return ProfileData.from_serialized_xspace(raw), xspace.tf_ops(raw)


def reduce_scopes(pd, names: Dict[str, Dict[str, str]], n_chips: int
                  ) -> Dict[str, float]:
    """Device seconds per innermost ``closed.*`` scope, and
    ``unscoped``, averaged over the first ``n_chips`` chips."""
    chips, spans = {}, []
    for plane in pd.planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if m:
            chips[int(m.group(1))] = plane
        elif plane.name.startswith("/host:"):
            spans += [(ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                      for line in plane.lines for ev in line.events
                      if ev.name == trace_reduce.WINDOW_SPAN]
    used = sorted(chips)[:n_chips]
    if len(used) < n_chips:
        raise trace_reduce.TraceError(
            f"trace holds {len(used)} chip(s), {n_chips} expected")
    if not spans:
        raise trace_reduce.TraceError(
            f"no {trace_reduce.WINDOW_SPAN!r} span in the trace")
    lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    out: Dict[str, float] = {}
    for c in used:
        plane = chips[c]
        ops, _, _ = trace_reduce._device_events(plane)
        of = names.get(plane.name, {})
        scope: Dict[str, str] = {}
        for s, e, name in ops:
            if e <= lo or s >= hi:
                continue
            if name not in scope:
                scope[name] = scope_of(of.get(name, ""))
            k = scope[name]
            out[k] = out.get(k, 0.0) + (min(e, hi) - max(s, lo)) / len(used)
    return out


def per_sweep(run: dict, name: str):
    """Device seconds of scope ``name`` per traced sweep, or nothing
    where the trace has no such scope."""
    t = run["trace"]
    s = t.get("scopes", {}).get(name)
    if s is None or not t["sweeps"]:
        return None
    return s / t["sweeps"]
