"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

The TPU planes (``/device:TPU:<i>``) hold one event per executed HLO
instruction on their ``XLA Ops`` line, named by the instruction's text
(``%fusion.725 = (...) fusion(...), kind=kCustom, ...``); control flow
(``while``, ``conditional``, ``call``) appears as an event around the
events of its body.  The events carry no name stack, so ops are
attributed by their HLO opcode and by how often they run:

* busy: per chip, the union of the intervals of its leaf ops (control
  flow events left out) inside the window;
* window: from the start of the first to the end of the last of the
  harness's ``sweep`` annotations on the host;
* groups: device seconds per layer of the closed fixed point, averaged
  over the chips: ``scan`` is every op that runs at least
  :data:`INNER_LOOP` times per program execution, which only the steps
  of the departure scan nested inside the round loop do; ``sort`` is
  every ``sort`` instruction; everything else is ``other``;
* runs: on the first chip, per program execution, the most runs of any
  ``scan`` op and the runs of all ``sort`` ops together, which the
  harness logs beside ``groups`` (the readers take a stage's seconds by
  the program's scope names, :mod:`scope_reduce`);
* breakdown: the leaf ops that took most device time, and the longest
  idle gaps on chip 0, each named by the innermost host event that
  covers its middle.

A device plane that reports dropped events is refused: its busy time
and groups would be short.
"""
from __future__ import annotations

import glob
import re
from collections import Counter
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "sweep"
CONTROL_FLOW = ("while", "conditional", "call")
# a round loop runs at most a few hundred rounds; a scan step inside it
# runs once per step per round, thousands of times per execution
INNER_LOOP = 256
TOP = 10


class TraceError(ValueError):
    """A trace that cannot give the numbers."""


def hlo_opcode(text: str) -> str:
    """Opcode of an HLO instruction's text (``%x = shape opcode(...)``)."""
    i = text.find(" = ")
    if i < 0:
        return ""
    rest = text[i + 3:]
    if rest.startswith("("):           # tuple shape: skip to its close
        depth = 0
        for j, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[j + 1:]
                break
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    m = re.match(r"\s*([a-z][a-z0-9\-]*)\(", rest)
    return m.group(1) if m else ""


def union_length(intervals: Sequence[Tuple[float, float]]) -> float:
    """Total length covered by ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """Stretches of ``[lo, hi]`` that no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def layer_of(opcode: str, runs_per_execution: float) -> str:
    if runs_per_execution >= INNER_LOOP:
        return "scan"
    if opcode == "sort":
        return "sort"
    return "other"


def _device_events(plane) -> Tuple[list, int, int]:
    """Leaf op events ``(start_s, end_s, name)``, program executions and
    dropped events of one device plane."""
    dropped = int(dict(plane.stats or []).get("dropped_traces", 0) or 0)
    ops, modules, opcode = [], 0, {}
    for line in plane.lines:
        if line.name == OPS_LINE:
            for ev in line.events:
                name = ev.name
                if name not in opcode:
                    opcode[name] = hlo_opcode(name)
                if opcode[name] not in CONTROL_FLOW:
                    ops.append((ev.start_ns * 1e-9, ev.end_ns * 1e-9, name))
        elif line.name == MODULES_LINE:
            modules += sum(1 for _ in line.events)
    return ops, modules, dropped


def reduce_profile(pd, n_chips: int) -> dict:
    """Numbers of one trace (a ``jax.profiler.ProfileData``)."""
    chips: Dict[int, tuple] = {}
    host: List[Tuple[float, float, str]] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chips[int(m.group(1))] = _device_events(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((ev.start_ns * 1e-9, ev.end_ns * 1e-9, ev.name)
                            for ev in line.events)
    used = sorted(chips)[:n_chips]
    if len(used) < n_chips:
        raise TraceError(f"trace holds {len(used)} chip(s), {n_chips} "
                         "expected")
    for c in used:
        if chips[c][2]:
            raise TraceError(f"chip {c} dropped {chips[c][2]} trace events")
    spans = [(s, e) for s, e, name in host if name == WINDOW_SPAN]
    if not spans:
        raise TraceError(f"no {WINDOW_SPAN!r} span in the trace")
    lo, hi = min(s for s, _ in spans), max(e for _, e in spans)

    busy, groups, by_op = [], {}, {}
    for c in used:
        ops, modules, _ = chips[c]
        inside = [(max(s, lo), min(e, hi), name) for s, e, name in ops
                  if e > lo and s < hi]
        busy.append(union_length([(s, e) for s, e, _ in inside]))
        per_exec = {name: n / max(1, modules) for name, n in
                    Counter(name for _, _, name in inside).items()}
        layer = {name: layer_of(hlo_opcode(name), r)
                 for name, r in per_exec.items()}
        if c == used[0]:
            runs = dict(
                scan=max((r for name, r in per_exec.items()
                          if layer[name] == "scan"), default=0.0),
                sort=sum(r for name, r in per_exec.items()
                         if layer[name] == "sort"))
        for s, e, name in inside:
            g = layer[name]
            groups[g] = groups.get(g, 0.0) + (e - s) / len(used)
            by_op[name] = by_op.get(name, 0.0) + (e - s) / len(used)
    idle = gaps([(s, e) for s, e, _ in chips[used[0]][0]], lo, hi)
    named = []
    for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:TOP]:
        mid = 0.5 * (s + e)
        cover = [(he - hs, name) for hs, he, name in host
                 if hs <= mid <= he]
        name = min(cover)[1] if cover else "no host event"
        if name == WINDOW_SPAN:
            name = "run_sweep host code"
        named.append([name, e - s])
    top_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]
    return dict(busy_s=busy, window_s=hi - lo, groups=groups, runs=runs,
                breakdown=dict(device_ops=[[k, v] for k, v in top_ops],
                               idle_gaps=named))


def newest_trace(trace_dir: Path) -> str:
    files = sorted(glob.glob(str(Path(trace_dir) / "plugins" / "profile"
                                 / "*" / "*.xplane.pb")))
    if not files:
        raise TraceError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))


def dropped(pd) -> int:
    """Op events the device planes of a trace report as dropped."""
    return sum(int(dict(p.stats or []).get("dropped_traces", 0) or 0)
               for p in pd.planes if DEVICE_PLANE.match(p.name))
