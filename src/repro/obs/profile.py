"""Compile-time / trace-count / device-memory profiling for kernels.

Thin wrappers over JAX's AOT API (``jit(...).lower(...).compile()``)
plus a retrace counter, so the benchmark harness can report *where*
sweep walltime goes: Python tracing, XLA compilation, or execution.
Everything degrades gracefully off-TPU — ``cost_analysis`` /
``memory_analysis`` fields that a backend does not provide are simply
absent from the result dict.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Tuple

from .clock import walltime


class TraceCounter:
    """Wrap ``fn`` so each *Python trace* (i.e. each time jit actually
    re-traces, not each call) bumps ``.traces``.  Jit the wrapper:
    cached executions skip the Python body entirely."""

    def __init__(self, fn: Callable) -> None:
        self.fn = fn
        self.traces = 0
        functools.update_wrapper(self, fn)

    def __call__(self, *args: Any, **kw: Any) -> Any:
        self.traces += 1
        return self.fn(*args, **kw)


def profile_compile(fn: Callable, *args: Any,
                    static_argnames: Tuple[str, ...] = (),
                    **kw: Any) -> Dict[str, float]:
    """AOT-compile ``fn(*args, **kw)`` and report stage timings plus
    whatever cost/memory analysis the backend exposes.

    Returns keys: ``trace_lower_s``, ``compile_s``, ``traces`` and —
    backend permitting — ``flops``, ``bytes_accessed``,
    ``peak_bytes``, ``argument_bytes``, ``output_bytes``,
    ``generated_code_bytes``.
    """
    import jax

    counter = TraceCounter(fn)
    jitted = jax.jit(counter, static_argnames=static_argnames)
    t0 = walltime()
    lowered = jitted.lower(*args, **kw)
    t1 = walltime()
    compiled = lowered.compile()
    t2 = walltime()
    out: Dict[str, float] = {
        "trace_lower_s": t1 - t0,
        "compile_s": t2 - t1,
        "traces": float(counter.traces),
    }
    cost = _maybe(compiled.cost_analysis)
    if cost:
        for src, dst in (("flops", "flops"),
                         ("bytes accessed", "bytes_accessed")):
            if src in cost:
                out[dst] = float(cost[src])
    mem = _maybe(compiled.memory_analysis)
    if mem is not None:
        for attr, dst in (
                ("temp_size_in_bytes", "peak_bytes"),
                ("argument_size_in_bytes", "argument_bytes"),
                ("output_size_in_bytes", "output_bytes"),
                ("generated_code_size_in_bytes", "generated_code_bytes")):
            v = getattr(mem, attr, None)
            if v is not None:
                out[dst] = float(v)
    return out


def profile_maxplus(n: int = 4096, rows: int = 8,
                    backend: str = "assoc",
                    interpret: bool = False) -> Dict[str, float]:
    """Profile one ``maxplus_depart`` configuration (the sweep engine's
    hot kernel) at a representative ``(rows, n)`` scan shape — under
    ``jax.enable_x64``, the regime every sweep call traces in."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.maxplus_scan import maxplus_depart

    def run(a, s):
        return maxplus_depart(a, s, backend=backend, interpret=interpret)

    with jax.enable_x64(True):
        arrive = jnp.linspace(0.0, 1.0, rows * n,
                              dtype=jnp.float64).reshape(rows, n)
        svc = jnp.full((rows, n), 1e-4, dtype=jnp.float64)
        out = profile_compile(run, arrive, svc)
    out["rows"], out["n"] = float(rows), float(n)
    return out


def _maybe(fn: Callable) -> Any:
    try:
        return fn()
    except Exception:      # backend without analysis support
        return None
