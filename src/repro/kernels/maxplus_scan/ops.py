"""Dispatch wrapper for the max-plus departure scan.

Three interchangeable evaluations of ``d_i = max(a_i, d_{i-1}) + s_i``:

* ``numpy`` — the closed form ``S + cummax(a - exclusive_cumsum(s))``
  (the expression the fast simulator engine historically inlined as
  ``np.maximum.accumulate``); exact float64, zero dispatch overhead, the
  right choice for host-side per-group scans.
* ``assoc`` — ``jax.lax.associative_scan`` over max-plus affine maps
  ``x -> max(x + m, c)``; maps compose associatively as
  ``(m1,c1)∘(m2,c2) = (m1+m2, max(c1+m2, c2))``, and a *segment reset* is
  just ``m = -inf`` (the map forgets its input), so segmented scans need
  no extra machinery.  This is the backend the sweep engine jits and
  ``vmap``s over whole parameter grids.
* ``pallas`` — the kernel in ``kernel.py`` (sequential chunk grid,
  VMEM carry).  It runs only in interpret mode (``interpret=True``): the
  TPU compiler refuses it (no Pallas TPU lowering of its in-chunk
  ``cumsum``, and ``block_rows=1`` breaks the (8, 128) block rule).

``backend="auto"`` picks ``numpy`` for concrete numpy inputs and
``assoc`` for jax arrays/tracers, so the same call site works inside and
outside ``jax.jit``.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from .kernel import maxplus_depart_kernel
from .ref import maxplus_depart_ref


def _combine(e1, e2):
    m1, c1 = e1
    m2, c2 = e2
    return m1 + m2, jnp.maximum(c1 + m2, c2)


def _assoc(arrive, svc, reset, init):
    arrive = jnp.asarray(arrive)
    svc = jnp.asarray(svc, arrive.dtype)
    if reset is None:
        # closed form: two single-array associative scans (cumsum +
        # cummax) instead of one over (m, c) pairs — half the scan work.
        # Spelled as explicit associative scans: the TPU compiler lowers
        # jnp.cumsum / lax.cummax to a window reduction, which in
        # (emulated) float64 does not finish compiling at L ~ 1000
        ax = arrive.ndim - 1
        S = jax.lax.associative_scan(jnp.add, svc, axis=ax)
        z = jax.lax.associative_scan(jnp.maximum, arrive - (S - svc),
                                     axis=ax)
        if init is not None:
            x0 = jnp.asarray(init, arrive.dtype)
            z = jnp.maximum(z, x0[..., None] if x0.ndim else x0)
        return S + z
    m = jnp.where(reset, -jnp.inf, svc)
    M, C = jax.lax.associative_scan(_combine, (m, arrive + svc), axis=-1)
    if init is None:
        return C
    x0 = jnp.asarray(init, arrive.dtype)
    return jnp.maximum(C, x0[..., None] + M if x0.ndim else x0 + M)


def _numpy(arrive, svc, reset, init):
    a = np.asarray(arrive)
    s = np.asarray(svc, a.dtype)
    if reset is not None and np.asarray(reset).any():
        rs = np.broadcast_to(np.asarray(reset, bool), a.shape)
        out = np.empty_like(a)
        flat_a = a.reshape(-1, a.shape[-1])
        flat_s = s.reshape(-1, a.shape[-1])
        flat_r = rs.reshape(-1, a.shape[-1])
        flat_o = out.reshape(-1, a.shape[-1])
        for row in range(flat_a.shape[0]):
            starts = np.flatnonzero(flat_r[row]).tolist()
            bounds = [0] + [b for b in starts if b > 0] + [a.shape[-1]]
            x0 = init
            for lo, hi in zip(bounds[:-1], bounds[1:]):
                flat_o[row, lo:hi] = _numpy_seg(
                    flat_a[row, lo:hi], flat_s[row, lo:hi],
                    None if flat_r[row, lo] else x0)
                x0 = None  # later segments start from an idle leader
        return out
    return _numpy_seg(a, s, init)


def _numpy_seg(a, s, init):
    S = np.cumsum(s, axis=-1)
    cm = np.maximum.accumulate(a - (S - s), axis=-1)
    if init is not None:
        cm = np.maximum(cm, np.asarray(init)[..., None]
                        if np.ndim(init) else init)
    return S + cm


def maxplus_depart(arrive, svc, reset=None, *, init=None,
                   backend: str = "auto", chunk: int = 256,
                   block_rows: int = 1,
                   interpret: bool = False):
    """Departure times for the leader-stage recurrence.  (..., L) in,
    (..., L) out; see module docstring for the backends.

    ``block_rows`` (pallas only) blocks the batched row axis of the
    kernel grid: ``block_rows`` rows share one grid step, so a sweep's
    whole (config, group) row stack scans in one ``pallas_call`` with
    the VPU lanes filled even for short rows.  ``init`` seeds each row's
    carry (idle leader = -inf); supported on every backend.
    """
    if backend == "auto":
        concrete = isinstance(arrive, np.ndarray) or not isinstance(
            arrive, jax.Array)
        backend = "numpy" if concrete else "assoc"
    if backend == "numpy":
        return _numpy(arrive, svc, reset, init)
    if backend == "assoc":
        return _assoc(arrive, svc, reset, init)
    if backend == "ref":
        return maxplus_depart_ref(arrive, svc, reset=reset, init=init)
    if backend != "pallas":
        raise ValueError(f"unknown backend {backend!r}")
    if reset is not None:
        raise NotImplementedError(
            "the pallas backend segments by row; pre-split sequences into "
            "rows instead of passing reset")
    a = jnp.asarray(arrive)
    s = jnp.asarray(svc, a.dtype)
    shape = a.shape
    a2 = a.reshape(-1, shape[-1]) if a.ndim != 2 else a
    s2 = s.reshape(-1, shape[-1]) if s.ndim != 2 else s
    L = a2.shape[-1]
    chunk = min(chunk, max(8, L))
    pad = (-L) % chunk
    if pad:
        # padding rides at the end of each row: with arrive=0, svc=0 the
        # recurrence just carries the last departure forward
        a2 = jnp.pad(a2, ((0, 0), (0, pad)))
        s2 = jnp.pad(s2, ((0, 0), (0, pad)))
    x0 = None
    if init is not None:
        x0 = jnp.broadcast_to(jnp.asarray(init, a.dtype),
                              shape[:-1]).reshape(-1)
    R = a2.shape[0]
    block_rows = max(1, min(block_rows, R))
    rpad = (-R) % block_rows
    if rpad:
        # rows are independent, so trailing zero rows are inert
        a2 = jnp.pad(a2, ((0, rpad), (0, 0)))
        s2 = jnp.pad(s2, ((0, rpad), (0, 0)))
        if x0 is not None:
            x0 = jnp.pad(x0, (0, rpad), constant_values=-jnp.inf)
    out = maxplus_depart_kernel(a2, s2, init=x0, chunk=chunk,
                                block_rows=block_rows, interpret=interpret)
    if rpad:
        out = out[:R]
    if pad:
        out = out[:, :L]
    return out.reshape(shape)
