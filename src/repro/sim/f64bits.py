"""Exact IEEE binary64 time arithmetic on int64 bit patterns.

The TPU has no float64 unit; XLA emulates float64 there.  With its
times in emulated float64, the closed-loop fixed point missed the 1e-9
relative contract on a v5e: the 1M-client point's p95 differed from the
host's by 2.7e-7 relative.  Which emulated op diverges from IEEE
binary64 was not isolated.  Keeping each time as the int64 bit pattern
of its IEEE double and adding with integer operations only, which every
backend evaluates exactly, removed the miss on the same chip.

All times are non-negative (finite or ``+inf``).  For non-negative
doubles the integer order of the bit patterns is the numeric order, so
max, min, equality and sort work on the patterns as they are.
:data:`NEG_INF` (-1) stands below every pattern for an idle leader.
"""
from __future__ import annotations

import numpy as np

FRAC_BITS = 52
FRAC_MASK = (1 << FRAC_BITS) - 1
HIDDEN = 1 << FRAC_BITS
EXP_INF = 0x7FF
INF = EXP_INF << FRAC_BITS      # bit pattern of +inf
NEG_INF = -1                    # below every non-negative pattern


def to_bits(x) -> np.ndarray:
    """float64 values -> their int64 bit patterns (host side)."""
    return np.ascontiguousarray(x, dtype=np.float64).view(np.int64)


def from_bits(b) -> np.ndarray:
    """int64 bit patterns -> float64 values (host side)."""
    return np.ascontiguousarray(b, dtype=np.int64).view(np.float64)


def add(xp, a, b):
    """``a + b`` rounded to nearest, ties to even, exactly as IEEE
    binary64 adds two non-negative doubles; ``a``/``b`` and the result
    are bit patterns.  Pure in ``xp`` (numpy or jax.numpy)."""
    hi, lo = xp.maximum(a, b), xp.minimum(a, b)
    e_hi, e_lo = hi >> FRAC_BITS, lo >> FRAC_BITS
    # significands with the hidden bit; subnormals use exponent 1
    m_hi = (hi & FRAC_MASK) | xp.where(e_hi > 0, HIDDEN, 0)
    m_lo = (lo & FRAC_MASK) | xp.where(e_lo > 0, HIDDEN, 0)
    e_hi, e_lo = xp.maximum(e_hi, 1), xp.maximum(e_lo, 1)
    # align the smaller operand with three extra bits (guard, round,
    # sticky); everything shifted past them folds into the sticky bit
    shift = xp.minimum(e_hi - e_lo, 63)
    m_lo = m_lo << 3
    aligned = m_lo >> shift
    sticky = ((aligned << shift) != m_lo).astype(aligned.dtype)
    s = (m_hi << 3) + (aligned | sticky)
    carry = s >= (HIDDEN << 4)
    s = xp.where(carry, (s >> 1) | (s & 1), s)
    e = e_hi + carry.astype(e_hi.dtype)
    low, m = s & 7, s >> 3
    up = (low > 4) | ((low == 4) & ((m & 1) == 1))
    m = m + up.astype(m.dtype)
    over = m >= (HIDDEN << 1)            # rounded up to 2^53: exact shift
    m = xp.where(over, m >> 1, m)
    e = e + over.astype(e.dtype)
    out = (xp.where(m >= HIDDEN, e, 0) << FRAC_BITS) | (m & FRAC_MASK)
    return xp.where((e >= EXP_INF) | ((hi >> FRAC_BITS) >= EXP_INF),
                    INF, out)
