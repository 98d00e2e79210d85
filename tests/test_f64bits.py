"""Exact IEEE binary64 addition on int64 bit patterns (the closed-loop
sweep's time arithmetic): bit-identical to float64 ``+`` on numpy and
under jit, across magnitudes, rounding ties, zero, subnormals and
overflow to +inf."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.sim import f64bits as fb

N = 200_000


def _cases():
    rng = np.random.default_rng(11)
    a1 = np.exp(rng.uniform(-40, 40, N))
    b1 = np.exp(rng.uniform(-40, 40, N))
    a2 = rng.uniform(0, 100, N)            # times and delays of a sweep
    b2 = rng.uniform(0, 1e-2, N)
    a3 = rng.uniform(1, 2, N)
    ulp = np.spacing(a3)
    pats = rng.integers(0, fb.INF, (2, N), dtype=np.int64)
    sp = np.array([0.0, np.inf, 5e-324, 1.0, 1e308,
                   np.finfo(np.float64).max])
    sa, sb = np.meshgrid(sp, sp)
    return {
        "wide": (a1, b1), "sim": (a2, b2),
        "half_ulp_ties": (a3, ulp / 2), "one_and_half_ulp": (a3, 1.5 * ulp),
        "random_patterns": (fb.from_bits(pats[0]), fb.from_bits(pats[1])),
        "specials": (sa.ravel(), sb.ravel()),
    }


CASES = _cases()


@pytest.mark.parametrize("name", sorted(CASES))
@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_add_matches_ieee_float64(name, backend):
    a, b = CASES[name]
    with np.errstate(over="ignore"):
        want = fb.to_bits(a + b)
    ab, bb = fb.to_bits(a), fb.to_bits(b)
    if backend == "numpy":
        got = fb.add(np, ab, bb)
    else:
        with jax.enable_x64(True):
            got = np.asarray(jax.jit(lambda x, y: fb.add(jnp, x, y))(ab, bb))
    assert np.array_equal(got, want)


def test_bit_patterns_order_like_the_times():
    rng = np.random.default_rng(3)
    x = np.concatenate([rng.uniform(0, 50, 1000), [0.0, np.inf]])
    order = np.argsort(fb.to_bits(x), kind="stable")
    assert np.array_equal(x[order], np.sort(x))
    assert fb.NEG_INF < fb.to_bits(np.array([0.0]))[0]
    assert fb.to_bits(np.array([np.inf]))[0] == fb.INF
    assert np.array_equal(fb.from_bits(fb.to_bits(x)), x)
