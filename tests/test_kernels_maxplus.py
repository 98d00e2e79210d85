"""maxplus_scan kernel equivalence: Pallas (interpret) vs associative
scan vs sequential ref vs the numpy ``maximum.accumulate`` oracle, across
dtypes, lengths, resets, and init values."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.maxplus_scan import (maxplus_depart,
                                        maxplus_depart_kernel,
                                        maxplus_depart_ref)


def numpy_oracle(arrive, svc):
    """The expression the fast engine historically inlined."""
    s = np.cumsum(svc, axis=-1)
    return s + np.maximum.accumulate(arrive - (s - svc), axis=-1)


def sequential_oracle(arrive, svc, reset=None, init=None):
    out = np.empty_like(arrive)
    flat_a = arrive.reshape(-1, arrive.shape[-1])
    flat_s = svc.reshape(-1, arrive.shape[-1])
    flat_r = (None if reset is None
              else reset.reshape(-1, arrive.shape[-1]))
    for r in range(flat_a.shape[0]):
        d = -np.inf if init is None else float(np.asarray(init).reshape(-1)[
            r % np.asarray(init).size])
        for i in range(arrive.shape[-1]):
            if flat_r is not None and flat_r[r, i]:
                d = -np.inf
            d = max(flat_a[r, i], d) + flat_s[r, i]
            out.reshape(-1, arrive.shape[-1])[r, i] = d
    return out


def make(shape, seed=0, dtype=np.float64):
    rng = np.random.default_rng(seed)
    arrive = np.sort(rng.random(shape), axis=-1).astype(dtype) * 10
    svc = (rng.random(shape) * 0.3).astype(dtype)
    return arrive, svc


@pytest.mark.parametrize("L", [1, 7, 128, 1000])
def test_numpy_backend_is_bit_exact_vs_inline_oracle(L):
    a, s = make((3, L))
    got = maxplus_depart(a, s, backend="numpy")
    assert np.array_equal(got, numpy_oracle(a, s))


@pytest.mark.parametrize("backend", ["assoc", "ref", "pallas"])
@pytest.mark.parametrize("L,chunk", [(8, 8), (96, 16), (250, 64)])
def test_jax_backends_match_numpy_oracle_f64(backend, L, chunk):
    a, s = make((4, L), seed=L)
    with jax.enable_x64(True):
        got = np.asarray(maxplus_depart(jnp.asarray(a), jnp.asarray(s),
                                        backend=backend, chunk=chunk,
                                        interpret=True))
    np.testing.assert_allclose(got, numpy_oracle(a, s), rtol=1e-12,
                               atol=1e-12)


@pytest.mark.parametrize("backend", ["assoc", "pallas"])
def test_float32_tolerance(backend):
    a, s = make((2, 64), seed=5, dtype=np.float32)
    got = np.asarray(maxplus_depart(jnp.asarray(a), jnp.asarray(s),
                                    backend=backend, chunk=16,
                                    interpret=True))
    assert got.dtype == np.float32
    np.testing.assert_allclose(got, numpy_oracle(a, s), rtol=1e-5,
                               atol=1e-5)


def test_auto_backend_dispatch():
    a, s = make((2, 32))
    assert isinstance(maxplus_depart(a, s), np.ndarray)
    out = maxplus_depart(jnp.asarray(a), jnp.asarray(s))
    assert isinstance(out, jax.Array)


@pytest.mark.parametrize("backend", ["numpy", "assoc", "ref"])
def test_segment_resets(backend):
    a, s = make((3, 40), seed=9)
    reset = np.zeros((3, 40), bool)
    reset[:, 13] = True
    reset[1, 0] = True
    reset[2, 39] = True
    want = sequential_oracle(a, s, reset=reset)
    with jax.enable_x64(True):
        got = np.asarray(maxplus_depart(
            jnp.asarray(a) if backend != "numpy" else a,
            jnp.asarray(s) if backend != "numpy" else s,
            reset=jnp.asarray(reset) if backend != "numpy" else reset,
            backend=backend))
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("backend", ["numpy", "assoc", "ref"])
def test_init_busy_leader(backend):
    a, s = make((4, 25), seed=3)
    init = np.array([0.0, 5.0, 20.0, 2.5])
    want = sequential_oracle(a, s, init=init)
    with jax.enable_x64(True):
        got = np.asarray(maxplus_depart(
            jnp.asarray(a) if backend != "numpy" else a,
            jnp.asarray(s) if backend != "numpy" else s,
            init=jnp.asarray(init) if backend != "numpy" else init,
            backend=backend))
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-12)


def test_pallas_rows_are_independent():
    """The VMEM carry must reset per row: permuting rows permutes
    departures."""
    a, s = make((5, 64), seed=11)
    with jax.enable_x64(True):
        out = np.asarray(maxplus_depart(jnp.asarray(a), jnp.asarray(s),
                                        backend="pallas", chunk=16,
                                        interpret=True))
        perm = np.array([3, 1, 4, 0, 2])
        out_p = np.asarray(maxplus_depart(jnp.asarray(a[perm]),
                                          jnp.asarray(s[perm]),
                                          backend="pallas", chunk=16,
                                          interpret=True))
    np.testing.assert_allclose(out_p, out[perm], rtol=1e-12)


def test_pallas_pad_to_chunk():
    """Non-multiple lengths are padded and sliced back."""
    a, s = make((2, 37), seed=13)
    with jax.enable_x64(True):
        got = np.asarray(maxplus_depart(jnp.asarray(a), jnp.asarray(s),
                                        backend="pallas", chunk=16,
                                        interpret=True))
    np.testing.assert_allclose(got, numpy_oracle(a, s), rtol=1e-12)


def test_kernel_direct_multiple_of_chunk():
    a, s = make((3, 32), seed=17, dtype=np.float32)
    got = np.asarray(maxplus_depart_kernel(jnp.asarray(a), jnp.asarray(s),
                                           chunk=8, interpret=True))
    np.testing.assert_allclose(got, numpy_oracle(a, s), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("block_rows", [2, 4, 8])
@pytest.mark.parametrize("R,L", [(1, 64), (5, 96), (16, 37)])
def test_pallas_batched_rows_matches_oracle(block_rows, R, L):
    """The ``block_rows`` grid axis tiles rows; results must not depend
    on the tile size, including when R is not a multiple of it."""
    a, s = make((R, L), seed=R * 100 + L)
    with jax.enable_x64(True):
        got = np.asarray(maxplus_depart(jnp.asarray(a), jnp.asarray(s),
                                        backend="pallas", chunk=16,
                                        block_rows=block_rows,
                                        interpret=True))
    np.testing.assert_allclose(got, numpy_oracle(a, s), rtol=1e-12,
                               atol=1e-12)


def test_pallas_block_rows_bitwise_vs_block_rows_one():
    """Row tiling is pure batching: each row's scan is independent, so
    block_rows must be bit-invisible, not just within tolerance."""
    a, s = make((7, 48), seed=41)
    with jax.enable_x64(True):
        one = np.asarray(maxplus_depart(jnp.asarray(a), jnp.asarray(s),
                                        backend="pallas", chunk=16,
                                        block_rows=1, interpret=True))
        many = np.asarray(maxplus_depart(jnp.asarray(a), jnp.asarray(s),
                                         backend="pallas", chunk=16,
                                         block_rows=4, interpret=True))
    assert np.array_equal(one, many)


def test_monotone_departures_and_fifo_invariant():
    """Departures are nondecreasing in op order and each op departs no
    earlier than its own arrival + service."""
    a, s = make((1, 200), seed=23)
    d = maxplus_depart(a, s)
    assert np.all(np.diff(d[0]) >= 0)
    assert np.all(d >= a + s - 1e-12)


def test_ref_rejects_nothing_on_1d():
    a, s = make((16,), seed=29)
    with jax.enable_x64(True):
        got = np.asarray(maxplus_depart_ref(a, s))
    np.testing.assert_allclose(got, numpy_oracle(a, s), rtol=1e-12)
