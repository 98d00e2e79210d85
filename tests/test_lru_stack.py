"""The closed round's exact LRU stack distance
(:func:`repro.sim.sweep._lru_stack`) against the plain replay
:func:`repro.sim.vectorized.lru_hit_mask`, on key sequences laid out as
the round lays out its queues: rows of contiguous positions, each key a
(row, key) segment."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.sim import sweep
from repro.sim.vectorized import lru_hit_mask


def _hits(rows, capacity, Ls=None):
    """``(prev, hit)`` of queues ``rows`` (key arrays) laid end to end."""
    seg = np.concatenate([r * 1_000_000 + k for r, k in enumerate(rows)])
    Ls = Ls or max(len(k) for k in rows)
    fn = jax.jit(sweep._lru_stack, static_argnums=(1, 2))
    prev, hit = fn(jnp.asarray(seg, jnp.int32), capacity, Ls)
    return np.asarray(prev), np.asarray(hit)


def _plain_prev(rows):
    out, off = [], 0
    for keys in rows:
        last = {}
        for i, k in enumerate(keys.tolist()):
            out.append(last.get(k, -1))
            last[k] = off + i
        off += len(keys)
    return np.asarray(out)


@pytest.mark.parametrize("seed", range(8))
@pytest.mark.parametrize("capacity", [1, 2, 5, 13])
def test_stack_hits_equal_the_lru_replay(seed, capacity):
    rng = np.random.default_rng(seed)
    rows = [rng.integers(0, int(rng.integers(1, 40)),
                         size=int(rng.integers(1, 700)))
            for _ in range(int(rng.integers(1, 5)))]
    prev, hit = _hits(rows, capacity)
    assert np.array_equal(prev, _plain_prev(rows))
    want = np.concatenate([lru_hit_mask(k, capacity) for k in rows])
    assert np.array_equal(hit, want)


@pytest.mark.parametrize("capacity", [1, 3, 8])
def test_ties_at_capacity_hit_and_one_more_misses(capacity):
    """A key reused after ``capacity - 1`` other distinct keys (stack
    distance = capacity) hits; after ``capacity`` of them it misses."""
    at = np.r_[0, np.arange(1, capacity), 0]
    over = np.r_[0, np.arange(1, capacity + 1), 0]
    _, hit = _hits([at, over], capacity)
    assert hit[len(at) - 1] and not hit[-1]
    assert np.array_equal(hit, np.concatenate(
        [lru_hit_mask(at, capacity), lru_hit_mask(over, capacity)]))


def test_rows_longer_than_a_block_and_pad_positions():
    """Queues longer than one block of queries (the count's window
    reaches back over several blocks), a longest queue ``Ls`` above
    every row's length, and a trailing run of pad positions that share
    one segment: real positions keep their exact hits."""
    rng = np.random.default_rng(7)
    rows = [rng.integers(0, 300, size=n) for n in (1500, 40, 1100)]
    pads = np.zeros(37, np.int64)
    prev, hit = _hits(rows + [pads], 200, Ls=1600)
    n = sum(len(k) for k in rows)
    want = np.concatenate([lru_hit_mask(k, 200) for k in rows])
    assert np.array_equal(hit[:n], want)
    assert np.array_equal(prev[:n], _plain_prev(rows))
    assert (~want & (prev[:n] >= 0)).any()       # some capacity misses
