"""Closed-loop sweep equivalence: ``run_sweep(..., loop="closed")``'s
batched fixed-point program must reproduce independent
``SimEdgeKV(engine="fast").run_closed_loop`` runs per grid point to
<= 1e-9, in both LRU regimes, on every scan backend, and bit-identically
when the point axis is sharded over multiple devices."""
import numpy as np
import pytest
import jax

from repro.sim import SimEdgeKV
from repro.sim.cluster import ServiceParams
from repro.sim.sweep import SweepPoint, closed_grid, run_sweep

from test_sweep import (TOL, assert_point_matches, measured_speedup,
                        strict_perf_floor)


def closed_reference(p: SweepPoint, seed: int = 0,
                     setting: str = "edge",
                     service: ServiceParams = None) -> SimEdgeKV:
    sim = SimEdgeKV(setting=setting, seed=seed, service=service,
                    group_sizes=(p.group_size,) * p.groups, engine="fast")
    sim.run_closed_loop(threads_per_client=p.threads,
                        ops_per_client=p.ops,
                        workload_kw=dict(p_global=p.p_global,
                                         distribution=p.distribution,
                                         n_records=p.n_records),
                        seed_offset=seed)
    return sim


def test_closed_sweep_matches_fast_engine_per_point():
    """p_global x contention x distribution coverage, one batched call."""
    pts = [SweepPoint(p_global=pg, groups=g, n_records=nr,
                      distribution=dist, threads=t, ops=o)
           for pg, g, nr, dist, t, o in [
               (0.0, 3, 10_000, "uniform", 8, 64),
               (0.25, 3, 2_500, "zipfian", 8, 64),
               (0.5, 4, 10_000, "zipfian", 6, 48),
               (0.75, 3, 2_500, "latest", 8, 64),
               (1.0, 5, 10_000, "uniform", 4, 40),
           ]]
    res = run_sweep(pts, loop="closed", seed=0)
    assert len(res) == len(pts)
    for i, p in enumerate(pts):
        assert_point_matches(res.row(i), closed_reference(p))


def test_closed_sweep_mean_hops_and_ops_columns():
    p = SweepPoint(p_global=1.0, groups=5, threads=4, ops=40)
    res = run_sweep([p], loop="closed", seed=2)
    sim = closed_reference(p, seed=2)
    hops = sim.records.columns()["hops"]
    assert abs(res.columns["mean_hops"][0] - hops.mean()) <= TOL
    assert int(res.columns["ops"][0]) == len(sim.records)


def test_closed_sweep_cloud_setting_and_seed_offset():
    p = SweepPoint(p_global=0.5, groups=3, threads=8, ops=64)
    res = run_sweep([p], loop="closed", setting="cloud", seed=7)
    assert_point_matches(res.row(0),
                         closed_reference(p, seed=7, setting="cloud"))


def test_closed_sweep_eviction_regime_matches_lru_replay():
    """A page cache smaller than the working set forces the host-side
    fixed point with the exact (Fenwick) LRU replay — still <= 1e-9."""
    svc = ServiceParams(page_cache_keys=16)
    pts = [SweepPoint(p_global=0.5, groups=3, threads=8, ops=64),
           SweepPoint(p_global=0.0, groups=3, threads=8, ops=64,
                      distribution="zipfian")]
    res = run_sweep(pts, loop="closed", seed=0, service=svc)
    for i, p in enumerate(pts):
        assert_point_matches(res.row(i), closed_reference(p, service=svc))


def test_closed_sweep_pallas_backend_matches_assoc():
    """The two closed-form scan variants (associative scan vs the
    batched-row Pallas kernel) must agree through the whole fixed point.
    A violation beyond float-order noise would mean a near-tie queue
    order flipped between backends — percent-level drift, not ulps — so
    this doubles as an order-stability check."""
    pts = closed_grid(threads=4, ops=32)[:4]
    a = run_sweep(pts, loop="closed", seed=0, scan_backend="assoc")
    b = run_sweep(pts, loop="closed", seed=0, scan_backend="pallas",
                  interpret=True)
    for k in a.columns:
        np.testing.assert_allclose(a.columns[k], b.columns[k],
                                   rtol=1e-9)
    # and the exact sequential default stays within float-order noise of
    # the closed-form variants on this tie-free grid
    c = run_sweep(pts, loop="closed", seed=0)
    for k in c.columns:
        np.testing.assert_allclose(a.columns[k], c.columns[k],
                                   rtol=1e-9)


def test_closed_sweep_deterministic_and_seed_sensitive():
    p = SweepPoint(p_global=0.5, groups=3, threads=8, ops=64)
    a = run_sweep([p], loop="closed", seed=0)
    b = run_sweep([p], loop="closed", seed=0)
    c = run_sweep([p], loop="closed", seed=3)
    assert a.columns["mean_latency"][0] == b.columns["mean_latency"][0]
    assert a.columns["mean_latency"][0] != c.columns["mean_latency"][0]


def test_closed_grid_shape():
    grid = closed_grid()
    assert len(grid) == 16
    assert len({(p.p_global, p.n_records, p.groups) for p in grid}) == 16


def test_closed_sweep_rejects_bad_args():
    with pytest.raises(ValueError):
        run_sweep([SweepPoint()], devices=2)          # open loop
    with pytest.raises(ValueError):
        run_sweep([SweepPoint()], loop="closed", devices=0)
    with pytest.raises(ValueError):
        run_sweep([SweepPoint(threads=0)], loop="closed")
    with pytest.raises(ValueError):
        run_sweep([SweepPoint()], loop="think")
    with pytest.raises(ValueError):
        run_sweep([SweepPoint(threads=4, ops=32)], loop="closed",
                  devices=1 + jax.local_device_count())


def test_closed_sweep_nonconvergence_raises():
    p = SweepPoint(p_global=0.0, groups=3, threads=4, ops=64)
    with pytest.raises(RuntimeError):
        run_sweep([p], loop="closed", max_rounds=2)


def test_fig_scale_sweep_engine_matches_fast():
    from repro.sim.experiments import fig_scale
    kw = dict(groups=3, clients_per_group=8, ops_per_client=64, seed=1)
    a = fig_scale(engine="fast", **kw)[0]
    b = fig_scale(engine="sweep", **kw)[0]
    for k in a:
        if k in ("engine", "walltime_s"):
            continue
        want = a[k]
        assert abs(b[k] - want) <= TOL * max(1.0, abs(want)), (k, b[k],
                                                              want)


# --------------------------------------------------- multi-device sharding
needs_devices = pytest.mark.skipif(
    jax.local_device_count() < 2,
    reason="needs >1 jax device (XLA_FLAGS="
           "--xla_force_host_platform_device_count=N); the CI fast tier "
           "runs a dedicated 8-device leg for these")


@needs_devices
def test_sharded_closed_sweep_bit_identical_to_single_device():
    """Sharding the point axis must not change a single bit: the round
    map is idempotent past its fixed point, so shards that converge at
    different rounds still produce the same completions."""
    pts = closed_grid(threads=4, ops=32)
    r1 = run_sweep(pts, loop="closed", seed=0, devices=1)
    rd = run_sweep(pts, loop="closed", seed=0,
                   devices=jax.local_device_count())
    for k in r1.columns:
        assert np.array_equal(np.asarray(r1.columns[k]),
                              np.asarray(rd.columns[k]),
                              equal_nan=True), k


@needs_devices
def test_sharded_closed_sweep_uneven_points_and_device_clamp():
    """Point counts that don't divide the device count (ragged stripes,
    padded blocks) and devices > points (clamped) both stay exact."""
    pts = closed_grid(threads=4, ops=32)[:5] + [
        SweepPoint(p_global=0.5, groups=4, threads=6, ops=48)]
    r1 = run_sweep(pts, loop="closed", seed=0, devices=1)
    rd = run_sweep(pts, loop="closed", seed=0,
                   devices=jax.local_device_count())
    for k in r1.columns:
        assert np.array_equal(np.asarray(r1.columns[k]),
                              np.asarray(rd.columns[k]),
                              equal_nan=True), k
    one = [pts[0]]
    ra = run_sweep(one, loop="closed", seed=0, devices=1)
    rb = run_sweep(one, loop="closed", seed=0,
                   devices=jax.local_device_count())  # clamps to 1 point
    for k in ra.columns:
        assert np.array_equal(np.asarray(ra.columns[k]),
                              np.asarray(rb.columns[k]),
                              equal_nan=True), k


@pytest.mark.slow
def test_acceptance_closed_sweep_speedup():
    """Acceptance: >=3x wall clock over looping the numpy fast engine
    across the 16-point closed grid in the many-clients regime the
    batched path exists for (500 threads/group, short per-thread
    chains, so the fixed point converges in a handful of rounds).
    Median of 3 interleaved reps after warmup; the strict floor is
    nightly-only, where the runner forces multiple host devices and the
    point axis shards across them (see ci.yml)."""
    import time

    grid = closed_grid(threads=500, ops=1000)
    dev = min(4, jax.local_device_count())

    def sweep_once():
        t0 = time.perf_counter()
        run_sweep(grid, loop="closed", seed=0, devices=dev)
        return time.perf_counter() - t0

    def loop_once():
        t0 = time.perf_counter()
        for p in grid:
            sim = closed_reference(p)
            (sim.mean_latency(), sim.mean_latency(kind="update"),
             sim.throughput(), sim.tail_latency(95), sim.tail_latency(99))
        return time.perf_counter() - t0

    ratio, loops, sweeps = measured_speedup(loop_once, sweep_once)
    print(f"closed sweep speedup: {ratio:.1f}x "  # lint: ignore[EDK004] -- walltime reporting
          f"(loops={loops} sweeps={sweeps})")
    assert ratio > 0.75, (ratio, loops, sweeps)  # gross-regression tripwire
    if strict_perf_floor():
        assert ratio >= 3.0, (ratio, loops, sweeps)


@pytest.mark.slow
def test_acceptance_closed_grid_matches_fast_engine():
    """Acceptance: the full 16-point closed grid, every point matching
    the fast engine within 1e-9."""
    grid = closed_grid(threads=16, ops=128)
    res = run_sweep(grid, loop="closed", seed=0)
    for i, p in enumerate(grid):
        assert_point_matches(res.row(i), closed_reference(p))
