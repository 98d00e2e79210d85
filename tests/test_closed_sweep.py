"""Closed-loop sweep equivalence: ``run_sweep(..., loop="closed")``'s
batched fixed-point program must reproduce independent
``SimEdgeKV(engine="fast").run_closed_loop`` runs per grid point to
<= 1e-9, in both LRU regimes, on every scan backend, and bit-identically
when the point axis is sharded over multiple devices."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest
import jax

from repro.sim import SimEdgeKV, sweep
from repro.sim.cluster import ServiceParams
from repro.sim.network import SETTINGS
from repro.sim.sweep import SweepPoint, closed_grid, run_sweep
from repro.sim.vectorized import _DelayModel

from test_sweep import (TOL, assert_point_matches, measured_speedup,
                        strict_perf_floor)
from test_sweep_names import CONTENDED, _block, closed_rounds_host

SRC = Path(__file__).resolve().parents[1] / "src"


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


def test_closed_pad_src_inverts_dest():
    """The grid fill's gather index ``src`` is the exact inverse of
    ``dest`` on the slots real ops cover; every other slot reads out of
    bounds, and no pad position enters the grid."""
    built, _, aux, static, _ = _block(CONTENDED)
    R, Ls = static[-2:]
    dest, src, row = aux["dest"], aux["src"], aux["row"]
    n_max, n_real = row.shape[0], sum(b["n"] for b in built)
    assert n_max > n_real                                  # pad ops
    assert len(set(np.bincount(row[row < R]))) > 1         # ragged rows
    assert src.shape == (R * Ls,) and src.dtype == np.int32
    inb = dest < R * Ls
    assert np.array_equal(np.flatnonzero(inb), np.arange(n_real))
    assert np.array_equal(src[dest[inb]], np.flatnonzero(inb))
    uncovered = np.ones(R * Ls, bool)
    uncovered[dest[inb]] = False
    assert np.all(src[uncovered] >= n_max)
    assert not np.isin(np.arange(n_real, n_max), src).any()
    # the gather fills the grid exactly as the scatter through dest did
    vals = np.random.default_rng(0).random(n_max)
    scattered = np.full(R * Ls, np.inf)
    scattered[dest[inb]] = vals[inb]
    gathered = np.where(src < n_max, vals[np.minimum(src, n_max - 1)],
                        np.inf)
    assert np.array_equal(gathered, scattered)


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
    """A page cache smaller than the working set runs on the device
    path, with the exact LRU stack distance in every round — still
    <= 1e-9 against the fast engine's LRU replay."""
    svc = ServiceParams(page_cache_keys=16)
    pts = [SweepPoint(p_global=0.5, groups=3, threads=8, ops=64),
           SweepPoint(p_global=0.0, groups=3, threads=8, ops=64,
                      distribution="zipfian")]
    res = run_sweep(pts, loop="closed", seed=0, service=svc)
    assert res.info["path"] == "device"
    assert res.info["capacity_misses"] > 0
    for i, p in enumerate(pts):
        assert_point_matches(res.row(i), closed_reference(p, service=svc))


def _stack_distances(keys: np.ndarray) -> list:
    """Stack distance (distinct keys since the key's last use, itself
    counted) of every op that reuses a key, plainly."""
    out, last = [], {}
    for i, k in enumerate(keys.tolist()):
        if k in last:
            out.append(len(set(keys[last[k] + 1:i].tolist())) + 1)
        last[k] = i
    return out


def test_closed_sweep_eviction_ties_at_capacity():
    """A small keyspace and a cache of 12 keys leave converged ops at
    stack distance exactly 12 (hits) and 13 (misses); every column
    still matches the fast engine."""
    cap = 12
    svc = ServiceParams(page_cache_keys=cap)
    pts = [SweepPoint(p_global=0.5, groups=3, threads=8, ops=96,
                      n_records=64),
           SweepPoint(p_global=0.0, groups=3, threads=8, ops=96,
                      n_records=64, distribution="zipfian")]
    dm = _DelayModel(SETTINGS["edge"], svc)
    built = [sweep._closed_point_build(p, 0, dm, cap, 1) for p in pts]
    _, _, pieces = closed_rounds_host(
        built, cap, float(dm.seek), max(b["max_hops"] for b in built), 256)
    dist = [d for b, pc in zip(built, pieces) for m in b["rows"]
            for d in _stack_distances(
                b["flat"]["key"][m[np.argsort(pc[2][m], kind="stable")]])]
    assert cap in dist and cap + 1 in dist
    res = run_sweep(pts, loop="closed", seed=0, service=svc)
    assert res.info["path"] == "device"
    assert res.info["capacity_misses"] == sum(d > cap for d in dist)
    for i, p in enumerate(pts):
        assert_point_matches(res.row(i), closed_reference(p, service=svc))


def test_evicting_sweep_sharded_over_two_devices():
    """The evicting program sharded over the point axis, on two virtual
    CPU devices in a process of its own, gives one device's bits."""
    code = textwrap.dedent("""
        import jax
        import numpy as np
        from repro.sim.cluster import ServiceParams
        from repro.sim.sweep import SweepPoint, run_sweep
        assert jax.local_device_count() == 2
        svc = ServiceParams(page_cache_keys=12)
        pts = [SweepPoint(p_global=pg, groups=3, threads=6, ops=60,
                          n_records=64) for pg in (0.0, 0.5, 1.0)]
        one = run_sweep(pts, loop="closed", seed=0, service=svc)
        two = run_sweep(pts, loop="closed", seed=0, service=svc,
                        devices=2)
        assert two.info["path"] == "device" and two.info["devices"] == 2
        assert one.info["capacity_misses"] == \
            two.info["capacity_misses"] > 0
        for k in one.columns:
            assert np.array_equal(one.columns[k], two.columns[k],
                                  equal_nan=True), k
        print("bit-identical")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") + " --xla_force_"
                          "host_platform_device_count=2").strip(),
               PYTHONPATH=os.pathsep.join(
                   [str(SRC)] + [p for p in [os.environ.get("PYTHONPATH")]
                                 if p]))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.strip() == "bit-identical"


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
