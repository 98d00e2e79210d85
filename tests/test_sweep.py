"""Sweep-engine equivalence: run_sweep's batched array program must
reproduce independent ``SimEdgeKV(engine="fast")`` open-loop runs on the
same seeds, per grid point, to float-order accuracy (<= 1e-9)."""
import numpy as np
import pytest

from repro.sim import SimEdgeKV
from repro.sim.sweep import SweepPoint, SweepResult, run_sweep, sweep_grid

TOL = 1e-9


def fast_reference(p: SweepPoint, duration: float, seed: int = 0,
                   setting: str = "edge") -> SimEdgeKV:
    sim = SimEdgeKV(setting=setting, seed=seed,
                    group_sizes=(p.group_size,) * p.groups, engine="fast")
    sim.run_open_loop(rate_per_client=p.rate, duration=duration,
                      workload_kw=dict(p_global=p.p_global,
                                       distribution=p.distribution,
                                       n_records=p.n_records))
    return sim


def assert_point_matches(row: dict, sim: SimEdgeKV) -> None:
    checks = [
        ("ops", len(sim.records)),
        ("mean_latency", sim.mean_latency()),
        ("read_latency", sim.mean_latency(kind="read")),
        ("update_latency", sim.mean_latency(kind="update")),
        ("global_latency", sim.mean_latency(dtype="global")),
        ("update_global_latency",
         sim.mean_latency(kind="update", dtype="global")),
        ("throughput", sim.throughput()),
        ("p95_latency", sim.tail_latency(95)),
        ("p99_latency", sim.tail_latency(99)),
    ]
    for name, want in checks:
        got = row[name]
        if np.isnan(want):
            assert np.isnan(got), name
            continue
        assert abs(got - want) <= TOL * max(1.0, abs(want)), \
            (name, got, want)


def test_run_sweep_matches_fast_engine_per_point():
    pts = [SweepPoint(p_global=pg, rate=r, groups=g, n_records=nr,
                      distribution=dist)
           for pg, r, g, nr, dist in [
               (0.0, 120.0, 3, 10_000, "uniform"),
               (0.5, 180.0, 3, 10_000, "zipfian"),
               (0.75, 150.0, 4, 2_500, "uniform"),
               (1.0, 100.0, 5, 10_000, "latest"),
           ]]
    res = run_sweep(pts, duration=1.5, seed=0)
    assert len(res) == len(pts)
    for i, p in enumerate(pts):
        assert_point_matches(res.row(i), fast_reference(p, 1.5))


def test_run_sweep_cloud_setting_and_seed():
    p = SweepPoint(p_global=0.5, rate=150.0, groups=3)
    res = run_sweep([p], duration=1.0, setting="cloud", seed=7)
    assert_point_matches(res.row(0),
                         fast_reference(p, 1.0, seed=7, setting="cloud"))


def test_run_sweep_pallas_scan_backend():
    pts = [SweepPoint(p_global=0.5, rate=120.0, groups=3)]
    a = run_sweep(pts, duration=1.0)
    b = run_sweep(pts, duration=1.0, scan_backend="pallas",
                  interpret=True)
    for k in a.columns:
        np.testing.assert_allclose(a.columns[k], b.columns[k], rtol=1e-12)


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_pallas_scan_backend_refuses_compiled_mode(loop):
    """The kernel does not compile for the TPU at the sweep's float64, so
    without interpret=True the sweep refuses before tracing instead of
    degrading into another backend."""
    pts = [SweepPoint(p_global=0.5, rate=120.0, groups=3, threads=4,
                      ops=16)]
    with pytest.raises(ValueError, match="interpret=True"):
        run_sweep(pts, duration=1.0, loop=loop, scan_backend="pallas")


def test_run_sweep_deterministic_and_seed_sensitive():
    pts = [SweepPoint(p_global=0.5, rate=150.0)]
    a = run_sweep(pts, duration=1.0, seed=0)
    b = run_sweep(pts, duration=1.0, seed=0)
    c = run_sweep(pts, duration=1.0, seed=3)
    assert a.columns["mean_latency"][0] == b.columns["mean_latency"][0]
    assert a.columns["mean_latency"][0] != c.columns["mean_latency"][0]


def test_sweep_grid_shape_and_rows():
    grid = sweep_grid()
    assert len(grid) == 64
    assert len({(p.p_global, p.rate, p.n_records, p.groups)
                for p in grid}) == 64
    res = run_sweep(grid[:2], duration=0.5)
    rows = res.rows()
    assert len(rows) == 2
    assert {"p_global", "rate", "groups", "mean_latency", "throughput",
            "p95_latency", "p99_latency"} <= set(rows[0])


def test_run_sweep_rejects_bad_args():
    with pytest.raises(ValueError):
        run_sweep([])
    with pytest.raises(ValueError):
        run_sweep([SweepPoint()], duration=0.0)


def test_lru_hit_mask_matches_cache_replay():
    """The vectorized penalty mask must equal an OrderedDict LRU replay,
    including the eviction (Fenwick) regime."""
    from repro.core.cache import LRUCache
    from repro.sim.vectorized import lru_hit_mask

    rng = np.random.default_rng(0)
    for capacity, nkeys, n in ((8, 30, 400), (64, 50, 500),
                               (2500, 100, 300), (5, 5, 100)):
        seq = rng.integers(0, nkeys, size=n)
        cache = LRUCache(capacity)
        want = np.zeros(n, bool)
        for i, k in enumerate(seq.tolist()):
            want[i] = cache.get(k) is not None
            cache.put(k, True)
        got = lru_hit_mask(seq, capacity)
        assert np.array_equal(got, want), (capacity, nkeys)


def test_record_array_tail_latency_and_group_tails():
    sim = SimEdgeKV(setting="edge", seed=0, engine="fast")
    sim.run_closed_loop(threads_per_client=10, ops_per_client=200,
                        workload_kw=dict(p_global=0.5))
    lat = sim.records.columns()["latency"]
    assert sim.tail_latency(95) == np.percentile(lat, 95)
    assert sim.tail_latency(99) == np.percentile(lat, 99)
    assert sim.tail_latency(95) <= sim.tail_latency(99)
    assert sim.tail_latency(50) < sim.tail_latency(99)
    # selection-aware tails
    upd = lat[sim.records.columns()["kind"] == 1]
    assert sim.tail_latency(95, kind="update") == np.percentile(upd, 95)
    # per-group extension of group_stats keeps the legacy 3-tuple intact
    legacy = sim.records.group_stats()
    count, t0, t1 = legacy["g0"]
    ext = sim.records.group_stats(percentiles=(95, 99))
    assert ext["g0"][:3] == (count, t0, t1)
    g0_lat = np.asarray([r.latency for r in sim.records
                         if r.group == "g0"])
    assert ext["g0"][3] == np.percentile(g0_lat, 95)
    assert ext["g0"][4] == np.percentile(g0_lat, 99)
    # regression: a second bulk run (extend_columns) must invalidate the
    # cached tails, not serve the first run's percentiles
    p99_first = sim.tail_latency(99)
    sim.run_closed_loop(threads_per_client=10, ops_per_client=200,
                        workload_kw=dict(p_global=1.0), seed_offset=5)
    lat2 = sim.records.columns()["latency"]
    assert sim.tail_latency(99) == np.percentile(lat2, 99)
    assert sim.tail_latency(99) != p99_first
    ext2 = sim.records.group_tails((95.0, 99.0))
    g0_lat2 = lat2[sim.records.columns()["group"] == 0]
    assert ext2["g0"][1] == np.percentile(g0_lat2, 99)


@pytest.mark.slow
def test_acceptance_64_point_grid_matches_fast_engine():
    """Acceptance: a >=64-point grid evaluated as one jitted array
    program, every point matching the fast engine within 1e-9."""
    grid = sweep_grid()
    assert len(grid) >= 64
    res = run_sweep(grid, duration=1.0, seed=0)
    for i, p in enumerate(grid):
        assert_point_matches(res.row(i), fast_reference(p, 1.0))


def measured_speedup(loop_once, sweep_once, reps: int = 3):
    """Interleaved walltime comparison: warm both sides (jit compiles,
    allocator pools), then alternate loop/sweep reps so host-load drift
    hits both, and compare *medians* — a single noisy-neighbour spike
    then lands in at most one rep per side and cannot flip the ratio the
    way best-of or single-shot timing can."""
    import statistics

    sweep_once()
    sweep_once()
    loop_once()
    loops, sweeps = [], []
    for _ in range(reps):
        loops.append(loop_once())
        sweeps.append(sweep_once())
    return statistics.median(loops) / statistics.median(sweeps), \
        loops, sweeps


def strict_perf_floor() -> bool:
    """Hard walltime floors only run where the host is quiet enough to
    make them meaningful (the nightly tier exports EDGEKV_NIGHTLY=1);
    everywhere else the ratio is printed and sanity-checked, and the
    equivalence tests carry the correctness load."""
    import os
    return os.environ.get("EDGEKV_NIGHTLY", "") not in ("", "0")


@pytest.mark.slow
def test_acceptance_sweep_speedup():
    """Acceptance: >=2x wall clock over looping the numpy fast engine at
    the 64-point grid size (median of 3 interleaved reps after warmup;
    the strict floor is nightly-only, see strict_perf_floor)."""
    import time

    grid = sweep_grid()

    def sweep_once():
        t0 = time.perf_counter()
        run_sweep(grid, duration=2.0)
        return time.perf_counter() - t0

    def loop_once():
        t0 = time.perf_counter()
        for p in grid:
            sim = fast_reference(p, 2.0)
            (sim.mean_latency(), sim.mean_latency(kind="update"),
             sim.throughput(), sim.tail_latency(95), sim.tail_latency(99))
        return time.perf_counter() - t0

    import os

    ratio, loops, sweeps = measured_speedup(loop_once, sweep_once)
    print(f"sweep speedup: {ratio:.1f}x "  # lint: ignore[EDK004] -- walltime reporting
          f"(loops={loops} sweeps={sweeps})")
    if os.cpu_count() == 1 and not strict_perf_floor():
        # single-vCPU hosts timeshare XLA's compile/execute threads with
        # the numpy loop under test, so even the gross tripwire flakes;
        # the equivalence tests above still carry the correctness load
        pytest.skip(f"1-cpu host: speedup ratio {ratio:.2f} "
                    "reported, walltime floor not enforced")
    assert ratio > 0.75, (ratio, loops, sweeps)  # gross-regression tripwire
    if strict_perf_floor():
        assert ratio >= 2.0, (ratio, loops, sweeps)
