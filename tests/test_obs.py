"""``repro.obs`` — span-level cross-engine differentials, the metrics
registry, sweep per-stage aggregates, and the trace CLI.

The span contract mirrors the latency contract one level deeper: on
closed-loop no-churn runs the oracle's inline stage boundaries and the
fast engine's column reconstruction must agree **bit-exactly** (they are
the same float additions, recorded at the same intermediate points);
under churn the per-stage means stay within the engines' 2% statistical
envelope; ``run_sweep``'s jit-computed stage aggregates match a traced
fast-engine run to <= 1e-9.
"""
from pathlib import Path

import numpy as np
import pytest

from repro.obs import (BOUNDARY_FIELDS, Counter, Gauge, Histogram,
                       MetricsRegistry, NULL_INSTRUMENT, STAGES, TraceSet,
                       format_snapshot)
from repro.obs.__main__ import main as obs_cli
from repro.sim import SimEdgeKV
from repro.sim.records import RecordArray
from repro.sim.sweep import SweepPoint, run_sweep

REPO = Path(__file__).resolve().parent.parent
SAMPLE_TRACE = REPO / "benchmarks" / "sample_trace.json"
SPAN_COLS = ("t_start", "latency") + BOUNDARY_FIELDS
TOL = 1e-9


def traced(engine, init, run, churn_kw=None, open_loop=False):
    sim = SimEdgeKV(engine=engine, trace=True, **init)
    if churn_kw:
        sim.env.process(sim.churn_proc(**churn_kw))
    if open_loop:
        sim.run_open_loop(**run)
    else:
        sim.run_closed_loop(**run)
    return sim


def bounds_matrix(sim):
    """(9, n) absolute boundaries: t_start then the eight stage ends."""
    cols = sim.records.columns()
    return np.stack([cols["t_start"]]
                    + [cols[f] for f in BOUNDARY_FIELDS])


def stage_means(sim):
    """Mean per-stage durations, one per entry of STAGES."""
    return np.diff(bounds_matrix(sim), axis=0).mean(axis=1)


# ------------------------------------------------- span invariants (per run)
def assert_span_invariants(sim):
    cols = sim.records.columns()
    b = bounds_matrix(sim)
    # boundaries are monotone: every stage has non-negative duration
    assert (np.diff(b, axis=0) >= 0).all()
    # the decomposition telescopes exactly to the recorded latency
    assert np.array_equal(cols["b_end"] - cols["t_start"], cols["latency"])


@pytest.mark.parametrize("init,run", [
    (dict(setting="edge", seed=2),
     dict(threads_per_client=15, ops_per_client=150,
          workload_kw=dict(p_global=0.5, distribution="zipfian"))),
    (dict(setting="cloud", seed=0),
     dict(threads_per_client=10, ops_per_client=100,
          workload_kw=dict(p_global=1.0))),
    (dict(setting="edge", seed=4, group_sizes=(1, 3, 5)),
     dict(threads_per_client=10, ops_per_client=120,
          workload_kw=dict(p_global=0.7))),
    (dict(setting="edge", seed=5, virtual_nodes=4, group_sizes=(3,) * 4),
     dict(threads_per_client=10, ops_per_client=120,
          workload_kw=dict(p_global=1.0), seed_offset=7)),
])
def test_closed_loop_spans_bit_exact(init, run):
    """Closed-loop no-churn: all eight boundary columns identical across
    engines, monotone, and summing exactly to the recorded latency."""
    o = traced("oracle", init, run)
    f = traced("fast", init, run)
    assert_span_invariants(o)
    assert_span_invariants(f)
    a, b = o.records.columns(), f.records.columns()
    for col in SPAN_COLS:
        assert np.array_equal(a[col], b[col]), col


def test_tracing_does_not_perturb_either_engine():
    """trace=True must be a pure observer: base columns bit-identical to
    an untraced run, on both engines."""
    init = dict(setting="edge", seed=2)
    run = dict(threads_per_client=15, ops_per_client=150,
               workload_kw=dict(p_global=0.5))
    for engine in ("oracle", "fast"):
        plain = SimEdgeKV(engine=engine, **init)
        plain.run_closed_loop(**run)
        span = traced(engine, init, run)
        a, b = plain.records.columns(), span.records.columns()
        for col in ("t_start", "latency", "kind", "dtype", "group", "hops"):
            assert np.array_equal(a[col], b[col]), (engine, col)


def test_closed_loop_churn_spans_statistical():
    """Under membership churn the engines resolve routing at different
    instants (schedule-time vs mid-flight), so the span contract relaxes
    to the same 2% envelope the latency differentials use — per stage."""
    init = dict(setting="edge", seed=0, group_sizes=(3,) * 6)
    run = dict(threads_per_client=50, ops_per_client=500,
               workload_kw=dict(p_global=0.5, n_records=2000))
    churn = dict(t_start=0.05, period=0.1, adds=2)
    o = traced("oracle", init, run, churn_kw=churn)
    f = traced("fast", init, run, churn_kw=churn)
    assert_span_invariants(o)
    assert_span_invariants(f)
    mo, mf = stage_means(o), stage_means(f)
    for s, a, b in zip(STAGES, mo, mf):
        assert abs(b - a) <= max(0.02 * abs(a), 1e-5), (s, a, b)


def test_open_loop_spans_invariant_and_statistical():
    """Open loop draws arrivals from different RNG streams per engine, so
    spans agree only statistically — but each engine's own decomposition
    still telescopes exactly."""
    init = dict(setting="edge", seed=3)
    run = dict(rate_per_client=150.0, duration=1.0,
               workload_kw=dict(p_global=0.5))
    o = traced("oracle", init, run, open_loop=True)
    f = traced("fast", init, run, open_loop=True)
    assert_span_invariants(o)
    assert_span_invariants(f)
    assert abs(len(f.records) - len(o.records)) / len(o.records) < 0.05
    mo, mf = stage_means(o), stage_means(f)
    for s, a, b in zip(STAGES, mo, mf):
        # route rides on which ops the Poisson streams emitted (~3%) and
        # queue is tiny and clustering-sensitive — loose band, abs floor
        assert abs(b - a) <= max(0.25 * abs(a), 1e-4), (s, a, b)


# ------------------------------------------------ sweep per-stage aggregates
def sweep_stage_reference(sim):
    return stage_means(sim)


def test_open_sweep_stage_aggregates_match_fast_engine():
    pts = [SweepPoint(p_global=0.5, rate=180.0, groups=3,
                      distribution="zipfian"),
           SweepPoint(p_global=1.0, rate=100.0, groups=5,
                      distribution="latest")]
    res = run_sweep(pts, duration=1.5, seed=0)
    for i, p in enumerate(pts):
        sim = SimEdgeKV(setting="edge", seed=0, engine="fast", trace=True,
                        group_sizes=(p.group_size,) * p.groups)
        sim.run_open_loop(rate_per_client=p.rate, duration=1.5,
                          workload_kw=dict(p_global=p.p_global,
                                           distribution=p.distribution,
                                           n_records=p.n_records))
        want = sweep_stage_reference(sim)
        for si, s in enumerate(STAGES):
            got = res.columns[f"stage_{s}"][i]
            assert abs(got - want[si]) <= TOL * max(1.0, abs(want[si])), \
                (s, got, want[si])


@pytest.mark.parametrize("service_kw", [None, dict(page_cache_keys=16)])
def test_closed_sweep_stage_aggregates_match_fast_engine(service_kw):
    """Both closed-loop regimes — the cache-resident program and the
    evicting one with its exact LRU stack distance, both on the device
    path — emit the same stage aggregates the traced fast engine
    reconstructs, <= 1e-9."""
    from repro.sim.cluster import ServiceParams
    svc = ServiceParams(**service_kw) if service_kw else None
    pts = [SweepPoint(p_global=0.5, groups=3, threads=8, ops=64,
                      distribution="zipfian"),
           SweepPoint(p_global=1.0, groups=5, threads=4, ops=40)]
    res = run_sweep(pts, loop="closed", seed=0, service=svc)
    assert res.info["path"] == "device"
    assert (res.info["capacity_misses"] > 0) == bool(service_kw)
    for i, p in enumerate(pts):
        sim = SimEdgeKV(setting="edge", seed=0, engine="fast", trace=True,
                        service=svc,
                        group_sizes=(p.group_size,) * p.groups)
        sim.run_closed_loop(threads_per_client=p.threads,
                            ops_per_client=p.ops,
                            workload_kw=dict(p_global=p.p_global,
                                             distribution=p.distribution,
                                             n_records=p.n_records),
                            seed_offset=0)
        want = sweep_stage_reference(sim)
        for si, s in enumerate(STAGES):
            got = res.columns[f"stage_{s}"][i]
            assert abs(got - want[si]) <= TOL * max(1.0, abs(want[si])), \
                (s, got, want[si])


# ----------------------------------------------------------- fig_trace smoke
def test_fig_trace_rows_bitexact_and_shares():
    from repro.sim.experiments import fig_trace
    rows = fig_trace(ops_per_client=60, threads=6)
    assert {r["setting"] for r in rows} == {"edge", "cloud"}
    for r in rows:
        assert r["span_bitexact"] is True
        shares = sum(r[f"share_{s}"] for s in STAGES)
        assert abs(shares - 1.0) < 1e-9
        total = sum(r[f"stage_{s}_ms"] for s in STAGES)
        assert abs(total - r["mean_latency_ms"]) < 1e-6
    edge = {r["dtype"]: r for r in rows if r["setting"] == "edge"}
    # the §7 split: global ops pay routing, local ops never do
    assert edge["local"]["stage_route_ms"] == 0.0
    assert edge["global"]["stage_route_ms"] > 1.0


# ------------------------------------------------------------------- tracer
def test_trace_set_roundtrip_and_summary(tmp_path):
    sim = traced("fast", dict(setting="edge", seed=1),
                 dict(threads_per_client=6, ops_per_client=60,
                      workload_kw=dict(p_global=0.5)))
    ts = sim.trace_set(meta=dict(figure="unit"))
    path = tmp_path / "t.json"
    ts.to_json(path)
    back = TraceSet.from_json(path)
    assert back.meta["figure"] == "unit"
    assert back.metrics == ts.metrics
    for f in ("t_start", "latency") + BOUNDARY_FIELDS:
        assert np.array_equal(back.columns[f], ts.columns[f]), f
    summary = ts.stage_summary()
    assert set(summary) == set(STAGES)
    assert abs(sum(v["share"] for v in summary.values()) - 1.0) < 1e-9
    path_txt = ts.flamegraph()
    assert "response" in path_txt and "route" in path_txt
    ranked = ts.critical_path()
    assert ranked[0]["mean"] >= ranked[-1]["mean"]
    assert {r["stage"] for r in ranked} == set(STAGES)


def test_disabled_tracer_and_registry_overhead():
    """Disabled observability must leave no footprint: untraced record
    buffers carry no span columns, and a disabled registry hands out the
    one shared null instrument (no allocation, no-op mutators)."""
    sim = SimEdgeKV(setting="edge", seed=0, engine="fast")
    sim.run_closed_loop(threads_per_client=5, ops_per_client=50,
                        workload_kw=dict(p_global=0.5))
    assert not sim.records.stages
    assert set(sim.records.columns()) == {
        "t_start", "latency", "kind", "dtype", "group", "hops"}
    with pytest.raises(ValueError):
        sim.trace_set()
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("x.y")
    assert c is NULL_INSTRUMENT
    assert reg.gauge("z") is NULL_INSTRUMENT
    assert reg.histogram("h") is NULL_INSTRUMENT
    c.inc(5)
    assert reg.snapshot() == {}


# ------------------------------------------------------------------ metrics
def test_metrics_registry_instruments_and_diff():
    reg = MetricsRegistry()
    reg.counter("a.reads").inc()
    reg.counter("a.reads").inc(4)
    reg.gauge("a.depth").set(7)
    h = reg.histogram("a.lat")
    for v in (1e-4, 2e-4, 1e-3):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["a.reads"] == 5
    assert snap["a.depth"] == 7
    assert snap["a.lat.count"] == 3
    assert abs(snap["a.lat.mean"] - (1.3e-3 / 3)) < 1e-12
    assert snap["a.lat.min"] == 1e-4 and snap["a.lat.max"] == 1e-3
    assert 1e-4 <= snap["a.lat.p95"] <= 1e-3
    reg.counter("a.reads").inc(2)
    diff = MetricsRegistry.diff(snap, reg.snapshot())
    assert diff["a.reads"] == 2 and diff["a.depth"] == 0
    lines = format_snapshot(reg.snapshot(), prefix="a.")
    assert any("a.reads" in ln for ln in lines)
    with pytest.raises(TypeError):
        reg.gauge("a.reads")
    assert isinstance(reg.counter("a.reads"), Counter)
    assert isinstance(reg.gauge("a.depth"), Gauge)
    assert isinstance(reg.histogram("a.lat"), Histogram)


def test_sim_metrics_snapshot_names():
    sim = SimEdgeKV(setting="edge", seed=0, engine="fast")
    sim.run_closed_loop(threads_per_client=5, ops_per_client=50,
                        workload_kw=dict(p_global=0.5))
    m = sim.metrics()
    assert m["sim.records.count"] == 150
    assert m["sim.lost_ops"] == 0
    for name in ("sim.refusals.writes", "sim.cache.page.hits",
                 "sim.latency.mean", "sim.latency.p99",
                 "sim.churn.events"):
        assert name in m, name
    assert abs(m["sim.latency.mean"] - sim.mean_latency()) < 1e-15


# ------------------------------------------- RecordArray invalidation (fix)
def test_group_stats_invalidated_by_both_mutation_paths():
    """Regression: a group_stats/group_tails snapshot taken before an
    extend_columns (or append) must not survive the mutation."""
    ra = RecordArray()
    ra.register_group("g0")
    ra.append(0.0, 1.0, 0, 0, 0, 0)
    assert ra.group_stats()["g0"] == (1, 0.0, 1.0)
    assert ra.group_tails()["g0"]
    ra.extend_columns(np.array([5.0]), np.array([2.0]),
                      np.zeros(1, np.uint8), np.zeros(1, np.uint8),
                      np.zeros(1, np.int32), np.zeros(1, np.int32))
    count, first, last = ra.group_stats()["g0"]
    assert (count, first, last) == (2, 0.0, 7.0)
    assert ra.group_stats(percentiles=(95,))["g0"][0] == 2
    ra.append(10.0, 0.5, 0, 0, 0, 0)
    assert ra.group_stats()["g0"][0] == 3
    assert ra.group_stats()["g0"][2] == 10.5


def test_stage_record_array_requires_bounds():
    ra = RecordArray(stages=True)
    ra.register_group("g0")
    with pytest.raises(ValueError):
        ra.append(0.0, 1.0, 0, 0, 0, 0)
    with pytest.raises(ValueError):
        ra.extend_columns(np.zeros(1), np.ones(1),
                          np.zeros(1, np.uint8), np.zeros(1, np.uint8),
                          np.zeros(1, np.int32), np.zeros(1, np.int32))
    ra.append(0.0, 1.0, 0, 0, 0, 0, bounds=(0.1,) * 7 + (1.0,))
    assert ra.columns()["b_end"][0] == 1.0


# ---------------------------------------------------------------- CLI smoke
def test_cli_summarize_committed_sample(capsys):
    assert SAMPLE_TRACE.is_file(), "committed sample trace missing"
    assert obs_cli(["summarize", str(SAMPLE_TRACE)]) == 0
    out = capsys.readouterr().out
    assert "route" in out and "share" in out
    assert "sim.records.count" in out


def test_cli_flamegraph_and_critical_path(capsys):
    assert obs_cli(["flamegraph", str(SAMPLE_TRACE), "--split",
                    "dtype"]) == 0
    out = capsys.readouterr().out
    assert "critical path" in out
    assert "global" in out and "local" in out


def test_cli_diff_self_is_zero(capsys):
    assert obs_cli(["diff", str(SAMPLE_TRACE), str(SAMPLE_TRACE)]) == 0
    out = capsys.readouterr().out
    assert "+0.0000" in out


def test_cli_summarize_json(capsys):
    import json
    assert obs_cli(["summarize", str(SAMPLE_TRACE), "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert set(doc["stages"]["all"]) == set(STAGES)
