"""CPU tests of the readers of the evicting cell's metrics: the exact LRU
stack distance's device seconds (scope ``closed.lru_stack``), its share
of the HBM roofline, and the share of capacity misses (the program's
counter ``capacity_misses``), on made-up runs and on a trace of one
small evicting closed sweep recorded on a TPU v5e."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

import harness
import scope_reduce as S
import trace_reduce as T

BENCH = Path(__file__).resolve().parent
FIXTURES = BENCH / "fixtures"
SCOPED = FIXTURES / "closed_sweep_scoped.xplane.pb.gz"
EVICTING = FIXTURES / "closed_sweep_evicting.xplane.pb.gz"
READERS = ("lru_stack_s", "lru_stack_roofline", "lru_capacity_miss_share")
V5E = "TPU v5 lite"


def _read(run):
    return {n: harness.load_reader(n)(run) for n in READERS}


def _run(scopes, sweeps, rounds=105, traced=2):
    trace = dict(busy_s=[1.0], window_s=1.2, sweeps=traced, groups={},
                 runs={}, scopes=scopes)
    return dict(sweeps=sweeps, trace=trace, loop="closed", rounds=rounds,
                queue_len=5873, device_kind=V5E)


def _sweep(**counters):
    return dict(walltime_s=1.4, device_s=1.35, rounds=105, ops=45_000,
                **counters)


def test_readers_read_a_made_up_evicting_run():
    run = _run({"closed.lru_stack": 0.4, "closed.order": 0.02},
               [_sweep(capacity_misses=540), _sweep(capacity_misses=560)])
    peak = json.loads((BENCH / "peaks.json").read_text())["devices"][V5E]
    assert _read(run) == pytest.approx(dict(
        lru_stack_s=0.2,
        lru_stack_roofline=100.0 * 20 * 45_000 * 106
        / peak["hbm_bytes_per_s"] / 0.2,
        lru_capacity_miss_share=100.0 * 1100 / 90_000), rel=1e-12)


@pytest.mark.parametrize("scopes,sweeps,rounds,kind", [
    ({"closed.lru": 0.1}, [_sweep()], 105, V5E),     # cache-resident
    ({"closed.lru_stack": 0.4}, [_sweep(capacity_misses=5)], None, V5E),
    ({"closed.lru_stack": 0.4}, [_sweep(capacity_misses=5)], 105, "cpu"),
    ({"closed.lru_stack": 0.4}, [], 105, V5E),        # no sweep finished
])
def test_readers_read_nothing_where_it_is_absent(scopes, sweeps, rounds,
                                                  kind):
    """No scope, no counter, traced sweeps of different rounds, or a
    device ``peaks.json`` does not hold: nothing is read there."""
    run = dict(_run(scopes, sweeps, rounds), device_kind=kind)
    read = _read(run)
    assert read["lru_stack_roofline"] is None
    if "closed.lru_stack" not in scopes:
        assert read["lru_stack_s"] is None
    if not any("capacity_misses" in s for s in sweeps):
        assert read["lru_capacity_miss_share"] is None


def test_readers_read_nothing_from_the_cache_resident_trace():
    """The recorded program of a grid that cannot evict has no
    ``closed.lru_stack`` scope and counts no capacity misses."""
    pd, names = S.load(SCOPED)
    red = T.reduce_profile(pd, n_chips=1)
    red.update(scopes=S.reduce_scopes(pd, names, n_chips=1), sweeps=1)
    run = dict(_run({}, [_sweep()], rounds=21), trace=red)
    assert "closed.lru_stack" not in red["scopes"]
    assert set(_read(run).values()) == {None}


def test_recorded_evicting_sweep_reads_under_its_roofline():
    """One traced sweep of a small evicting grid (recorded on a v5e):
    the stage has device time, it and the other scopes add up to busy,
    and its roofline share lies in (0, 100] with the ops and rounds that
    the same grid gives on the CPU."""
    from repro.sim.cluster import ServiceParams
    from repro.sim.sweep import SweepPoint, run_sweep
    pts = [SweepPoint(p_global=pg, groups=3, threads=6, ops=60,
                      n_records=64) for pg in (0.0, 1.0)]
    res = run_sweep(pts, loop="closed", seed=0,
                    service=ServiceParams(page_cache_keys=12))
    pd, names = S.load(EVICTING)
    red = T.reduce_profile(pd, n_chips=1)
    red.update(scopes=S.reduce_scopes(pd, names, n_chips=1), sweeps=1)
    assert sum(red["scopes"].values()) == pytest.approx(red["busy_s"][0],
                                                        rel=1e-6)
    sweep = _sweep(capacity_misses=res.info["capacity_misses"])
    sweep.update(ops=int(res.columns["ops"].sum()),
                 rounds=res.info["rounds"])
    read = _read(dict(_run({}, [sweep], rounds=res.info["rounds"]),
                      trace=red))
    assert read["lru_stack_s"] == red["scopes"]["closed.lru_stack"] > 0
    assert 0 < read["lru_stack_roofline"] <= 100
    assert read["lru_capacity_miss_share"] > 0


@pytest.fixture
def evicting_cell(checkout):
    """A small evicting closed cell in the test checkout: the benchmark
    deployment cut to 10 threads x 100 ops per client over 200 records,
    its leaders caching 20 keys, so that a reused key often misses."""
    bench = checkout / "benchmarks" / "chip"
    config = json.loads((bench / "configs" / "edgekv-paper-evicting.json")
                        .read_text())
    config.update(threads=10, ops_per_client=100, n_records=200)
    config["service"] = dict(config["service"], page_cache_keys=20)
    (bench / "configs" / "tiny-evicting.json").write_text(json.dumps(config))
    (bench / "traffic" / "tiny-evicting-grid.json").write_text(json.dumps(
        {"workload_seeds": [0, 1], "loop": "closed",
         "axes": {"p_global": [0.0, 0.5, 1.0]}, "devices": 1}))
    spec = json.loads((checkout / "BENCHMARK.json").read_text())
    spec["workloads"].append(dict(
        name="tiny-evicting", config="tiny-evicting",
        traffic="tiny-evicting-grid", chips=1, why="CPU test cell"))
    (checkout / "BENCHMARK.json").write_text(json.dumps(spec))
    return "tiny-evicting"


def _measure(cell, make_system=None, seed=2147483653):
    import argparse
    import time
    args = argparse.Namespace(workload=cell, seed=seed, seconds=0.2,
                              trace=0)
    return harness.measure(args, time.perf_counter(),
                           make_system or harness.System)


def test_evicting_run_matches_the_plain_reference(evicting_cell):
    """The program's evicting sweeps, on the device path, agree with
    ``reference.py``'s one-op-at-a-time LRU leaders to the check's
    limit; the float32 control does not."""
    import control
    out = _measure(evicting_cell)
    assert out["correct"] is True, out["check"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert out["check"]["worst_rel_gap"]["value"] <= harness.GAP_LIMIT
    assert _measure(evicting_cell, control.ControlSystem)["correct"] is False
