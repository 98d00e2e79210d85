"""CPU tests of the chip benchmark's harness: cells found by name, the
end-to-end arithmetic, and the refusal to run without a TPU."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from itertools import product
from pathlib import Path

import pytest

import harness
import reference

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_files_found_by_name(cell):
    spec, w, config, traffic = harness.load_cell(cell)
    entry = next(c for c in spec["configs"] if c["name"] == w["config"])
    assert entry["file"] == f"benchmarks/chip/configs/{w['config']}.json"
    assert set(entry["reduced"]) <= set(config["reduced"])
    assert set(config["reduced"]) <= set(entry["reduced"])
    points = reference.grid_points(config, traffic)
    axes = traffic["axes"]
    assert len(points) == len(list(product(*axes.values())))
    assert {tuple(p[a] for a in axes) for p in points} == \
        set(product(*axes.values()))
    for p in points:
        assert p["groups"] >= 1 and p["threads"] >= 1


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_run_seed_draws_the_schedule_and_the_order(cell):
    _, _, config, traffic = harness.load_cell(cell)
    big = 2147483647 + 17
    first, seed = reference.request(config, traffic, big)
    again, seed2 = reference.request(config, traffic, big)
    assert first == again and seed == seed2
    key = lambda p: sorted(p.items())  # noqa: E731
    drawn = set()
    for run_seed in range(64):
        points, wl = reference.request(config, traffic, run_seed)
        assert sorted(map(key, points)) == \
            sorted(map(key, reference.grid_points(config, traffic)))
        drawn.add(wl)
    assert drawn == set(traffic["workload_seeds"])


def _hops(config, point, seed):
    d = reference.Delays(config, reference.identity)
    paths, _ = reference._paths(
        reference.closed_schedule(point, seed, config), point, d)
    return max(q.hops for q in paths)


@pytest.mark.parametrize("cell", [
    w["name"] for w in SPEC["workloads"]
    if harness.load_cell(w["name"])[3]["loop"] == "closed"])
def test_workload_seeds_give_the_same_shapes(cell):
    """Every schedule a closed cell draws has the same longest queue and
    the same most overlay hops, the sizes its program is compiled for,
    so that no run with a new seed compiles."""
    _, _, config, traffic = harness.load_cell(cell)
    points = reference.grid_points(config, traffic)
    shapes = {(reference.longest_queue(config, traffic, points, s),
               max(_hops(config, p, s) for p in points))
              for s in traffic["workload_seeds"]}
    assert len(shapes) == 1, shapes


def test_names_and_paths_keep_the_contract():
    names = [c["name"] for c in SPEC["configs"]] + \
        [w["name"] for w in SPEC["workloads"]] + \
        [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
    for path in SPEC["paths"]:
        assert (ROOT / path).is_dir()


def test_new_traffic_file_is_picked_up_by_name(tmp_path, monkeypatch):
    root = tmp_path / "co"
    shutil.copytree(BENCH, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    mix = {"loop": "open", "duration_s": 0.5,
           "axes": {"rate": [100.0, 300.0], "groups": [3, 4, 5]},
           "devices": 1}
    (root / "benchmarks" / "chip" / "traffic" / "new-mix.json").write_text(
        json.dumps(mix))
    spec = dict(SPEC, workloads=SPEC["workloads"] + [dict(
        name="paper-new-mix", config="edgekv-paper", traffic="new-mix",
        chips=1, why="a mix that only a data file adds")])
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    monkeypatch.setattr(harness, "ROOT", root)
    monkeypatch.setattr(harness, "BENCH", root / "benchmarks" / "chip")
    _, cell, config, traffic = harness.load_cell("paper-new-mix")
    assert traffic == mix
    points = reference.grid_points(config, traffic)
    assert [(p["rate"], p["groups"]) for p in points] == \
        list(product([100.0, 300.0], [3, 4, 5]))
    assert all(p["n_records"] == config["n_records"] for p in points)
    with pytest.raises(harness.HarnessError, match="no workload"):
        harness.load_cell("not-a-cell")


def test_every_metric_has_its_reader():
    for m in SPEC["per_layer"]:
        assert callable(harness.load_reader(m["name"]))
    closed = [c["name"] for c in SPEC["workloads"]
              if harness.load_cell(c["name"])[3]["loop"] == "closed"]
    for c in SPEC["workloads"]:
        got = {m["name"] for m in harness.cell_metrics(SPEC, c["name"],
                                                        trace=True)}
        assert {"host_s", "device_call_s", "device_idle_share"} <= got
        assert ("scan_s" in got) == (c["name"] in closed)
        e2e = {m["name"] for m in harness.cell_metrics(SPEC, c["name"],
                                                        trace=False)}
        assert {"sim_ops_per_s", "setup_s"} <= e2e


def test_readers_read_what_is_there():
    sweeps = [dict(walltime_s=2.0, device_s=1.5, rounds=20),
              dict(walltime_s=3.0, device_s=2.5, rounds=21)]
    trace = dict(busy_s=[0.75, 0.5], window_s=1.0, sweeps=2,
                 groups={"sort": 0.2, "scan": 0.4},
                 runs={"scan": 21 * 500, "sort": 22})
    run = dict(sweeps=sweeps, trace=trace, loop="closed", rounds=21,
               queue_len=500)
    read = {m["name"]: harness.load_reader(m["name"])(run)
            for m in SPEC["per_layer"]}
    assert read == dict(host_s=0.5, device_call_s=2.0,
                        device_idle_share=50.0, fixed_point_rounds=21,
                        sort_s=0.1, scan_s=0.2)
    open_run = dict(sweeps=[dict(s, rounds=None) for s in sweeps],
                    trace=dict(trace, groups={"other": 1.0}), loop="open",
                    rounds=None)
    for name in ("fixed_point_rounds", "sort_s", "scan_s"):
        assert harness.load_reader(name)(open_run) is None


@pytest.mark.parametrize("name,runs", [
    ("scan_s", {"scan": 21 * 500 - 1, "sort": 22}),   # a step short
    ("scan_s", {"scan": 9, "sort": 22}),              # scan not a loop
    ("sort_s", {"scan": 21 * 500, "sort": 44}),       # two sorts a round
    ("sort_s", {"scan": 21 * 500, "sort": 0}),
])
def test_readers_read_nothing_from_a_trace_of_another_shape(name, runs):
    trace = dict(busy_s=[0.75], window_s=1.0, sweeps=1,
                 groups={"sort": 0.2, "scan": 0.4}, runs=runs)
    run = dict(sweeps=[dict(walltime_s=2.0, device_s=1.5, rounds=21)],
               trace=trace, loop="closed", rounds=21, queue_len=500)
    assert harness.load_reader(name)(run) is None


@pytest.mark.parametrize("ops,window,want", [
    ([1000, 3000], 2.0, 2000.0),
    ([200_000] * 13, 25.5, 200_000 * 13 / 25.5),
    ([7], 0.25, 28.0),
])
def test_rate_is_all_ops_over_the_whole_window(ops, window, want):
    assert harness.ops_rate(ops, window) == pytest.approx(want, rel=1e-15)


def test_rate_of_an_empty_window_is_refused():
    with pytest.raises(harness.HarnessError):
        harness.ops_rate([], 0.0)


@pytest.mark.parametrize("n,want,beyond", [
    (100, 90.1, 10),     # 1..100
    (116, 104.5, 12),
    (11, 10.0, 1),
    (1, 1.0, 0),
])
def test_p90_and_the_samples_beyond_it(n, want, beyond):
    times = [float(i) for i in range(n, 0, -1)]   # order does not matter
    value, above = harness.p90(times)
    assert value == pytest.approx(want, rel=1e-12)
    assert above == beyond


def test_p90_counts_ties_at_the_percentile_as_not_beyond():
    value, above = harness.p90([1.0] * 95 + [2.0] * 5)
    assert value == 1.0 and above == 5


def _run(cwd: Path, env: dict) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "paper-closed", "--seed", "2147483649", "--seconds", "1"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_and_names_the_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = _run(ROOT, env)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr and "platform 'cpu'" in proc.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for path in SPEC["paths"]:
        shutil.copytree(ROOT / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = _run(tmp_path, env)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no program" in proc.stderr
