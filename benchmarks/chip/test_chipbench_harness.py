"""CPU tests of the chip benchmark's harness: cells found by name, the
end-to-end arithmetic, and the refusal to run without a TPU."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from contextlib import nullcontext
from itertools import product
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

import harness
import reference

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def check_cell(cell: str) -> None:
    """What holds for any cell: its files found by name, its
    configuration's cuts as the spec lists them, its grid whole."""
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


def check_names(spec: dict) -> None:
    """Names as the contract has them: unique, of its letters, and
    ``setup_s`` among the end-to-end metrics."""
    names = [c["name"] for c in spec["configs"]] + \
        [w["name"] for w in spec["workloads"]] + \
        [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert "setup_s" in {m["name"] for m in spec["end_to_end"]}


def check_metrics(spec: dict) -> None:
    """What holds for any cell and entry: a reader file for every
    per-layer entry, its ``workloads`` naming only cells of the spec and
    its ``moves`` an end-to-end metric; every cell reports
    ``sim_ops_per_s``, ``setup_s`` and a per-layer metric."""
    cells = {w["name"] for w in spec["workloads"]}
    e2e = {m["name"] for m in spec["end_to_end"]}
    for m in spec["per_layer"]:
        assert callable(harness.load_reader(m["name"])), m["name"]
        assert m["workloads"] and set(m["workloads"]) <= cells, m["name"]
        assert m["moves"] in e2e, m["name"]
    for c in cells:
        got = {m["name"] for m in harness.cell_metrics(spec, c, trace=False)}
        assert {"sim_ops_per_s", "setup_s"} <= got, c
        assert harness.cell_metrics(spec, c, trace=True), c


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_files_found_by_name(cell):
    check_cell(cell)


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
    check_names(SPEC)
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
    check_metrics(SPEC)
    got = {m["name"] for m in harness.cell_metrics(SPEC, "paper-closed",
                                                    trace=True)}
    assert {"host_s", "device_call_s", "device_idle_share", "scan_s"} <= got


def _snapshot(root: Path) -> dict:
    return {p.relative_to(root): p.read_bytes()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_a_cell_its_counter_and_its_metric_are_added_as_data(
        tmp_path, monkeypatch):
    """A later PR adds a closed cell on a configuration and traffic file
    of its own, and a per-layer metric whose reader reads a counter the
    program puts in ``info``, by new files and new entries alone."""
    root = tmp_path / "co"
    bench = root / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    before = _snapshot(bench)
    config = json.loads((bench / "configs" / "edgekv-paper.json")
                        .read_text())
    config["service"] = dict(config["service"], page_cache_keys=2500)
    (bench / "configs" / "edgekv-paper-evict.json").write_text(
        json.dumps(config))
    (bench / "traffic" / "closed-evict-grid.json").write_text(json.dumps(
        {"workload_seeds": [549, 8975], "loop": "closed",
         "axes": {"p_global": [0.0, 1.0]}, "devices": 1}))
    (bench / "metrics" / "capacity_miss_share.py").write_text(
        '"""Made-up reader of a made-up counter."""\n\n\n'
        "def read(run):\n"
        "    v = [s.get('capacity_misses') for s in run['sweeps']]\n"
        "    if not v or None in v:\n"
        "        return None\n"
        "    return 100.0 * sum(v) / sum(s['ops'] for s in run['sweeps'])\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append(dict(
        spec["configs"][0], name="edgekv-paper-evict",
        file="benchmarks/chip/configs/edgekv-paper-evict.json"))
    spec["workloads"].append(dict(
        name="paper-evict", config="edgekv-paper-evict",
        traffic="closed-evict-grid", chips=1, why="a cell added as data"))
    spec["per_layer"].append(dict(
        name="capacity_miss_share", unit="%", better="lower",
        source="program_counter", layer="closed fixed point",
        moves="sim_ops_per_s", workloads=["paper-evict"]))
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    after = _snapshot(bench)
    assert {k: after[k] for k in before} == before  # no file touched
    assert len(after) == len(before) + 3
    for group, entries in SPEC.items():   # the spec only gains entries
        if isinstance(entries, list):
            assert spec[group][:len(entries)] == entries, group
        else:
            assert spec[group] == entries, group

    monkeypatch.setattr(harness, "ROOT", root)
    monkeypatch.setattr(harness, "BENCH", bench)
    check_names(spec)
    check_metrics(spec)
    for cell in ("paper-closed", "paper-evict"):
        check_cell(cell)
    got = {m["name"] for m in harness.cell_metrics(spec, "paper-evict",
                                                    trace=True)}
    assert got == {"capacity_miss_share"}

    class Sweep:   # what run_sweep returns, with the new counter
        info = dict(path="device", device_s=0.5, rounds=3,
                    capacity_misses=45, spans={"run_sweep.build": 0.01})
        walltime_s = 0.6
        columns = {"ops": np.array([450.0, 450.0])}

        def __len__(self):
            return 2

    system = SimpleNamespace(sweep=Sweep)
    win = harness.run_window(system, 0.0, lambda name: nullcontext())
    run = dict(sweeps=win["sweeps"], trace={}, loop="closed", rounds=3)
    assert harness.load_reader("capacity_miss_share")(run) == 5.0


def test_readers_read_what_is_there():
    spans = {"run_sweep.build": 0.01, "run_sweep.fold": 0.004}
    sweeps = [dict(walltime_s=2.0, device_s=1.5, rounds=20, ops=900,
                   spans=spans, changed=300, op_rounds=18_000,
                   grid_slots=1800),
              dict(walltime_s=3.0, device_s=2.5, rounds=21, ops=900,
                   spans=dict(spans, **{"run_sweep.build": 0.03}),
                   changed=600, op_rounds=18_900, grid_slots=1800)]
    scopes = {"closed.arrival": 0.02, "closed.order": 0.2,
              "closed.lru": 0.06, "closed.to_grid": 0.08,
              "closed.depart": 0.4, "closed.from_grid": 0.1,
              "closed.completion": 0.004, "unscoped": 0.386}
    trace = dict(busy_s=[0.75, 0.5], window_s=1.0, sweeps=2,
                 groups={"sort": 0.2, "scan": 0.4},
                 runs={"scan": 21 * 500, "sort": 22}, scopes=scopes)
    run = dict(sweeps=sweeps, trace=trace, loop="closed", rounds=21,
               queue_len=500)
    read = {m["name"]: harness.load_reader(m["name"])(run)
            for m in SPEC["per_layer"]}
    known = dict(host_s=0.5, device_call_s=2.0, device_idle_share=50.0,
                 fixed_point_rounds=21, sort_s=0.1, scan_s=0.2,
                 arrival_s=0.01, lru_s=0.03, to_grid_s=0.04,
                 from_grid_s=0.05, completion_s=0.002, build_s=0.02,
                 fold_s=0.004, round_useful_share=100.0 * 900 / 36_900,
                 grid_fill=50.0)
    assert {k: read[k] for k in known} == pytest.approx(known, rel=1e-12)
    open_run = dict(sweeps=[dict(s, rounds=None, changed=None,
                                 op_rounds=None, grid_slots=None)
                            for s in sweeps],
                    trace=dict(trace, groups={"other": 1.0},
                               scopes={"unscoped": 1.0}),
                    loop="open", rounds=None)
    got = {m["name"]: harness.load_reader(m["name"])(open_run)
           for m in SPEC["per_layer"]}
    for name in ("fixed_point_rounds", "sort_s", "scan_s", "arrival_s",
                 "lru_s", "to_grid_s", "from_grid_s", "completion_s",
                 "round_useful_share", "grid_fill"):
        assert got[name] is None, name
    for name in set(read) - set(known):   # entries later PRs add
        for v in (read[name], got[name]):
            assert v is None or isinstance(v, (int, float)), name


@pytest.mark.parametrize("name,scopes,sweeps,want", [
    ("sort_s", {"unscoped": 0.75}, 1, None),               # no such scope
    ("sort_s", {"closed.order": 0.2, "closed.from_grid": 0.2}, 1, 0.2),
    ("sort_s", {"closed.order": 0.2}, 0, None),            # no sweeps
    ("scan_s", {"closed.depart": 0.4, "closed.replay": 0.1}, 2, 0.2),
])
def test_readers_read_nothing_from_a_trace_of_another_shape(
        name, scopes, sweeps, want):
    """The scan and sort readers take their scope's seconds alone: a sort
    under another scope, or a step count other than the opcode groups'
    (``runs``), leaves them as they are."""
    trace = dict(busy_s=[0.75], window_s=1.0, sweeps=sweeps,
                 groups={"sort": 0.4, "scan": 0.3},
                 runs={"scan": 9, "sort": 44}, scopes=scopes)
    run = dict(sweeps=[dict(walltime_s=2.0, device_s=1.5, rounds=21)],
               trace=trace, loop="closed", rounds=21, queue_len=500)
    assert harness.load_reader(name)(run) == want


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
