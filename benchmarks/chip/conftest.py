"""Fixtures of the chip benchmark's CPU tests: a small checkout whose
cells run the harness end to end on the CPU, the look for a chip
skipped.  Nothing here describes or loads a TPU."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]

TINY_CELLS = {
    "tiny-closed": ("tiny-paper", "tiny-closed-grid",
                    {"workload_seeds": [0, 1, 2], "loop": "closed",
                     "axes": {"p_global": [0.5, 1.0]}, "devices": 1}),
    "tiny-open": ("tiny-paper", "tiny-open-grid",
                  {"workload_seeds": [0, 1, 2], "loop": "open",
                   "duration_s": 0.2,
                   "axes": {"p_global": [0.25, 0.75], "rate": [200.0]},
                   "devices": 1}),
}


def make_checkout(root: Path) -> Path:
    """A checkout at ``root`` with the benchmark's files, the program
    linked in, and two small cells over a cut ``edgekv-paper``."""
    bench = root / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "test_*.py", "conftest.py"))
    (root / "src").symlink_to(ROOT / "src")
    config = json.loads((bench / "configs" / "edgekv-paper.json")
                        .read_text())
    config.update(threads=10, ops_per_client=100)
    (bench / "configs" / "tiny-paper.json").write_text(json.dumps(config))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for name, (cfg, traffic, mix) in TINY_CELLS.items():
        (bench / "traffic" / f"{traffic}.json").write_text(json.dumps(mix))
        spec["workloads"].append(dict(name=name, config=cfg, traffic=traffic,
                                      chips=1, why="CPU test cell"))
    for group in ("end_to_end", "per_layer"):
        for m in spec[group]:
            if "workloads" in m:
                m["workloads"] += list(TINY_CELLS)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture
def checkout(tmp_path, monkeypatch):
    """The harness pointed at a small checkout, on the CPU, with JAX's
    persistent compile cache left as the test session has it."""
    import harness
    root = make_checkout(tmp_path / "checkout")
    monkeypatch.setattr(harness, "ROOT", root)
    monkeypatch.setattr(harness, "BENCH", root / "benchmarks" / "chip")
    monkeypatch.setattr(harness, "check_devices",
                        lambda jax, chips, peaks: jax.devices()[:chips])
    monkeypatch.setattr(harness, "use_cache", lambda: "unchanged")
    return root
