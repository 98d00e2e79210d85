"""Compile rehearsals of the sweep engine's jitted programs for a TPU v5e
that is described, not attached (the TPU compiler ships with jaxlib).

Nothing runs: each test lowers one program on the described chip's
shardings and compiles it, which raises what the chip's compiler would
raise (unsupported float64 ops, shapes it refuses, programs that do not
fit).  The topology is described inside a module fixture, never at
import: only one process at a time may load the TPU library, so every
test worker must collect the same tests and only the worker that runs
this file loads it.  Sizes are small so each compile takes seconds; the
real sizes are what ``chip_smoke.py`` runs on the chip.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.sim.cluster import ServiceParams
from repro.sim.network import SETTINGS
from repro.sim.sweep import (SweepPoint, _closed_assemble,
                             _closed_pad, _closed_point_build,
                             _closed_round_fn, _compiled, _open_args,
                             _open_build, _shard_points, closed_grid)
from repro.sim.f64bits import to_bits
from repro.sim.vectorized import _DelayModel

from test_sweep_names import _op_names


@pytest.fixture(scope="module")
def topo():
    # the one supported installation (jax[tpu]==0.9.0) ships the TPU
    # compiler, so failing to describe the chip is a failure, not a skip
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                       sharding=sharding), tree)


def _closed_blocks(points, blocks, cache_keys=None):
    """Per-block padded device inputs of a closed grid, as ``_run_closed``
    lays them out, plus the round program's static arguments; with
    ``cache_keys``, leader page caches small enough to evict."""
    svc = ServiceParams() if cache_keys is None else \
        ServiceParams(page_cache_keys=cache_keys)
    dm = _DelayModel(SETTINGS["edge"], svc)
    built = [_closed_point_build(p, 0, dm, svc.page_cache_keys, 1)
             for p in points]
    assert any(b["evict"] for b in built) == (cache_keys is not None)
    blks = [_closed_assemble(built[d::blocks]) for d in range(blocks)]
    n = max(b["n"] for b in blks)
    R = max(len(b["rows"]) for b in blks)
    Ls = max(max(len(m) for m in b["rows"]) for b in blks)
    padded = [({k: to_bits(v) if v.dtype == np.float64 else v
                for k, v in f.items()}, a)
              for f, a in (_closed_pad(b, n, R, Ls) for b in blks)]
    max_hops = max(b["max_hops"] for b in built)
    static = (max_hops, "seq", False, 64, float(dm.seek), R, Ls)
    return padded, static


def test_closed_round_program_compiles_for_v5e(one_chip):
    """The closed loop's fixed-point program (default ``seq`` scan over
    IEEE bit patterns in int64, composite stable sort, segment_min) on
    one chip."""
    [(flat, aux)], static = _closed_blocks(
        closed_grid(threads=8, ops=64)[:4], 1)
    run = _closed_round_fn(*static)
    with jax.enable_x64(True):
        compiled = jax.jit(run).lower(_shapes(flat, one_chip),
                                      _shapes(aux, one_chip)).compile()
    n = flat["c_req"].shape[0]
    out = compiled.out_info
    assert out[0].shape == (n,) and out[0].dtype == jnp.int64
    assert out[4].shape == (6, n)
    # the chip's compiler keeps the scan grid's fill a gather
    assert not [name for name in _op_names(compiled.as_text(), "scatter")
                if "closed.to_grid" in name]


def test_evicting_closed_round_program_compiles_for_v5e(one_chip):
    """The same program with the exact LRU stack distance of a cache
    smaller than the leaders' working sets (two int32 sorts and a
    pairwise count in every round and the replay), and its third
    count, the capacity misses."""
    [(flat, aux)], static = _closed_blocks(
        closed_grid(threads=8, ops=64)[:4], 1, cache_keys=8)
    run = _closed_round_fn(*static, True, 8)
    with jax.enable_x64(True):
        compiled = jax.jit(run).lower(_shapes(flat, one_chip),
                                      _shapes(aux, one_chip)).compile()
    assert compiled.out_info[3].shape == (3,)   # rounds, changed, misses
    assert [name for name in _op_names(compiled.as_text(), "sort")
            if "closed.lru_stack" in name]


def test_open_program_compiles_for_v5e(one_chip):
    """The open loop's grid program with its default ``assoc`` scan in
    float64 (a window-reduction cumsum here never finishes compiling)."""
    grid = [SweepPoint(p_global=pg, rate=r, groups=g)
            for pg, r, g in [(0.0, 200.0, 3), (0.5, 800.0, 5)]]
    ob = _open_build(grid, duration=0.5, setting="edge", seed=0,
                     service=None, virtual_nodes=1)
    with jax.enable_x64(True):
        compiled = _compiled(ob["max_hops"], "assoc", False).lower(
            *_shapes(_open_args(ob), one_chip)).compile()
    R, Ls = ob["gidx"].shape
    assert compiled.out_info[2].shape == (R, Ls)


def test_sharded_closed_program_compiles_for_v5e_2x2(topo):
    """The point-sharded closed program on the 2x2 mesh: one block of
    points per chip, no cross-chip collectives in the fixed point."""
    devs = topo.devices[:4]
    padded, static = _closed_blocks(closed_grid(threads=8, ops=64)[:8], 4)
    flat = {k: np.stack([f[k] for f, _ in padded]) for k in padded[0][0]}
    aux = {k: np.stack([a[k] for _, a in padded]) for k in padded[0][1]}
    sharding = NamedSharding(Mesh(np.asarray(devs), ("pt",)),
                             PartitionSpec("pt"))
    exe = _shard_points(_closed_round_fn(*static), devs)
    with jax.enable_x64(True):
        compiled = exe.lower(_shapes(flat, sharding),
                             _shapes(aux, sharding)).compile()
    assert compiled.out_info[0].shape == flat["c_req"].shape
    assert "all-reduce" not in compiled.as_text()
