"""What ``run_sweep`` names and counts about its own work: the closed
round's named scopes in the compiled program, the host spans in
``info["spans"]`` on both loops, and the closed fixed point's counters
(``changed``, ``op_rounds``, ``grid_slots``, ``capacity_misses``)
against a count of the rounds of the same round map in numpy
(:func:`closed_rounds_host`)."""
import re
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np
import pytest

from repro.obs import span
from repro.sim import sweep
from repro.sim.cluster import ServiceParams
from repro.sim.f64bits import to_bits
from repro.sim.network import SETTINGS
from repro.sim.sweep import SweepPoint, closed_grid, run_sweep
from repro.sim.vectorized import (_DelayModel, arrival_chain,
                                  completion_chain, lru_hit_mask)

STAGES = ("closed.arrival", "closed.order", "closed.lru", "closed.to_grid",
          "closed.depart", "closed.from_grid", "closed.completion",
          "closed.converge", "closed.replay")
DEVICE_SPANS = ("run_sweep.build", "run_sweep.dispatch", "run_sweep.wait",
                "run_sweep.fold")
# contended points (shared keys, global ops queueing at remote leaders),
# so that queue orders change between rounds and ops change more than once
CONTENDED = [SweepPoint(p_global=pg, groups=g, n_records=nr, threads=t,
                        ops=o, distribution=dist)
             for pg, g, nr, t, o, dist in [
                 (0.5, 3, 2_500, 8, 64, "zipfian"),
                 (1.0, 4, 10_000, 6, 48, "uniform"),
                 (0.25, 3, 2_500, 8, 64, "latest")]]


def closed_rounds_host(built: Sequence[dict], capacity: int, seek: float,
                       max_hops: int, max_rounds: int,
                       seen: Optional[list] = None
                       ) -> Tuple[List[np.ndarray], List[np.ndarray],
                                  List[np.ndarray]]:
    """The closed round map in numpy float64, point by point, as the
    reference of the device program: same rounds and expressions, page
    penalties from the exact LRU replay
    (:func:`~repro.sim.vectorized.lru_hit_mask`) of each round's queue
    order, and a sequential departure recurrence in the engine's float
    order.  ``seen`` collects every round's completions.

    Returns per point the completions, the start times and the span
    pieces ``(b_request, b_route, arrival, start, departure,
    b_replicate)`` of the round that found the fixed point."""
    comp_pt, t0_pt, pieces_pt = [], [], []
    for b in built:
        flat, n = b["flat"], b["n"]
        comp = np.full(n, np.inf)
        for _ in range(max_rounds):
            t0 = np.where(flat["first"], 0.0, comp[flat["pred"]])
            cuts: list = []
            arr = arrival_chain(np, t0, flat["c_req"], flat["f_req"],
                                flat["sg_req"], flat["h_req"],
                                flat["lf"], flat["glob"], flat["hops"],
                                max_hops, cuts=cuts)
            dep = np.zeros(n)
            start = np.zeros(n)
            for m in b["rows"]:
                order = m[np.argsort(arr[m], kind="stable")]
                hitm = lru_hit_mask(flat["key"][order], capacity)
                svc = flat["svc_base"][order] + np.where(hitm, 0.0, seek)
                # sequential recurrence in the engine's exact float
                # order: a closed-form scan reassociates, and its ulp
                # drift can flip near-tied queue orders across rounds
                start_o, dep_o, d = [], [], -np.inf
                for a_j, s_j in zip(arr[order].tolist(), svc.tolist()):
                    st = a_j if a_j > d else d
                    d = st + s_j
                    start_o.append(st)
                    dep_o.append(d)
                start[order] = start_o
                dep[order] = dep_o
            ccuts: list = []
            new = completion_chain(np, dep, flat["q_ri"],
                                   flat["sg_resp"], flat["g_resp"],
                                   flat["f_resp"], flat["c_resp"],
                                   flat["lf"], flat["glob"],
                                   flat["remote"], cuts=ccuts)
            if seen is not None:
                seen.append(new)
            if np.array_equal(new, comp):
                break
            comp = new
        else:
            raise RuntimeError(f"no fixed point in {max_rounds} rounds")
        comp_pt.append(comp)
        t0_pt.append(t0)
        pieces_pt.append(np.stack([cuts[0], cuts[1], arr, start, dep,
                                   ccuts[0]]))
    return comp_pt, t0_pt, pieces_pt


def _block(points, seed=0, capacity=10_000):
    dm = _DelayModel(SETTINGS["edge"], ServiceParams())
    built = [sweep._closed_point_build(p, seed, dm, capacity, 1)
             for p in points]
    blk = sweep._closed_assemble(built)
    R = len(blk["rows"])
    Ls = max(len(m) for m in blk["rows"])
    flat, aux = sweep._closed_pad(blk, blk["n"] + 5, R, Ls)   # 5 pad ops
    flat = {k: to_bits(v) if v.dtype == np.float64 else v
            for k, v in flat.items()}
    static = (max(b["max_hops"] for b in built), "seq", False, 64,
              float(dm.seek), R, Ls)
    return built, flat, aux, static, dm


def _closed_program_text(capacity=None):
    """The compiled closed program of a small grid: the cache-resident
    one without ``capacity``, else the evicting one at that cache size."""
    pts = closed_grid(threads=4, ops=16)[:2]
    built, flat, aux, static, _ = _block(pts, capacity=capacity or 10_000)
    evict = capacity is not None
    assert any(b["evict"] for b in built) == evict
    with jax.enable_x64(True):
        return jax.jit(sweep._closed_round_fn(
            *static, evict, capacity or 0)).lower(
                flat, aux).compile().as_text()


def _op_names(text, opcode):
    """op_name of every ``opcode`` instruction of a compiled program."""
    return [m.group(1) if m else "" for line in text.splitlines()
            if re.search(rf"\s{opcode}\(", line)
            for m in [re.search(r'op_name="([^"]*)"', line)]]


def test_compiled_closed_program_names_every_stage():
    text = _closed_program_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    scopes = {part for name in names for part in name.split("/")
              if part.startswith("closed.")}
    assert scopes == set(STAGES)
    # the round's stages run inside the fixed point's loop and again in
    # the replay; no scope may take the harness's window span's name
    assert any("/while/body/closed.to_grid/" in n for n in names)
    assert any("closed.replay/closed.to_grid/" in n for n in names)
    assert not any(part == "sweep" for n in names for part in n.split("/"))


def test_grid_fill_gathers_and_never_scatters():
    """The scan grid is filled by gathers through the static slot ->
    position map, in the loop body and in the replay: no scatter runs
    under ``closed.to_grid``."""
    text = _closed_program_text()
    gathers = _op_names(text, "gather")
    for where in ("/while/body/closed.to_grid/",
                  "closed.replay/closed.to_grid/"):
        assert sum(where in n for n in gathers) == 2, where
    assert not [n for n in _op_names(text, "scatter")
                if "closed.to_grid" in n]


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_spans_cover_the_device_call(loop):
    pts = ([SweepPoint(p_global=0.5, rate=150.0)] if loop == "open"
           else CONTENDED[:1])
    res = run_sweep(pts, loop=loop, seed=1, duration=0.5)
    info = res.info
    assert info["path"] == "device"
    assert set(info["spans"]) == set(DEVICE_SPANS)
    assert all(v >= 0.0 for v in info["spans"].values())
    inner = info["spans"]["run_sweep.dispatch"] + \
        info["spans"]["run_sweep.wait"]
    assert inner <= info["device_s"] <= res.walltime_s
    assert sum(info["spans"].values()) <= res.walltime_s


def test_host_fixed_point_spans():
    """A grid that evicts runs on the device path too, with the device
    path's spans: no host fixed point is left."""
    svc = ServiceParams(page_cache_keys=4)
    res = run_sweep(CONTENDED[:1], loop="closed", seed=0, service=svc)
    info = res.info
    assert info["path"] == "device"
    assert set(info["spans"]) == set(DEVICE_SPANS)
    assert sum(info["spans"].values()) <= res.walltime_s
    assert info["capacity_misses"] > 0


def test_span_sums_a_name_entered_twice():
    into = {}
    for _ in range(2):
        with span("x", into):
            pass
    assert set(into) == {"x"} and into["x"] >= 0.0
    with pytest.raises(ValueError), span("y", into):
        raise ValueError("the span still closes")
    assert "y" in into


def test_changed_counts_the_host_fixed_points_changes():
    """``changed`` is the sum over rounds of the ops whose completion
    moved, pad ops left out.  The numpy round map runs the same rounds
    in float64 (no eviction here, so its LRU replay is the device's
    seen-before mask): count its changes round by round."""
    built, flat, aux, static, dm = _block(CONTENDED)
    n_real = sum(b["n"] for b in built)
    with jax.enable_x64(True):
        _, _, done, (rounds, changed), _ = jax.device_get(
            jax.jit(sweep._closed_round_fn(*static))(flat, aux))
    assert bool(done)

    seen: list = []
    host_rounds, want = [], 0
    for b in built:
        seen.clear()
        closed_rounds_host([b], 10_000, float(dm.seek), static[0], 64,
                           seen=seen)
        prev = np.full(b["n"], np.inf)
        for new in seen:
            want += int(np.count_nonzero(new != prev))
            prev = new
        host_rounds.append(len(seen))
    assert int(changed) == want
    assert int(rounds) == max(host_rounds)
    assert n_real < want < int(rounds) * n_real


def test_counters_in_info_and_columns_unchanged():
    res = run_sweep(CONTENDED, loop="closed", seed=0)
    info = res.info
    n_real = int(res.columns["ops"].sum())
    assert info["op_rounds"] == info["rounds"] * n_real
    assert n_real <= info["changed"] <= info["op_rounds"]
    _, flat, aux, static, _ = _block(CONTENDED)
    R, Ls = static[-2:]
    assert info["grid_slots"] == R * Ls >= n_real
    # the device fixed point folds to the very bits of the host one
    dm = _DelayModel(SETTINGS["edge"], ServiceParams())
    built = [sweep._closed_point_build(p, 0, dm, 10_000, 1)
             for p in CONTENDED]
    comp, t0, pieces = closed_rounds_host(
        built, 10_000, float(dm.seek), static[0], 64)
    want = sweep._closed_fold(CONTENDED, built, (95.0, 99.0), comp, t0,
                              pieces)
    assert set(want) == set(res.columns)
    for k, v in want.items():
        assert np.array_equal(v, res.columns[k], equal_nan=True), k


def test_lru_stack_stage_only_where_a_cache_can_evict():
    """The stack-distance stage is compiled in only for a grid whose
    leaders can evict: the program of a cache-resident grid has no
    ``closed.lru_stack`` op, and the evicting one runs it in the loop
    body and in the replay."""
    names = set(re.findall(r'op_name="([^"]*)"', _closed_program_text()))
    assert not [n for n in names if "closed.lru_stack" in n]
    names = set(re.findall(r'op_name="([^"]*)"',
                           _closed_program_text(capacity=4)))
    assert any("/while/body/closed.lru_stack/" in n for n in names)
    assert any("closed.replay/closed.lru_stack/" in n for n in names)


def _stack_misses(keys: np.ndarray, capacity: int) -> int:
    """Ops of one queue that miss although their key came before."""
    seen = np.zeros(len(keys), bool)
    _, first = np.unique(keys, return_index=True)
    seen[np.setdiff1d(np.arange(len(keys)), first)] = True
    return int(np.count_nonzero(seen & ~lru_hit_mask(keys, capacity)))


@pytest.mark.parametrize("capacity", [4, 16])
def test_capacity_misses_count_the_lru_replays_misses(capacity):
    """``capacity_misses`` is the count of ops that miss although their
    key was served before in their queue, read from ``lru_hit_mask`` on
    the numpy round map's converged queue orders."""
    svc = ServiceParams(page_cache_keys=capacity)
    res = run_sweep(CONTENDED, loop="closed", seed=0, service=svc)
    dm = _DelayModel(SETTINGS["edge"], svc)
    built = [sweep._closed_point_build(p, 0, dm, capacity, 1)
             for p in CONTENDED]
    assert any(b["evict"] for b in built)
    _, _, pieces = closed_rounds_host(
        built, capacity, float(dm.seek),
        max(b["max_hops"] for b in built), 128)
    want = 0
    for b, pc in zip(built, pieces):
        for m in b["rows"]:
            order = m[np.argsort(pc[2][m], kind="stable")]
            want += _stack_misses(b["flat"]["key"][order], capacity)
    assert res.info["path"] == "device"
    assert res.info["capacity_misses"] == want > 0


def test_capacity_misses_read_zero_without_eviction():
    res = run_sweep(CONTENDED, loop="closed", seed=0)
    assert res.info["capacity_misses"] == 0
