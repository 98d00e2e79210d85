"""What ``run_sweep`` names and counts about its own work: the closed
round's named scopes in the compiled program, the host spans in
``info["spans"]`` on both loops, and the closed fixed point's counters
(``changed``, ``op_rounds``, ``grid_slots``) against a count of the host
fixed point's rounds."""
import re

import jax
import numpy as np
import pytest

from repro.obs import span
from repro.sim import sweep
from repro.sim.cluster import ServiceParams
from repro.sim.f64bits import to_bits
from repro.sim.network import SETTINGS
from repro.sim.sweep import SweepPoint, closed_grid, run_sweep
from repro.sim.vectorized import _DelayModel

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


def _block(points, seed=0):
    dm = _DelayModel(SETTINGS["edge"], ServiceParams())
    built = [sweep._closed_point_build(p, seed, dm, 10_000, 1)
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


def _closed_program_text():
    _, flat, aux, static, _ = _block(closed_grid(threads=4, ops=16)[:2])
    with jax.enable_x64(True):
        return jax.jit(sweep._closed_round_fn(*static)).lower(
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
    svc = ServiceParams(page_cache_keys=4)       # evicts: the host path
    res = run_sweep(CONTENDED[:1], loop="closed", seed=0, service=svc)
    assert res.info["path"] == "host"
    assert set(res.info["spans"]) == {"run_sweep.build",
                                      "run_sweep.host_rounds",
                                      "run_sweep.fold"}
    assert sum(res.info["spans"].values()) <= res.walltime_s


def test_span_sums_a_name_entered_twice():
    into = {}
    for _ in range(2):
        with span("x", into):
            pass
    assert set(into) == {"x"} and into["x"] >= 0.0
    with pytest.raises(ValueError), span("y", into):
        raise ValueError("the span still closes")
    assert "y" in into


def test_changed_counts_the_host_fixed_points_changes(monkeypatch):
    """``changed`` is the sum over rounds of the ops whose completion
    moved, pad ops left out.  The host fixed point runs the same round
    map in numpy float64 (no eviction here, so its LRU replay is the
    device's seen-before mask): count its changes round by round."""
    built, flat, aux, static, dm = _block(CONTENDED)
    n_real = sum(b["n"] for b in built)
    with jax.enable_x64(True):
        _, _, done, (rounds, changed), _ = jax.device_get(
            jax.jit(sweep._closed_round_fn(*static))(flat, aux))
    assert bool(done)

    seen = []
    chain = sweep.completion_chain

    def record(xp, *args, **kw):
        out = chain(xp, *args, **kw)
        if xp is np:
            seen.append(out)
        return out

    monkeypatch.setattr(sweep, "completion_chain", record)
    host_rounds, want = [], 0
    for b in built:
        seen.clear()
        sweep._closed_rounds_host([b], 10_000, float(dm.seek),
                                  static[0], 64)
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
    comp, t0, pieces = sweep._closed_rounds_host(
        built, 10_000, float(dm.seek), static[0], 64)
    want = sweep._closed_fold(CONTENDED, built, (95.0, 99.0), comp, t0,
                              pieces)
    assert set(want) == set(res.columns)
    for k, v in want.items():
        assert np.array_equal(v, res.columns[k], equal_nan=True), k
