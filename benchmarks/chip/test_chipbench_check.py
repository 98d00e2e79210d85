"""CPU tests of the check that decides ``correct``: sound runs pass, and
the control and each fault the cells can have come out not correct.

Each run drives the harness end to end on small cells (the look for a
chip skipped): set-up, window, check.  The faults are planted in the
program underneath ``run_sweep``:

* a round that returns its state unchanged (closed fixed point);
* half of the batch left out, the means taken over the rest;
* an answer altered where it is produced (one operation's completion,
  or one row's latency sum, 1 ms late).

The cells run on one chip each, so there is no exchange between chips
to leave out.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import control
import harness
import reference
from repro.sim import f64bits, sweep


def measure(cell: str, make_system=harness.System,
            seed: int = 2147483651) -> dict:
    args = argparse.Namespace(workload=cell, seed=seed, seconds=0.2,
                              trace=0)
    return harness.measure(args, time.perf_counter(), make_system)


@pytest.fixture
def fresh_programs():
    """Programs built while a fault is planted are thrown away after."""
    sweep._closed_exe.cache_clear()
    sweep._closed_round_fn.cache_clear()
    sweep._compiled.cache_clear()
    yield
    sweep._closed_exe.cache_clear()
    sweep._closed_round_fn.cache_clear()
    sweep._compiled.cache_clear()


@pytest.mark.parametrize("cell", ["tiny-closed", "tiny-open"])
def test_sound_run_is_correct(checkout, cell):
    out = measure(cell)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert out["check"]["worst_rel_gap"]["value"] <= harness.GAP_LIMIT
    assert list(out["check"])[-1] == "worst_rel_gap"
    assert set(out["metrics"]) >= {"sim_ops_per_s", "setup_s"}


@pytest.mark.parametrize("cell", ["tiny-closed", "tiny-open"])
def test_sound_runs_are_correct_on_every_workload_seed(checkout, cell):
    _, _, config, traffic = harness.load_cell(cell)
    run_seed = {}
    for s in range(2147483648, 2147483648 + 64):
        run_seed.setdefault(reference.request(config, traffic, s)[1], s)
    assert set(run_seed) == set(traffic["workload_seeds"])
    for wl in sorted(run_seed):
        out = measure(cell, seed=run_seed[wl])
        assert out["correct"] is True, (wl, out["check"])


@pytest.mark.parametrize("cell", ["tiny-closed", "tiny-open"])
def test_float32_control_is_not_correct(checkout, cell):
    out = measure(cell, control.ControlSystem)
    assert out["correct"] is False
    assert out["check"]["worst_rel_gap"]["value"] > harness.GAP_LIMIT


def _unchanged_state(monkeypatch):
    def while_loop(cond, body, init):
        comp, _, _ = init
        return comp, jnp.asarray(True), jnp.asarray(1)
    monkeypatch.setattr(jax.lax, "while_loop", while_loop)


def _half_batch(monkeypatch):
    plan, segments = sweep.closed_loop_plan, sweep._open_loop_segments

    def half_plan(*a, **k):
        return plan(*a, **k)[::2]            # every other worker thread

    def half_segments(*a, **k):
        return [(code, wl) + tuple(x[:len(x) // 2] for x in cols)
                for code, wl, *cols in segments(*a, **k)]
    monkeypatch.setattr(sweep, "closed_loop_plan", half_plan)
    monkeypatch.setattr(sweep, "_open_loop_segments", half_segments)


def _altered_answer(monkeypatch):
    device_get = jax.device_get

    def altered(x):
        out = [np.array(o) for o in device_get(x)]
        if len(out) == 5:       # closed: completions as binary64 bits
            comp = out[0].reshape(-1)
            comp[0] = f64bits.to_bits(f64bits.from_bits(comp[:1]) + 1e-3)[0]
        else:                   # open: per-row category latency sums
            cnt4, sum4 = out[0], out[1]
            r, c = np.argwhere(cnt4 > 0)[0]
            sum4[r, c] += 1e-3
        return tuple(out)
    monkeypatch.setattr(jax, "device_get", altered)


FAULTS = {"unchanged_state": _unchanged_state, "half_batch": _half_batch,
          "altered_answer": _altered_answer}


@pytest.mark.parametrize("cell,fault", [
    ("tiny-closed", "unchanged_state"),
    ("tiny-closed", "half_batch"),
    ("tiny-open", "half_batch"),
    ("tiny-closed", "altered_answer"),
    ("tiny-open", "altered_answer"),
])
def test_planted_fault_is_not_correct(checkout, fresh_programs,
                                      monkeypatch, cell, fault):
    FAULTS[fault](monkeypatch)
    out = measure(cell)
    assert out["correct"] is False
    assert out["check"]["worst_rel_gap"]["value"] > harness.GAP_LIMIT


def test_control_cli_reports_a_gap_per_seed(checkout, capsys):
    assert control.main(["--workload", "tiny-closed", "--seeds", "3",
                         "2147483650"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == 3
    assert '"control_min_gap"' in lines[-1]
    assert all('"correct": false' in line for line in lines[:2])
