"""CPU tests of the trace reduction: on made-up traces, and on a small
trace of one closed sweep recorded on a TPU v5e."""
from __future__ import annotations

import gzip
from contextlib import nullcontext
from itertools import count
from pathlib import Path
from types import SimpleNamespace as NS

import numpy as np
import pytest

import harness
import scope_reduce as S
import trace_reduce as T

FIXTURE = Path(__file__).resolve().parent / "fixtures" / \
    "closed_sweep.xplane.pb.gz"
# its numbers as first reduced (seconds)
FIX_BUSY = 0.004171920999999926
FIX_WINDOW = 0.016420380000000005
FIX_GROUPS = {"other": 0.003090268999999958, "sort": 5.446799999999502e-05,
              "scan": 0.0010271839999999727}


@pytest.mark.parametrize("text,opcode", [
    ("%while.27 = (u32[200000]{0:T(1024)S(1)}, pred[]{:T(512)}) "
     "while((u32[2]) %tuple.1), condition=%c, body=%b", "while"),
    ("%fusion.721 = s32[200000]{0:T(1024)} fusion(s32[200000]{0:T(1024)"
     "S(1)} %fusion.720), kind=kCustom", "fusion"),
    ("%dynamic_update_slice.34 = u32[12122,40]{1,0:T(8,128)S(1)} "
     "dynamic-update-slice(u32[12122,40]{1,0:T(8,128)S(1)} %g)",
     "dynamic-update-slice"),
    ("%sort.3 = (u32[15000]{0}, s32[15000]{0}) sort(u32[15000]{0} %a, "
     "s32[15000]{0} %b), dimensions={0}, is_stable=true", "sort"),
    ("not an instruction", ""),
])
def test_hlo_opcode(text, opcode):
    assert T.hlo_opcode(text) == opcode


def test_union_and_gaps():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.75)]
    assert T.union_length(iv) == 3.0
    assert T.gaps(iv, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0), (4.0, 5.0)]
    assert T.union_length([]) == 0.0
    assert T.gaps([], 0.0, 1.0) == [(0.0, 1.0)]


def _ev(name, start_s, end_s):
    return NS(name=name, start_ns=start_s * 1e9, end_ns=end_s * 1e9)


def _op(name, opcode="fusion"):
    return f"%{name} = u32[8]{{0}} {opcode}(u32[8]{{0}} %x)"


def _profile(dropped=0):
    """One chip: a program running 0.0-0.8 s inside a sweep span of
    0.0-1.0 s; a loop op (the scan's step) runs 300 times, a sort once,
    a fusion once, all inside one while event."""
    ops = [_ev("%while.1 = (u32[8]{0}) while((u32[8]{0}) %t), body=%b",
               0.0, 0.8),
           _ev(_op("sort.1", "sort"), 0.0, 0.1),
           _ev(_op("fusion.9"), 0.1, 0.2)]
    ops += [_ev(_op("select_fusion.3"), 0.2 + i * 0.002,
                0.2 + (i + 1) * 0.002) for i in range(300)]
    device = NS(name="/device:TPU:0", stats=[("dropped_traces", dropped)],
                lines=[NS(name="XLA Ops", events=ops),
                       NS(name="XLA Modules",
                          events=[_ev("jit_run(1)", 0.0, 0.8)])])
    host = NS(name="/host:CPU", stats=[], lines=[NS(name="python3", events=[
        _ev("sweep", 0.0, 1.0), _ev("np.asarray(jax.Array)", 0.85, 0.95)])])
    return NS(planes=[device, host])


def test_reduce_made_up_trace():
    red = T.reduce_profile(_profile(), n_chips=1)
    assert red["busy_s"] == [pytest.approx(0.8)]
    assert red["window_s"] == pytest.approx(1.0)
    assert red["groups"] == pytest.approx(
        {"sort": 0.1, "other": 0.1, "scan": 0.6})
    assert red["runs"] == {"scan": 300.0, "sort": 1.0}
    top = red["breakdown"]["device_ops"]
    assert top[0][0] == _op("select_fusion.3") and \
        top[0][1] == pytest.approx(0.6)
    assert all("while" not in name for name, _ in top)
    assert red["breakdown"]["idle_gaps"] == [
        ["np.asarray(jax.Array)", pytest.approx(0.2)]]


def test_dropped_events_are_refused():
    with pytest.raises(T.TraceError, match="dropped"):
        T.reduce_profile(_profile(dropped=5), n_chips=1)


def test_missing_chips_are_refused():
    with pytest.raises(T.TraceError, match="1 chip"):
        T.reduce_profile(_profile(), n_chips=4)


def test_reduce_recorded_closed_sweep():
    """One closed sweep (3 groups x 60 threads, 900 ops) traced on a TPU
    v5e through the harness."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_serialized_xspace(
        gzip.decompress(FIXTURE.read_bytes()))
    red = T.reduce_profile(pd, n_chips=1)
    assert red["busy_s"] == [pytest.approx(FIX_BUSY, rel=1e-9)]
    assert red["window_s"] == pytest.approx(FIX_WINDOW, rel=1e-9)
    assert red["groups"] == pytest.approx(FIX_GROUPS, rel=1e-9)
    assert red["busy_s"][0] < red["window_s"]
    # leaf ops on one core do not overlap: the groups add up to busy
    assert sum(red["groups"].values()) == pytest.approx(red["busy_s"][0],
                                                        rel=1e-6)
    top = red["breakdown"]["device_ops"]
    assert len(top) == T.TOP and top[0][1] >= top[-1][1]
    assert all(T.hlo_opcode(name) not in T.CONTROL_FLOW for name, _ in top)
    assert red["breakdown"]["idle_gaps"][0] == [
        "TpuClient::LinearizeIntoImpl", pytest.approx(0.008448426)]
    assert T.dropped(pd) == 0
    # 10 rounds over a longest queue of 459 ops: the scan's step ran
    # 10 x 459 times, the sort once a round and once in the replay
    assert red["runs"] == {"scan": 4590.0, "sort": 11.0}


class _Sweep:
    """What ``run_sweep`` returns on the device path, made up."""
    info = dict(path="device", device_s=0.5)
    walltime_s = 0.6
    columns = {"ops": np.array([10.0])}

    def __len__(self):
        return 1


def test_a_stretch_that_dropped_events_is_traced_again(monkeypatch,
                                                       tmp_path):
    calls = []

    class System:
        def sweep(self):
            calls.append("sweep")
            return _Sweep()

    losses = iter([7, 3, 0])
    attempts = count(1)
    fake_jax = NS(profiler=NS(
        ProfileOptions=NS, TraceAnnotation=lambda name: nullcontext(),
        start_trace=lambda *a, **k: calls.append("start"),
        stop_trace=lambda: calls.append("stop")))
    trace = tmp_path / "x.xplane.pb"
    trace.write_bytes(b"")
    monkeypatch.setattr(T, "newest_trace", lambda d: str(trace))
    monkeypatch.setattr(T, "load", lambda path: f"profile {next(attempts)}")
    monkeypatch.setattr(T, "dropped", lambda pd: next(losses))
    monkeypatch.setattr(T, "reduce_profile", lambda pd, n_chips: dict(
        busy_s=[0.25], window_s=1.0, groups={}, runs={}, breakdown={}))
    scoped = []
    monkeypatch.setattr(S, "reduce_scopes", lambda pd, names, n_chips: (
        scoped.append((pd, names, n_chips)) or {"unscoped": 0.25}))
    monkeypatch.setattr(harness, "TRACE_SECONDS", 0.0)
    out = harness.trace_window(fake_jax, System(), [None], tmp_path / "t")
    assert calls == ["start", "sweep", "stop"] * 3
    assert len(out["results"]) == 3 and out["trace"]["sweeps"] == 1
    # the scopes of the accepted attempt alone
    assert scoped == [("profile 3", {}, 1)]
    assert out["trace"]["scopes"] == {"unscoped": 0.25}
    losses = iter([1, 1, 1])
    monkeypatch.setattr(T, "dropped", lambda pd: next(losses))
    with pytest.raises(harness.HarnessError, match="dropped in 3 tries"):
        harness.trace_window(fake_jax, System(), [None], tmp_path / "t")


def test_window_hands_the_programs_spans_and_counters_to_the_readers():
    """Each sweep keeps ``info``'s spans and every number under its own
    name, a counter that no reader knows yet among them; the harness's
    own timings are kept as they were."""
    spans = {"run_sweep.build": 0.01, "run_sweep.fold": 0.004}

    class Sweep(_Sweep):
        info = dict(path="device", device_s=0.5, rounds=7, changed=12,
                    new_counter=3.5, ops=-1, spans=spans, note="text")

    win = harness.run_window(NS(sweep=Sweep), 0.0,
                             lambda name: nullcontext())
    [sweep] = win["sweeps"]
    assert sweep.pop("wall_s") >= 0.0
    assert sweep == dict(device_s=0.5, rounds=7, changed=12,
                         new_counter=3.5, spans=spans, walltime_s=0.6,
                         ops=10)
    # a program that names nothing gives the readers nothing
    [bare] = harness.run_window(NS(sweep=_Sweep), 0.0,
                                lambda name: nullcontext())["sweeps"]
    assert bare["spans"] is None and "changed" not in bare
    assert harness.load_reader("build_s")(dict(sweeps=[bare])) is None
