"""CPU tests of what the benchmark reads by name: the ``tf_op`` decoder,
the closed round's scopes in a trace, and the readers of the program's
spans, scopes and counters, on made-up inputs and on two small traces
of one closed sweep recorded on a TPU v5e (one before the program named
its stages, one after)."""
from __future__ import annotations

from pathlib import Path

import pytest

import harness
import scope_reduce as S
import trace_reduce as T
import xspace as X

FIXTURES = Path(__file__).resolve().parent / "fixtures"
OLD = FIXTURES / "closed_sweep.xplane.pb.gz"
SCOPED = FIXTURES / "closed_sweep_scoped.xplane.pb.gz"
SCOPE_READERS = {"arrival_s": "closed.arrival", "sort_s": "closed.order",
                 "lru_s": "closed.lru", "to_grid_s": "closed.to_grid",
                 "scan_s": "closed.depart",
                 "from_grid_s": "closed.from_grid",
                 "completion_s": "closed.completion"}
PROGRAM_READERS = ("build_s", "fold_s", "round_useful_share", "grid_fill")
STAGES = set(SCOPE_READERS.values()) | {"closed.converge"}


# ------------------------------------------------- the wire format decoder
def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _len(field: int, body: bytes) -> bytes:
    return _varint(field << 3 | 2) + _varint(len(body)) + body


def _int(field: int, n: int) -> bytes:
    return _varint(field << 3) + _varint(n)


def _entry(field: int, key: int, value: bytes) -> bytes:
    return _len(field, _int(1, key) + _len(2, value))


def test_decoder_reads_str_and_ref_values_and_skips_the_rest():
    stat_meta = (_entry(5, 7, _int(1, 7) + _len(2, b"tf_op"))
                 + _entry(5, 8, _int(1, 8) + _len(2, b"flops"))
                 + _entry(5, 9, _int(1, 9) + _len(2, b"jit(f)/closed.lru/"
                                                    b"gather:")))
    by_str = _entry(4, 1, _int(1, 1) + _len(2, b"%fusion.1 = u32[8]")
                    + _len(5, _int(1, 8) + _int(3, 12))
                    + _len(5, _int(1, 7) + _len(5, b"jit(f)/while/body/max:")))
    by_ref = _entry(4, 2, _int(1, 2) + _len(2, b"%gather.2 = u32[8]")
                    + _len(5, _int(1, 7) + _int(7, 9)))
    no_op = _entry(4, 3, _int(1, 3) + _len(2, b"%copy.3"))
    fixed = _varint(9 << 3 | 1) + bytes(8) + _varint(10 << 3 | 5) + bytes(4)
    lines = _len(3, _len(4, _int(1, 1) + _int(3, 5)))   # events: skipped
    plane = (_int(1, 1) + _len(2, b"/device:TPU:0") + lines + stat_meta
             + by_str + by_ref + no_op + fixed)
    host = _len(1, _int(1, 2) + _len(2, b"/host:CPU") + lines)
    space = _len(1, plane) + host + _len(4, b"hostname")
    assert X.tf_ops(space) == {"/device:TPU:0": {
        "%fusion.1 = u32[8]": "jit(f)/while/body/max:",
        "%gather.2 = u32[8]": "jit(f)/closed.lru/gather:"}}


def test_decoder_reads_the_recorded_trace():
    import gzip
    names = X.tf_ops(gzip.decompress(OLD.read_bytes()))
    assert list(names) == ["/device:TPU:0"]
    ops = names["/device:TPU:0"]
    [(text, op)] = [(k, v) for k, v in ops.items()
                    if k.startswith("%bitcast-convert.3370 ")]
    assert op == "jit(run)/while/body/max:"
    assert text.endswith("bitcast-convert(u32[900]{0:T(1024)S(1)} "
                         "%custom-call.4)")
    assert not any("closed." in v for v in ops.values())


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(run)/while/body/closed.to_grid/scatter:", "closed.to_grid"),
    ("jit(run)/closed.replay/closed.depart/while/body/closed_call/max:",
     "closed.depart"),
    ("jit(run)/closed.replay/concatenate:", "closed.replay"),
    ("jit(run)/while/body/max:", "unscoped"),
    ("", "unscoped"),
])
def test_innermost_scope(tf_op, scope):
    assert S.scope_of(tf_op) == scope


# ---------------------------------------------------------------- readers
def _run(trace, sweeps=None):
    sweeps = sweeps or [dict(walltime_s=2.0, device_s=1.5, rounds=21,
                             ops=900)]
    return dict(sweeps=sweeps, trace=trace, loop="closed", rounds=21,
                queue_len=459)


def test_readers_read_nothing_from_the_unscoped_trace():
    pd, names = S.load(OLD)
    red = T.reduce_profile(pd, n_chips=1)
    red.update(scopes=S.reduce_scopes(pd, names, n_chips=1), sweeps=1)
    assert red["scopes"] == {"unscoped": pytest.approx(red["busy_s"][0],
                                                       rel=1e-12)}
    run = _run(red)
    for name in list(SCOPE_READERS) + list(PROGRAM_READERS):
        assert harness.load_reader(name)(run) is None, name
    # that program named no scopes, so the sort and the scan read nothing
    # there, even where the opcode groups have their seconds
    assert red["groups"]["sort"] > 0 and red["groups"]["scan"] > 0
    assert harness.load_reader("sort_s")(dict(run, rounds=10)) is None


def test_readers_of_the_programs_spans_and_counters():
    sweeps = [dict(walltime_s=2.0, device_s=1.5, rounds=102, ops=45_000,
                   spans={"run_sweep.build": 0.01, "run_sweep.fold": 0.004},
                   changed=687_749, op_rounds=4_590_000, grid_slots=88_440),
              dict(walltime_s=2.0, device_s=1.5, rounds=102, ops=45_000,
                   spans={"run_sweep.build": 0.03, "run_sweep.fold": 0.002},
                   changed=687_749, op_rounds=4_590_000, grid_slots=88_440)]
    run = _run(dict(busy_s=[1.0], window_s=1.0, sweeps=2, groups={},
                    runs={}, scopes={"closed.lru": 0.5}), sweeps)
    read = {n: harness.load_reader(n)(run) for n in PROGRAM_READERS}
    assert read == pytest.approx(dict(
        build_s=0.02, fold_s=0.003,
        round_useful_share=100.0 * 687_749 / 4_590_000,
        grid_fill=100.0 * 45_000 / 88_440), rel=1e-12)
    assert harness.load_reader("lru_s")(run) == 0.25
    assert harness.load_reader("sort_s")(run) is None
    # one sweep of the parent's program, which reports none of them
    del sweeps[1]["spans"], sweeps[1]["changed"], sweeps[1]["grid_slots"]
    for name in PROGRAM_READERS:
        assert harness.load_reader(name)(run) is None, name


# ---------------------------------------------------- the scoped recording
@pytest.fixture(scope="module")
def scoped():
    pd, names = S.load(SCOPED)
    red = T.reduce_profile(pd, n_chips=1)
    return pd, red, S.reduce_scopes(pd, names, n_chips=1)


def test_scoped_trace_adds_up_to_busy(scoped):
    _, red, scopes = scoped
    assert STAGES <= set(scopes)
    assert set(scopes) <= STAGES | {"closed.replay", "unscoped"}
    assert sum(scopes.values()) == pytest.approx(red["busy_s"][0],
                                                 rel=1e-6)
    assert scopes.get("unscoped", 0.0) < 0.02 * red["busy_s"][0]


def test_scoped_trace_agrees_with_the_opcode_groups(scoped):
    _, red, scopes = scoped
    assert red["groups"]["sort"] <= scopes["closed.order"] \
        <= 1.05 * red["groups"]["sort"]
    assert scopes["closed.depart"] >= red["groups"]["scan"]


def test_scoped_trace_names_its_idle_gaps(scoped):
    _, red, _ = scoped
    for name, seconds in red["breakdown"]["idle_gaps"]:
        assert not (name == "run_sweep host code" and seconds > 1e-3)
    names = {n for n, _ in red["breakdown"]["idle_gaps"]}
    assert names & {"run_sweep.build", "run_sweep.dispatch",
                    "run_sweep.wait", "run_sweep.fold"}


def test_scoped_trace_readers_read_every_stage(scoped):
    _, red, scopes = scoped
    run = _run(dict(red, scopes=scopes, sweeps=1))
    for name, scope in SCOPE_READERS.items():
        assert harness.load_reader(name)(run) == scopes[scope] > 0, name
