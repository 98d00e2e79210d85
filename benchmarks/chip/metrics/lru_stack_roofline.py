"""Share of its HBM roofline that the closed round's exact LRU stack
distance (scope ``closed.lru_stack``) reaches, in percent.

Bytes: the least the stage must move, whatever implements it (see
:func:`least_bytes`).  Time: the stage's device seconds per traced sweep
(``lru_stack_s``).  Peak: ``hbm_bytes_per_s`` of the device in
``peaks.json``.  Nothing where the trace has no such scope, the traced
sweeps' rounds differ, or the device is not in ``peaks.json``."""
import json
from pathlib import Path

import scope_reduce

PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"
# per real op and round: its key (int64) and its row (int32) read once,
# its page penalty (an int64 time) written once
BYTES_PER_OP_ROUND = 8 + 4 + 8


def least_bytes(ops: int, rounds: int) -> int:
    """HBM bytes the stage must move in one sweep: every real op in each
    of the fixed point's rounds and in the replay of the last one."""
    return BYTES_PER_OP_ROUND * ops * (rounds + 1)


def device_kind(run) -> str:
    """The kind the run names, else that of the device JAX runs on."""
    if "device_kind" in run:
        return run["device_kind"]
    import jax
    return jax.devices()[0].device_kind


def read(run):
    seconds = scope_reduce.per_sweep(run, "closed.lru_stack")
    ops = [s["ops"] for s in run["sweeps"]]
    if not seconds or run.get("rounds") is None or len(set(ops)) != 1:
        return None
    peak = json.loads(PEAKS.read_text())["devices"].get(device_kind(run))
    if peak is None:
        return None
    need = least_bytes(ops[0], run["rounds"]) / peak["hbm_bytes_per_s"]
    return 100.0 * need / seconds
