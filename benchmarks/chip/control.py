"""The check's control: the reference in the program's place, in float32.

The configurations state IEEE binary64 times.  The control computes the
plain reference with every time rounded to float32 (the next precision
down), puts it where ``run_sweep`` stands, drives the rest of a run
through the harness, and reports the worst relative gap that the check
then reads against the binary64 reference.  The check has to call it
not correct.

    python3 benchmarks/chip/control.py --workload <name> --seeds 1 2 3

runs the control once per seed, one sweep each, and prints one line per
seed; ``benchmarks/chip/test_chipbench_check.py`` runs it at a test size.
The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402
import reference  # noqa: E402


class Result:
    """What ``run_sweep`` returns, as far as the harness reads it."""

    def __init__(self, rows, walltime_s: float):
        self.columns = {k: np.asarray([r[k] for r in rows])
                        for k in rows[0]}
        self.walltime_s = walltime_s
        self.info = dict(path="device", device_s=0.0)

    def __len__(self) -> int:
        return len(next(iter(self.columns.values())))


class ControlSystem:
    """The float32 reference, answering each sweep request."""

    def __init__(self, config: dict, traffic: dict, run_seed: int):
        points, seed = reference.request(config, traffic, run_seed)
        self.args = (config, traffic, points, seed)

    def sweep(self) -> Result:
        t = time.perf_counter()
        rows = reference.reference_sweep(*self.args, reference.to_float32)
        return Result(rows, time.perf_counter() - t)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    worst = []
    for seed in args.seeds:
        run = argparse.Namespace(workload=args.workload, seed=seed,
                                 seconds=0.0, trace=0)
        try:
            out = harness.measure(run, time.perf_counter(), ControlSystem)
        except harness.HarnessError as e:
            harness.log(f"control: {e}")
            return 1
        gap = out["check"]["worst_rel_gap"]["value"]
        worst.append(gap)
        print(json.dumps(dict(workload=args.workload, seed=seed,
                              correct=out["correct"], worst_rel_gap=gap)),
              flush=True)
    print(json.dumps(dict(workload=args.workload, control_min_gap=min(worst),
                          seeds=args.seeds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
