"""Run one cell of the chip benchmark described by ``BENCHMARK.json``.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Prints progress, and last the compared numbers beside their limits, on
standard error; the last line of standard output is the result as one
JSON object.  Exits non-zero, printing no result, when JAX finds no TPU
or fewer chips than the cell needs.
"""
import time

T_PROC = time.perf_counter()

if __name__ == "__main__":
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parent))
    import harness
    sys.exit(harness.main(t_proc=T_PROC))
