"""Chip smoke test: drive the sweep engine's main path once on a TPU.

    python chip_smoke.py              # one chip: 1M-client closed point,
                                      # 64-point open grid, fast-engine check
    python chip_smoke.py --chips 4    # only the point-sharded closed grid
                                      # on four chips vs its one-chip run

Everything runs in this one process (a chip belongs to one process).
With no TPU the script names what JAX found and exits 1 without running
anything.  Phases print their numbers on earlier lines; the last line
of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``, printed only
when every phase passed.  JAX's persistent compilation cache goes where
``JAX_COMPILATION_CACHE_DIR`` says, else to ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent
TOL = 1e-9   # the differential contract of tests/test_sweep.py
SEED = 0     # workload seed of every phase and of the fast-engine check

# the fig_scale_1m deployment (benchmarks/run.py): 1000 groups x 1000
# worker threads = 1M simulated clients, 5M ops, a page cache that holds
# the whole keyspace so every leader stays on the device (no eviction)
METRO = dict(groups=1000, group_size=3, threads=1000, ops=5000)
METRO_CACHE_KEYS = 10_000
# --chips 4: four quarter-metro points (250k clients, 1.25M ops each), one
# per chip; their one-chip comparison is then one metro point's size
SHARDED = dict(METRO, groups=250)


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> int:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    return 1


def fast_columns(sim) -> dict:
    """The result columns of one ``SimEdgeKV(engine="fast")`` run, under
    the names ``SweepResult`` uses."""
    return {
        "ops": float(len(sim.records)),
        "mean_latency": sim.mean_latency(),
        "read_latency": sim.mean_latency(kind="read"),
        "update_latency": sim.mean_latency(kind="update"),
        "local_latency": sim.mean_latency(dtype="local"),
        "global_latency": sim.mean_latency(dtype="global"),
        "update_global_latency": sim.mean_latency(kind="update",
                                                  dtype="global"),
        "throughput": sim.throughput(),
        "p95_latency": sim.tail_latency(95),
        "p99_latency": sim.tail_latency(99),
    }


def compare(res, refs) -> tuple:
    """Largest relative difference and the worst test-contract ratio
    ``|got - want| / (TOL * max(1, |want|))`` over every column of every
    point (pass iff the ratio is <= 1)."""
    import math

    rel, worst, where = 0.0, 0.0, ""
    for i, ref in enumerate(refs):
        row = res.row(i)
        for name, want in ref.items():
            got = row[name]
            if math.isnan(want) or math.isnan(got):
                if not (math.isnan(want) and math.isnan(got)):
                    return math.inf, math.inf, f"point {i} {name}"
                continue
            d = abs(got - want)
            rel = max(rel, d / abs(want) if want else d)
            ratio = d / (TOL * max(1.0, abs(want)))
            if ratio > worst:
                worst, where = ratio, f"point {i} {name}"
    return rel, worst, where


def peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def phase_metro(dev) -> tuple:
    """The 1M-client closed point through run_sweep, cold then warm."""
    from repro.sim.cluster import ServiceParams
    from repro.sim.sweep import SweepPoint, run_sweep

    point = SweepPoint(p_global=0.5, **METRO)
    svc = ServiceParams(page_cache_keys=METRO_CACHE_KEYS)
    log(f"metro: closed loop, {point}, page_cache_keys={METRO_CACHE_KEYS}"
        f" ({point.groups * point.threads} clients, "
        f"{point.groups * point.ops} ops)")
    runs = []
    for label in ("cold", "warm"):
        res = run_sweep([point], loop="closed", seed=SEED, service=svc)
        if res.info.get("path") != "device":
            raise RuntimeError(
                f"metro point took the {res.info.get('path')} path, "
                "not the device fixed point")
        log(f"metro: {label} device_s={res.info['device_s']} "
            f"run_sweep_s={res.walltime_s} rounds={res.info['rounds']}")
        runs.append(res)
    log(f"metro: peak_bytes_in_use={peak_bytes(dev)}")
    cold, warm = runs
    for k in cold.columns:
        if not (cold.columns[k] == warm.columns[k]).all():
            raise RuntimeError(f"metro cold and warm runs differ in {k}")
    return point, svc, warm


def phase_open() -> object:
    from repro.sim.sweep import run_sweep, sweep_grid

    grid = sweep_grid()
    log(f"open: sweep_grid() with {len(grid)} points, duration=2.0, "
        "default scan backend")
    for label in ("cold", "warm"):
        res = run_sweep(grid, duration=2.0, seed=SEED)
        log(f"open: {label} device_s={res.info['device_s']} "
            f"run_sweep_s={res.walltime_s} "
            f"ops={int(res.columns['ops'].sum())}")
    return grid, res


def check_against_fast(metro, grid, open_res) -> None:
    from repro.obs import walltime
    from repro.sim import SimEdgeKV

    point, svc, res = metro
    t = walltime()
    sim = SimEdgeKV(setting="edge", seed=SEED, service=svc,
                    group_sizes=(point.group_size,) * point.groups,
                    engine="fast")
    sim.run_closed_loop(threads_per_client=point.threads,
                        ops_per_client=point.ops,
                        workload_kw=dict(p_global=point.p_global,
                                         distribution=point.distribution,
                                         n_records=point.n_records),
                        seed_offset=SEED)
    rel, worst, where = compare(res, [fast_columns(sim)])
    log(f"check: compared the 1M-client metro point itself with "
        f"SimEdgeKV(engine='fast') on the host ({walltime() - t} s): "
        f"max_rel_diff={rel} worst_contract_ratio={worst} ({where})")
    missed = [] if worst <= 1.0 else [f"metro point at {where}"]

    refs = []
    for p in grid:
        sim = SimEdgeKV(setting="edge", seed=SEED,
                        group_sizes=(p.group_size,) * p.groups,
                        engine="fast")
        sim.run_open_loop(rate_per_client=p.rate, duration=2.0,
                          workload_kw=dict(p_global=p.p_global,
                                           distribution=p.distribution,
                                           n_records=p.n_records))
        refs.append(fast_columns(sim))
    rel, worst, where = compare(open_res, refs)
    log(f"check: open grid, all {len(grid)} points vs the fast engine: "
        f"max_rel_diff={rel} worst_contract_ratio={worst} ({where})")
    if not worst <= 1.0:
        missed.append(f"open grid at {where}")
    if missed:
        raise RuntimeError("misses the 1e-9 contract: " + "; ".join(missed))


def phase_sharded(devs) -> None:
    """Four quarter-metro points sharded one per chip, against the same
    grid on one chip: the two must agree bit for bit."""
    import numpy as np

    from repro.sim.cluster import ServiceParams
    from repro.sim.sweep import SweepPoint, run_sweep

    pts = [SweepPoint(p_global=pg, **SHARDED)
           for pg in (0.25, 0.5, 0.75, 1.0)]
    svc = ServiceParams(page_cache_keys=METRO_CACHE_KEYS)
    log(f"sharded: {len(pts)} closed points, {pts[0].groups} groups x "
        f"{pts[0].threads} threads each, p_global="
        f"{[p.p_global for p in pts]}, "
        f"{sum(p.groups * p.ops for p in pts)} ops")
    out = {}
    for d in (len(devs), 1):
        res = run_sweep(pts, loop="closed", seed=SEED, service=svc,
                        devices=d)
        if res.info.get("path") != "device" or res.info["devices"] != d:
            raise RuntimeError(f"devices={d} run took {res.info}")
        log(f"sharded: devices={d} device_s={res.info['device_s']} "
            f"run_sweep_s={res.walltime_s} rounds={res.info['rounds']} "
            f"peak_bytes_in_use(chip0)={peak_bytes(devs[0])}")
        out[d] = res
    a, b = out[len(devs)], out[1]
    for k in b.columns:
        if not np.array_equal(a.columns[k], b.columns[k], equal_nan=True):
            raise RuntimeError(f"sharded and one-chip runs differ in {k}")
    log(f"sharded: all {len(b.columns)} columns of all {len(pts)} points "
        f"bit-identical between devices={len(devs)} and devices=1; "
        f"mean_latency={b.columns['mean_latency'].tolist()}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the point-sharded closed grid on "
                         "four chips and its one-chip comparison")
    args = ap.parse_args(argv)

    src = ROOT / "src"
    if not (src / "repro").is_dir():
        return fail(f"no repro package under {src}: run this script from "
                    "a checkout of the repository")
    sys.path.insert(0, str(src))
    from repro.jaxcache import use_compile_cache

    cache = use_compile_cache()
    cached = len(list(Path(cache).iterdir())) if Path(cache).is_dir() else 0
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        return fail(f"no TPU found: JAX sees platform={d0.platform!r}, "
                    f"{len(devs)} device(s) of kind {d0.device_kind!r}")
    if len(devs) < args.chips:
        return fail(f"--chips {args.chips} but JAX sees {len(devs)} TPU "
                    "device(s)")
    devs = devs[:args.chips]
    log(f"device: {d0.device_kind} x{len(devs)} (of {jax.device_count()}); "
        f"jax {jax.__version__}; compile cache {cache} "
        f"({cached} entries at start)")
    try:
        if args.chips == 4:
            phase_sharded(devs)
        else:
            metro = phase_metro(d0)
            grid, open_res = phase_open()
            check_against_fast(metro, grid, open_res)
    except Exception as e:  # any failed phase fails the smoke
        import traceback

        traceback.print_exc()
        return fail(f"{type(e).__name__}: {e}")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
