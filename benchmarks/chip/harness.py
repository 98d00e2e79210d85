"""Chip benchmark of the sweep engine: one cell of ``BENCHMARK.json``.

A cell names a configuration (``configs/<config>.json``: a deployment's
sizes, links, service times and guarantees) and a traffic mix
(``traffic/<traffic>.json``: a sweep grid over that deployment).  One
client submits that sweep to ``repro.sim.sweep.run_sweep``, waits for
the folded result and submits it again, back to back, for the window.

Set-up is the process start, JAX's start, the compile cache and one
warm-up sweep of the cell's own request (same seed, so the same shapes:
the window compiles nothing).  The window then repeats the request
until ``--seconds`` have passed, and ends at the end of the last sweep
started inside them.  With ``--trace 1`` a short traced stretch of
whole sweeps follows the window, and the per-layer metrics are read by
the readers in ``metrics/<name>.py``.  After that, with the program's
device memory read, every folded column of every sweep is compared
with the plain reference (``reference.py``).
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import shutil
import statistics
import sys
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence, Tuple

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))

import reference  # noqa: E402

# every folded column must agree with the binary64 reference to this
# relative gap; PERF.md gives the readings it was set from
GAP_LIMIT = 1e-9
# the traced stretch: whole sweeps started within this many seconds (a
# stretch in which the profiler dropped events is traced again, up to
# TRACE_TRIES times)
TRACE_SECONDS = 1.0
TRACE_TRIES = 3
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_hits")


class HarnessError(Exception):
    """A run that cannot measure: it prints no result and exits non-zero."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------- the cell
def load_json(path: Path) -> dict:
    if not path.is_file():
        raise HarnessError(f"missing {path}")
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> Tuple[dict, dict, dict, dict]:
    """(benchmark, cell, configuration, traffic) of workload ``name``,
    each file found by the name that ``BENCHMARK.json`` gives it."""
    spec = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise HarnessError(f"no workload {name!r} in BENCHMARK.json "
                           f"(has {sorted(cells)})")
    cell = cells[name]
    config = load_json(BENCH / "configs" / f"{cell['config']}.json")
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    return spec, cell, config, traffic


def cell_metrics(spec: dict, cell: str, trace: bool) -> List[dict]:
    """The metrics this cell reports: end-to-end ones without a trace,
    per-layer ones with it; a metric with a ``workloads`` list only in
    the cells it names."""
    group = spec["per_layer" if trace else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def load_reader(name: str) -> Callable:
    """``read(run)`` of ``metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.is_file():
        raise HarnessError(f"no reader {path} for metric {name!r}")
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------- end-to-end numbers
def ops_rate(ops: Sequence[int], window_s: float) -> float:
    """Simulated operations folded into results per second of window."""
    if window_s <= 0:
        raise HarnessError("empty window")
    return sum(ops) / window_s


def p90(times: Sequence[float]) -> Tuple[float, int]:
    """90th percentile (linear between order statistics) and the number
    of samples above it."""
    if not times:
        raise HarnessError("no sweep finished in the window")
    s = sorted(times)
    pos = 0.9 * (len(s) - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(s) - 1)
    value = s[lo] + (s[hi] - s[lo]) * (pos - lo)
    return value, sum(1 for t in s if t > value)


def sweep_p90(sweeps: Sequence[dict]) -> float:
    times = [s["wall_s"] for s in sweeps]
    value, beyond = p90(times)
    log(f"sweep times: p90 {value} s with {beyond} of {len(times)} "
        "sweeps beyond it")
    return value


# ------------------------------------------------------------- the chip
def check_devices(jax, chips: int, peaks: dict) -> list:
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise HarnessError(
            f"no TPU: JAX found platform {d0.platform!r} with {len(devs)} "
            f"device(s) of kind {d0.device_kind!r}")
    if len(devs) < chips:
        raise HarnessError(f"the cell needs {chips} chips, JAX sees "
                           f"{len(devs)} TPU device(s)")
    if d0.device_kind not in peaks:
        raise HarnessError(f"device kind {d0.device_kind!r} is not in "
                           "peaks.json")
    return devs[:chips]


def use_cache() -> str:
    """JAX's persistent compile cache, in the checkout at a fixed path
    (the path is part of the cache key), given to the program."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from repro.jaxcache import use_compile_cache
    return use_compile_cache(ROOT)


class CompileCounter:
    """Counts compilations and compile-cache loads while it is open."""

    def __init__(self, monitoring):
        self.count = 0
        self.monitoring = monitoring

    def __enter__(self) -> "CompileCounter":
        self.monitoring.register_event_listener(self._event)
        self.monitoring.register_event_duration_secs_listener(self._duration)
        return self

    def __exit__(self, *exc) -> None:
        self.monitoring.unregister_event_listener(self._event)
        self.monitoring.unregister_event_duration_listener(self._duration)

    def _event(self, name: str, **_) -> None:
        if name in COMPILE_EVENTS:
            self.count += 1

    def _duration(self, name: str, _secs: float, **_) -> None:
        if name in COMPILE_EVENTS:
            self.count += 1


class GcWatch:
    """Counts the garbage collector's passes and their longest pause
    while it is open, so that a stalled sweep can be told apart."""

    def __enter__(self) -> "GcWatch":
        self.passes, self.longest, self._t = 0, 0.0, 0.0
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._cb)

    def _cb(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.passes += 1
            self.longest = max(self.longest, time.perf_counter() - self._t)


def peak_bytes(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


# ------------------------------------------------------------ the system
class System:
    """The system under test: one sweep request of the cell, submitted to
    ``run_sweep`` as a user would."""

    def __init__(self, config: dict, traffic: dict, run_seed: int):
        from repro.sim.cluster import ServiceParams
        from repro.sim.sweep import SweepPoint
        points, seed = reference.request(config, traffic, run_seed)
        self.points = [SweepPoint(p_global=p["p_global"],
                                  rate=p.get("rate", 200.0),
                                  groups=p["groups"],
                                  n_records=p["n_records"],
                                  distribution=p["distribution"],
                                  group_size=p["group_size"],
                                  threads=p["threads"],
                                  ops=p["ops_per_client"])
                       for p in points]
        self.kw = dict(loop=traffic["loop"], seed=seed,
                       setting=config["setting"],
                       service=ServiceParams(**config["service"]),
                       devices=traffic.get("devices", 1))
        if traffic["loop"] == "open":
            self.kw["duration"] = traffic["duration_s"]

    def sweep(self):
        from repro.sim.sweep import run_sweep
        return run_sweep(self.points, **self.kw)


def longest(sweeps: Sequence[dict]) -> str:
    """The slowest sweep of a window, split into its parts."""
    if not sweeps:
        return "no sweep"
    s = max(sweeps, key=lambda s: s["wall_s"])
    return (f"longest sweep {s['wall_s']} s (median "
            f"{statistics.median(x['wall_s'] for x in sweeps)} s): "
            f"run_sweep {s['walltime_s']} s, of which device call "
            f"{s['device_s']} s")


def columns(res) -> List[Dict[str, float]]:
    return [{k: float(v[i]) for k, v in res.columns.items()}
            for i in range(len(res))]


def run_window(system, seconds: float, annotate) -> dict:
    """Submit sweeps back to back; the first starts at once, later ones
    only inside ``seconds``, and the window ends when the last returns.
    Each sweep keeps its own wall time, ``run_sweep``'s and its device
    call's, so that a slow one shows where it lost the time, and what
    the program names in ``info``: its ``spans`` and every number under
    its own name (counters such as ``changed``), for the readers."""
    sweeps, results, failed = [], [], 0
    t_start = time.perf_counter()
    t_end = t_start
    while not (sweeps or failed) or time.perf_counter() - t_start < seconds:
        t = time.perf_counter()
        try:
            with annotate("sweep"):
                res = system.sweep()
        except Exception as e:  # a sweep that raised is a failed sweep
            log(f"sweep failed: {type(e).__name__}: {e}")
            failed += 1
            t_end = time.perf_counter()
            continue
        t_end = time.perf_counter()
        info = res.info
        ok = info.get("path") == "device"
        if not ok:
            log(f"sweep left the device path: {info}")
            failed += 1
            continue
        sweeps.append(dict(
            {k: v for k, v in info.items() if isinstance(v, (int, float))},
            spans=info.get("spans"), wall_s=t_end - t,
            walltime_s=res.walltime_s, device_s=info["device_s"],
            rounds=info.get("rounds"), ops=int(res.columns["ops"].sum())))
        results.append(columns(res))
    return dict(sweeps=sweeps, results=results, failed=failed,
                attempted=len(sweeps) + failed, window_s=t_end - t_start)


def trace_window(jax, system, devs, trace_dir: Path) -> dict:
    """Whole sweeps under the profiler, then the trace reduced: by
    opcode (:mod:`trace_reduce`) and, for the accepted attempt, by the
    program's named scopes (:mod:`scope_reduce`, under ``scopes``)."""
    import scope_reduce
    import trace_reduce
    import xspace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    results, failed = [], 0
    for attempt in range(1, TRACE_TRIES + 1):
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        try:
            win = run_window(system, TRACE_SECONDS,
                             jax.profiler.TraceAnnotation)
        finally:
            jax.profiler.stop_trace()
        results += win["results"]
        failed += win["failed"]
        t = time.perf_counter()
        try:
            path = trace_reduce.newest_trace(trace_dir)
            pd = trace_reduce.load(path)
            lost = trace_reduce.dropped(pd)
            if not lost:
                red = trace_reduce.reduce_profile(pd, n_chips=len(devs))
                red["scopes"] = scope_reduce.reduce_scopes(
                    pd, xspace.tf_ops(Path(path).read_bytes()), len(devs))
                break
        except trace_reduce.TraceError as e:
            raise HarnessError(f"trace: {e}") from e
        log(f"trace attempt {attempt}: the device dropped {lost} op "
            "events; tracing again")
    else:
        raise HarnessError(f"trace: events dropped in {TRACE_TRIES} tries")
    red["sweeps"] = len(win["sweeps"])
    red["device_s"] = sum(s["device_s"] for s in win["sweeps"])
    log(f"trace read in {time.perf_counter() - t} s")
    # the device cannot be busy longer than the calls that drove it
    if max(red["busy_s"]) > red["device_s"]:
        raise HarnessError(f"trace: busy {red['busy_s']} s exceeds the "
                           f"device calls' {red['device_s']} s")
    return dict(win, results=results, failed=failed, trace=red)


# ------------------------------------------------------------- the check
def check(config: dict, traffic: dict, run_seed: int,
          results: Sequence[List[Dict[str, float]]]
          ) -> Tuple[float, str, float]:
    """Worst relative gap of any sweep's columns to the reference, where
    it is, and the reference's seconds."""
    t = time.perf_counter()
    points, seed = reference.request(config, traffic, run_seed)
    want = reference.reference_sweep(config, traffic, points, seed)
    ref_s = time.perf_counter() - t
    worst, where = (0.0, "") if results else (math.inf, "no results")
    for got in results:
        gap, at = reference.worst_gap(got, want)
        if not gap <= worst:
            worst, where = gap, at
    return worst, where, ref_s


# ------------------------------------------------------------------ run
def measure(args, t_proc: float, make_system: Callable = System) -> dict:
    """One run of the cell: set-up, window, optional trace, check."""
    spec, cell, config, traffic = load_cell(args.workload)
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise HarnessError(f"no program under {src}")
    sys.path.insert(0, str(src))
    peaks = load_json(BENCH / "peaks.json")["devices"]
    import jax
    devs = check_devices(jax, cell["chips"], peaks)
    cache = use_cache()
    log(f"cell {cell['name']}: config {cell['config']}, traffic "
        f"{cell['traffic']}, seed {args.seed} (workload seed "
        f"{reference.request(config, traffic, args.seed)[1]}), "
        f"{devs[0].device_kind} x{len(devs)}, compile cache {cache}")
    with CompileCounter(jax.monitoring) as counter:
        system = make_system(config, traffic, args.seed)
        with jax.profiler.TraceAnnotation("warmup"):
            warm = system.sweep()
        setup_s = time.perf_counter() - t_proc
        log(f"set-up {setup_s} s: warm-up sweep {warm.walltime_s} s, "
            f"{counter.count} compilations or cache loads, info "
            f"{warm.info}")
        del warm
        compiles0 = counter.count
        with GcWatch() as gcw:
            win = run_window(system, args.seconds,
                             jax.profiler.TraceAnnotation)
        in_window = counter.count - compiles0
    mem = peak_bytes(devs)
    rounds = sorted({s["rounds"] for s in win["sweeps"]} - {None})
    log(f"window {win['window_s']} s: {win['attempted']} sweeps, "
        f"{win['failed']} failed, {in_window} compilations inside the "
        f"window, rounds {rounds}, peak_bytes_in_use {mem} on the "
        "fullest chip")
    log(f"window: {longest(win['sweeps'])}; {gcw.passes} garbage "
        f"collector passes, the longest {gcw.longest} s")

    device = dict(platform=devs[0].platform, kind=devs[0].device_kind,
                  count=jax.device_count(), memory_peak_bytes=mem)
    results = list(win["results"])
    failed = win["failed"]
    out: dict = {}
    if args.trace:
        tw = trace_window(jax, system, devs,
                          ROOT / ".bench_trace" / cell["name"])
        results += tw["results"]
        failed += tw["failed"]
        red = tw["trace"]
        log(f"trace: {red['sweeps']} sweeps, busy {red['busy_s']} s of "
            f"{red['window_s']} s, device calls {red['device_s']} s "
            f"(busy/calls {max(red['busy_s']) / red['device_s']}), "
            f"groups {red['groups']}, scopes {red['scopes']}")
        device.update(busy_s=statistics.fmean(red["busy_s"]),
                      window_s=red["window_s"])
        traced = {s["rounds"] for s in tw["sweeps"]}
        run = dict(sweeps=win["sweeps"], trace=red, loop=traffic["loop"],
                   rounds=traced.pop() if len(traced) == 1 else None)
        if traffic["loop"] == "closed":
            run["queue_len"] = reference.longest_queue(
                config, traffic, *reference.request(config, traffic,
                                                    args.seed))
            log(f"trace: runs per execution {red['runs']}, rounds "
                f"{run['rounds']}, longest queue {run['queue_len']}")
        metrics = {}
        for m in cell_metrics(spec, cell["name"], trace=True):
            v = load_reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = dict(value=v, unit=m["unit"])
        out["breakdown"] = red["breakdown"]
    else:
        e2e = dict(sim_ops_per_s=lambda: ops_rate(
            [s["ops"] for s in win["sweeps"]], win["window_s"]),
            sweep_p90_s=lambda: sweep_p90(win["sweeps"]),
            setup_s=lambda: setup_s)
        metrics = {m["name"]: dict(value=e2e[m["name"]](), unit=m["unit"])
                   for m in cell_metrics(spec, cell["name"], trace=False)}

    gap, where, ref_s = check(config, traffic, args.seed, results)
    log(f"check: {len(results)} sweeps compared with the reference "
        f"({ref_s} s): worst gap {gap} at {where or '-'}")
    log(f"check: failed_sweeps {failed} limit 0")
    log(f"check: worst_rel_gap {gap} limit {GAP_LIMIT}")
    out = dict(correct=gap <= GAP_LIMIT and failed == 0,
               attempted=win["attempted"], failed=win["failed"],
               metrics=metrics, device=device, **out)
    out["check"] = dict(failed_sweeps=dict(value=failed, limit=0),
                        worst_rel_gap=dict(value=gap, limit=GAP_LIMIT))
    return out


def main(argv=None, t_proc: Optional[float] = None) -> int:
    import argparse
    t_proc = time.perf_counter() if t_proc is None else t_proc
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    try:
        out = measure(args, t_proc)
    except HarnessError as e:
        log(f"benchmark: {e}")
        return 1
    print(json.dumps(out), flush=True)
    return 0
