"""Batched parameter sweeps: N EdgeKV simulations as ONE jitted JAX
array program — open loop (exogenous Poisson arrivals) and closed loop
(think-time feedback, the regime every paper figure actually uses).

EdgeKV's evaluation (§6) is a grid of scenarios — workload mix x
local/global ratio x load x topology — and with the fast engine each grid
point still costs a separate numpy pass.  This module compiles the whole
grid instead: :func:`run_sweep` takes a list of :class:`SweepPoint`
configurations and evaluates them in a single ``jax.jit`` call.

Closed loop (``run_sweep(..., loop="closed")``): a worker thread's next
arrival is its previous completion (zero think time), so arrival times
are no longer exogenous — they are the *fixed point* of the coupled
recurrence in which threads interact only through each serving leader's
FIFO commit stage (the max-plus scan) and its LRU page cache.  The
program iterates a batched round to that fixed point inside one
``lax.while_loop``: completions -> next arrivals (elementwise
:func:`~repro.sim.vectorized.arrival_chain`) -> per-row stable sort into
leader-arrival order (ties broken by flat position = the heap engine's
pid order) -> seen-before page penalties -> batched max-plus departure
scan -> completions (:func:`~repro.sim.vectorized.completion_chain`).
Unresolved ops (predecessor not yet computed) carry ``+inf`` arrivals,
which sorts them harmlessly after every resolved op, so each round
extends the resolved wavefront by at least one op per thread and the
iteration converges — bitwise — in O(ops-per-thread) rounds.  The true
schedule is a fixed point of the round map, so extra rounds are no-ops;
that is what makes the multi-device program (``devices=N`` shards the
point axis with ``jax.shard_map``) bit-identical to
the single-device one even though shards converge at different rounds.

Layout: the grid is flattened to **one row per (config, serving group)**
— the granularity at which the leader FIFO serializes — with ops in
leader-arrival order and ragged tails padded.  That row axis is both the
``vmap`` axis for the pure delay-column chains shared with the per-run
engine (:func:`repro.sim.vectorized.arrival_chain` /
:func:`~repro.sim.vectorized.completion_chain`, evaluated from stacked
per-config component tables) and the batch axis of the max-plus
departure scan from :mod:`repro.kernels.maxplus_scan`
(``jax.lax.associative_scan`` by default, the Pallas kernel with
``scan_backend="pallas"``), so the open-loop program needs no in-program
gather/scatter at all (the closed-loop rounds gather/scatter because the
order itself is part of the fixed point).  Per-row masked category
reductions come back as batched aggregates; :class:`SweepResult` folds
them into per-point columns — mean latencies by kind/dtype, paper-metric
throughput, p95/p99 tails — the
:class:`~repro.sim.records.RecordArray` aggregate shape lifted to a
whole grid.

Only the parts that are inherently host-side stay in numpy: drawing the
op schedules (the numpy RNG streams must match the fast engine draw for
draw), Chord routing (one shared ring per group count, one ``route`` per
(gateway, successor-vnode) class for the *whole grid*), and the exact
LRU page-penalty masks (:func:`~repro.sim.vectorized.lru_hit_mask`).

Exactness: every per-point result matches an independent
``SimEdgeKV(engine="fast")`` run on the same seeds to ~1e-13 relative —
the array program evaluates the identical float64 expressions; only the
scan/reduction association order differs.  The jitted call runs under
``jax.enable_x64`` so float64 survives jax.  The closed loop's default
``seq`` path goes further: it carries every time as the int64 bit
pattern of its IEEE double (:mod:`repro.sim.f64bits`), because with the
TPU's emulated float64 it missed the 1e-9 contract on a v5e.
"""
from __future__ import annotations

import operator
from dataclasses import asdict, dataclass, field
from functools import lru_cache, partial
from itertools import product
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.hashring import ChordRing, stable_hash
from repro.kernels.maxplus_scan import maxplus_depart
from repro.obs import walltime
from repro.obs.trace import STAGES as OBS_STAGES

from . import f64bits
from .cluster import ServiceParams, arrival_seed, closed_loop_plan
from .network import SETTINGS
from .vectorized import (GLOBAL_CODE, READ_CODE, _DelayModel,
                         _open_loop_segments, arrival_chain,
                         completion_chain, lru_hit_mask, plan_columns)

_PAIRS = ("c_req", "c_resp", "f_req", "f_resp", "sg_req", "sg_resp",
          "h_req", "g_resp", "svc_base")


@dataclass(frozen=True)
class SweepPoint:
    """One configuration in a sweep grid.

    ``rate`` drives open-loop points; ``threads`` / ``ops`` (worker
    threads per client group, total ops per client group — the
    ``run_closed_loop`` knobs) drive closed-loop points.  The unused
    axis is simply ignored by the other loop mode.
    """
    p_global: float = 0.5
    rate: float = 200.0
    groups: int = 3
    n_records: int = 10_000
    distribution: str = "uniform"
    group_size: int = 3
    threads: int = 100
    ops: int = 10_000


def sweep_grid(p_globals: Sequence[float] = (0.0, 0.25, 0.5, 0.75),
               rates: Sequence[float] = (200.0, 400.0, 600.0, 800.0),
               contention: Sequence[int] = (10_000, 2_500),
               groups: Sequence[int] = (3, 5),
               distribution: str = "uniform",
               group_size: int = 3) -> List[SweepPoint]:
    """The §6-style evaluation grid: local/global ratio x contention
    (keyspace size — fewer records, hotter pages) x arrival rate (the
    Fig 13 axis) x group count.  Defaults to 4 x 2 x 4 x 2 = 64 points.
    """
    return [SweepPoint(p_global=pg, rate=float(r), n_records=int(nr),
                       groups=int(g), distribution=distribution,
                       group_size=group_size)
            for pg, nr, r, g in product(p_globals, contention, rates,
                                        groups)]


def closed_grid(p_globals: Sequence[float] = (0.0, 0.25, 0.5, 1.0),
                contention: Sequence[int] = (10_000, 2_500),
                groups: Sequence[int] = (3, 5),
                distribution: str = "uniform", group_size: int = 3,
                threads: int = 32, ops: int = 320) -> List[SweepPoint]:
    """A §6-style *closed-loop* grid: local/global ratio x contention x
    group count, each point a ``run_closed_loop`` configuration
    (``threads`` workers per client group sharing ``ops`` operations).
    Defaults to 4 x 2 x 2 = 16 points."""
    return [SweepPoint(p_global=pg, n_records=int(nr), groups=int(g),
                       distribution=distribution, group_size=group_size,
                       threads=int(threads), ops=int(ops))
            for pg, nr, g in product(p_globals, contention, groups)]


@dataclass
class SweepResult:
    """Batched sweep aggregates — one SoA column per metric, one slot per
    grid point (the :class:`~repro.sim.records.RecordArray` aggregate
    shape, lifted to a whole grid)."""
    points: List[SweepPoint]
    columns: Dict[str, np.ndarray]
    walltime_s: float = 0.0
    # how the grid ran: ``path`` ("device", or "host" for the eviction
    # regime's host fixed point), ``device_s`` (walltime from the
    # host->device transfer through the final ``device_get``, compile
    # included on a cold call), and for the closed loop ``devices`` and
    # the fixed point's ``rounds`` (max over shards)
    info: Dict[str, object] = field(default_factory=dict)

    def __len__(self) -> int:
        return len(self.points)

    def row(self, i: int) -> dict:
        r = dict(asdict(self.points[i]))
        r.update({k: float(v[i]) for k, v in self.columns.items()})
        return r

    def rows(self) -> List[dict]:
        return [self.row(i) for i in range(len(self))]


_KEYSPACE_HASHES: Dict[int, np.ndarray] = {}


def _keyspace_hashes(keys: List[str]) -> np.ndarray:
    """Ring hashes for a whole YCSB keyspace, memoized by size (the key
    strings are deterministic) — one sha1 pass per keyspace for the whole
    grid instead of one per point."""
    kh = _KEYSPACE_HASHES.get(len(keys))
    if kh is None:
        kh = _KEYSPACE_HASHES[len(keys)] = np.fromiter(
            (stable_hash(k) for k in keys), dtype=np.uint64,
            count=len(keys))
    return kh


class _Topology:
    """Shared Chord topology for every sweep point with the same group
    count: the ring depends only on the gateway names, so construction,
    key -> successor-vnode maps, and route classes amortize across the
    grid (one ``ring.route`` per (gateway, successor-vnode) class for the
    whole sweep)."""

    def __init__(self, groups: int, virtual_nodes: int = 1):
        self.ring = ChordRing(virtual_nodes=virtual_nodes)
        self.gw_of_code = [f"gw{i}" for i in range(groups)]
        for gw in self.gw_of_code:
            self.ring.add_node(gw)
        self._vh = np.asarray(self.ring._vhashes, dtype=np.uint64)
        self._svn: Dict[int, np.ndarray] = {}    # keyspace -> vnode of key
        self._cls: Dict[int, Tuple[int, int]] = {}  # class -> (owner, hops)

    def routes(self, client_codes: np.ndarray, key_indices: np.ndarray,
               keys: List[str]) -> Tuple[np.ndarray, np.ndarray]:
        svn_of_key = self._svn.get(len(keys))
        if svn_of_key is None:
            svn_of_key = self._svn[len(keys)] = (
                np.searchsorted(self._vh, _keyspace_hashes(keys),
                                side="left") % len(self._vh)
            ).astype(np.int64)
        svn = svn_of_key[key_indices]
        packed = client_codes.astype(np.int64) * len(self._vh) + svn
        uniq, uidx, inv = np.unique(packed, return_index=True,
                                    return_inverse=True)
        owner_u = np.empty(len(uniq), np.int32)
        hops_u = np.empty(len(uniq), np.int32)
        for j, u in enumerate(uniq.tolist()):
            ent = self._cls.get(u)
            if ent is None:
                rep = int(uidx[j])
                path = self.ring.route(
                    self.gw_of_code[int(client_codes[rep])],
                    keys[int(key_indices[rep])])
                ent = self._cls[u] = (
                    int(path[-1][2:]), len(path) - 1)  # "gw<i>" -> code
            owner_u[j], hops_u[j] = ent
        return owner_u[inv], hops_u[inv]


# one shared topology per (group count, vnodes) for the whole *process*:
# the ring is a pure function of the gateway names, so the open- and
# closed-loop sweep paths (and repeated run_sweep calls) reuse the same
# key->vnode maps and route-class memos instead of re-deriving them
_TOPOLOGIES: Dict[Tuple[int, int], _Topology] = {}


def _topology(groups: int, virtual_nodes: int) -> _Topology:
    topo = _TOPOLOGIES.get((groups, virtual_nodes))
    if topo is None:
        topo = _TOPOLOGIES[(groups, virtual_nodes)] = _Topology(
            groups, virtual_nodes)
    return topo


@lru_cache(maxsize=None)
def _compiled(max_hops: int, scan_backend: str, interpret: bool):
    """Build + jit the grid program for one static shape family.

    Everything is row-space (R, Ls): one row per (config, serving group),
    ops in leader-arrival order, padded tails masked by ``valid``.
    """

    def row_chain(tblr, t0, is_w, glob, lf, hops, pens):
        """Per-row arrival/service delay columns from the config's
        stacked component table — vmapped over the row axis.  Also
        returns the span-model cuts (b_request, b_route) the chain
        passes on the way, for the per-stage aggregates."""
        def pick(name):
            return jnp.where(is_w, tblr[name][1], tblr[name][0])
        cuts: list = []
        arr = arrival_chain(jnp, t0, pick("c_req"), pick("f_req"),
                            pick("sg_req"), pick("h_req"), lf, glob, hops,
                            max_hops, cuts=cuts)
        svc = pick("svc_base") + pens
        return arr, svc, cuts[0], cuts[1]

    def row_completion(tblr, dep, is_w, glob, lf, remote):
        def pick(name):
            return jnp.where(is_w, tblr[name][1], tblr[name][0])
        q_or_ri = jnp.where(is_w, tblr["q_ri"][1], tblr["q_ri"][0])
        cuts: list = []
        comp = completion_chain(jnp, dep, q_or_ri, pick("sg_resp"),
                                pick("g_resp"), pick("f_resp"),
                                pick("c_resp"), lf, glob, remote,
                                cuts=cuts)
        return comp, cuts[0]

    def program(tblr, flat, gidx):
        # row-space views: one gather per op column (padding index points
        # at the zeroed pad slot appended to each flat column)
        def take(name):
            return jnp.take(flat[name], gidx, mode="clip")
        t0, is_w, glob = take("t0"), take("is_w"), take("glob")
        lf, remote = take("lf"), take("remote")
        valid = gidx < flat["t0"].shape[0] - 1
        arr, svc, b_req, b_route = jax.vmap(row_chain)(
            tblr, t0, is_w, glob, lf, take("hops"), take("pens"))

        # the leader FIFO stage: batched max-plus departure scan, one
        # independent recurrence per row (padding tails carry harmlessly)
        if scan_backend == "pallas":
            dep = maxplus_depart(arr, svc, backend="pallas",
                                 interpret=interpret)
        else:
            dep = maxplus_depart(arr, svc, backend="assoc")

        comp, b_repl = jax.vmap(row_completion)(
            tblr, dep, is_w, glob, lf, remote)
        lat = comp - t0

        # span-model boundaries (rows are already leader-arrival order):
        # service start = max(arrival, previous departure), clamped to
        # the departure because the closed-form scans reassociate float
        # adds and may sit an ulp off the sequential recurrence
        prev = jnp.concatenate(
            [jnp.full((dep.shape[0], 1), -jnp.inf, dep.dtype),
             dep[:, :-1]], axis=1)
        start = jnp.minimum(jnp.maximum(arr, prev), dep)
        # per-row per-stage duration sums (open loop has no lease stage);
        # the host folds rows into per-point means alongside cnt4/sum4
        stage_sum = jnp.stack([
            jnp.sum(jnp.where(valid, d, 0.0), axis=1)
            for d in (b_req - t0, b_route - b_req,
                      jnp.zeros_like(t0),          # lease
                      arr - b_route, start - arr, dep - start,
                      b_repl - dep, comp - b_repl)], axis=1)

        # per-row aggregates over (is_write x is_global) categories; the
        # host folds rows into per-point kind/dtype means
        cnt4, sum4 = [], []
        for m in (valid & ~is_w & ~glob, valid & ~is_w & glob,
                  valid & is_w & ~glob, valid & is_w & glob):
            cnt4.append(jnp.sum(m, axis=1))
            sum4.append(jnp.sum(jnp.where(m, lat, 0.0), axis=1))
        return jnp.stack(cnt4, axis=1), jnp.stack(sum4, axis=1), lat, \
            stage_sum

    return jax.jit(program)


def run_sweep(points: Iterable[SweepPoint], *, duration: float = 2.0,
              setting: str = "edge", seed: int = 0,
              service: Optional[ServiceParams] = None,
              virtual_nodes: int = 1, scan_backend: Optional[str] = None,
              interpret: bool = False,
              percentiles: Sequence[float] = (95.0, 99.0),
              loop: str = "open", devices: int = 1,
              max_rounds: Optional[int] = None) -> SweepResult:
    """Evaluate a sweep grid in a single jitted array program.

    ``loop="open"`` (default): each :class:`SweepPoint` reproduces
    exactly what ``SimEdgeKV(setting=setting,
    group_sizes=(group_size,)*groups, seed=seed,
    engine="fast").run_open_loop(rate, duration, workload_kw)`` would
    record — same schedules, routes, penalties, and float64 delay
    arithmetic — but the grid shares one compiled program, one ring per
    group count, and one batched departure scan.

    ``loop="closed"``: each point reproduces
    ``run_closed_loop(threads_per_client=p.threads,
    ops_per_client=p.ops, workload_kw=..., seed_offset=seed)`` on the
    same fast-engine sim (closed-loop schedules are seeded by
    ``seed_offset``, so ``seed`` plays that role here; ``duration`` and
    ``p.rate`` are ignored).  The whole grid runs as one batched
    fixed-point iteration (see the module docstring), sharded over the
    point axis with ``devices`` > 1 (``jax.shard_map``; on CPU raise
    the device count with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``).
    ``max_rounds`` caps the fixed-point iteration (default: generous in
    ops-per-thread); non-convergence raises instead of returning wrong
    numbers.  Grids whose (config, group) rows can evict page-cache
    entries (distinct keys at one leader exceeding
    ``service.page_cache_keys``) fall back to an equivalent host-side
    fixed point with the exact LRU replay
    (:func:`~repro.sim.vectorized.lru_hit_mask`).

    ``scan_backend`` selects the leader-stage scan.  ``None`` (default)
    resolves per loop mode: ``"assoc"`` (``jax.lax.associative_scan``,
    closed-form) for open loop, ``"seq"`` (``lax.scan``, the engine's
    exact sequential float association) for closed loop.  ``"pallas"``
    uses the Pallas kernel, batched over rows; it runs only with
    ``interpret=True``, because the sweep feeds it float64 and the
    kernel does not compile for the TPU (no float64, no in-kernel
    ``cumsum`` lowering), so ``interpret=False`` raises.
    The closed loop defaults to ``"seq"`` because its fixed point feeds
    completions back into *queue ordering*: the closed-form scans
    reassociate float adds, and a 1-ulp deviation can flip the order of
    two near-tied arrivals and snowball into a genuinely different
    schedule — harmless ulps in the open loop, percent-level metric
    drift in the closed loop.  ``"assoc"``/``"pallas"`` remain valid for
    closed loop where ulp-exactness is not required (self-consistent
    schedules, same fixed-point semantics).
    """
    points = [points] if isinstance(points, SweepPoint) else list(points)
    if not points:
        raise ValueError("empty sweep grid")
    if duration <= 0:
        raise ValueError("duration must be positive")
    if loop not in ("open", "closed"):
        raise ValueError(f"unknown loop mode {loop!r}")
    if devices < 1:
        raise ValueError("devices must be >= 1")
    if scan_backend is None:
        scan_backend = "seq" if loop == "closed" else "assoc"
    if scan_backend not in ("seq", "assoc", "pallas"):
        raise ValueError(f"unknown scan_backend {scan_backend!r}")
    if loop == "open" and scan_backend == "seq":
        raise ValueError("scan_backend='seq' is closed-loop only")
    if scan_backend == "pallas" and not interpret:
        raise ValueError(
            "scan_backend='pallas' needs interpret=True: the sweep runs "
            "in float64, and the Pallas max-plus kernel does not compile "
            "for the TPU (no float64, no cumsum lowering)")
    if loop == "closed":
        return _run_closed(points, setting=setting, seed=seed,
                           service=service, virtual_nodes=virtual_nodes,
                           scan_backend=scan_backend, interpret=interpret,
                           percentiles=percentiles, devices=devices,
                           max_rounds=max_rounds)
    if devices != 1:
        raise ValueError("devices > 1 requires loop='closed'")
    t_wall = walltime()
    qs = tuple(float(q) for q in percentiles)
    ob = _open_build(points, duration=duration, setting=setting, seed=seed,
                     service=service, virtual_nodes=virtual_nodes)
    per, flat, gidx = ob["per"], ob["flat"], ob["gidx"]
    n_total, row_tbl_arr = ob["n_total"], ob["row_tbl"]
    valid = gidx < n_total

    # ---- the single jitted call ----
    fn = _compiled(ob["max_hops"], scan_backend, bool(interpret))
    with jax.enable_x64(True):
        t_dev = walltime()
        cnt4, sum4, lat_rows, stage_sum = jax.device_get(fn(
            *jax.tree.map(jnp.asarray, _open_args(ob))))
        device_s = walltime() - t_dev

    # ---- fold rows back into per-point RecordArray-style aggregates ----
    lat_op = np.empty(n_total)
    lat_op[gidx[valid]] = np.asarray(lat_rows)[valid]
    cnt4 = np.asarray(cnt4, np.float64)
    sum4 = np.asarray(sum4)
    N = len(points)
    cnt_pt = np.zeros((N, 4))
    sum_pt = np.zeros((N, 4))
    for c in range(4):
        cnt_pt[:, c] = np.bincount(row_tbl_arr, cnt4[:, c], minlength=N)
        sum_pt[:, c] = np.bincount(row_tbl_arr, sum4[:, c], minlength=N)

    # categories: (read-local, read-global, update-local, update-global)
    sel = {"mean_latency": (0, 1, 2, 3), "read_latency": (0, 1),
           "update_latency": (2, 3), "local_latency": (0, 2),
           "global_latency": (1, 3), "update_global_latency": (3,)}
    cols: Dict[str, np.ndarray] = {
        "ops": np.asarray([d["n"] for d in per], np.int64)}
    for name, cats in sel.items():
        c = cnt_pt[:, list(cats)].sum(axis=1)
        s = sum_pt[:, list(cats)].sum(axis=1)
        cols[name] = np.where(c > 0, s / np.maximum(c, 1), np.nan)

    # per-point per-stage mean durations (span model, program aggregates)
    n_ops_pt = cnt_pt.sum(axis=1)
    stage_sum = np.asarray(stage_sum, np.float64)
    for si, stage in enumerate(OBS_STAGES):
        s = np.bincount(row_tbl_arr, stage_sum[:, si], minlength=N)
        cols[f"stage_{stage}"] = np.where(
            n_ops_pt > 0, s / np.maximum(n_ops_pt, 1), np.nan)

    # paper-metric throughput (average of per-client rates) and tails,
    # from the op-order latency column — same expressions as
    # RecordArray.group_stats / tail_latency
    thr = np.zeros(N)
    tails = np.zeros((len(qs), N))
    for pi, d in enumerate(per):
        lo, n = d["offset"], d["n"]
        lat_pt = lat_op[lo:lo + n]
        t0_pt = flat["t0"][lo:lo + n]
        end_pt = t0_pt + lat_pt
        rates = []
        s = lo
        for ln in d["seg_len"]:
            span = (end_pt[s - lo:s - lo + ln].max()
                    - t0_pt[s - lo:s - lo + ln].min())
            if span > 0:
                rates.append(ln / span)
            s += ln
        thr[pi] = sum(rates) / len(rates) if rates else 0.0
        if qs:
            tails[:, pi] = np.percentile(lat_pt, qs)
    cols["throughput"] = thr
    for q, t in zip(qs, tails):
        cols[f"p{q:g}_latency"] = t
    return SweepResult(points, cols, walltime() - t_wall,
                       dict(path="device", device_s=device_s))


def _open_build(points: Sequence[SweepPoint], *, duration: float,
                setting: str, seed: int, service: Optional[ServiceParams],
                virtual_nodes: int) -> dict:
    """Host side of the open-loop sweep: schedules, routes and page
    penalties (seed-exact numpy), laid out as flat op columns plus the
    row-space index ``gidx`` of shape (R, Ls) with padded ragged tails.
    """
    svcp = service or ServiceParams()
    dm = _DelayModel(SETTINGS[setting], svcp)
    capacity = max(1, svcp.page_cache_keys)
    cols_op: Dict[str, List[np.ndarray]] = {
        k: [] for k in ("t0", "pens", "is_w", "glob", "lf", "remote",
                        "hops", "client")}
    per: List[dict] = []       # per-point metadata
    row_idx: List[np.ndarray] = []   # per row: global op indices
    row_tbl: List[int] = []          # per row: owning point
    offset = 0
    for pi, p in enumerate(points):
        topo = _topology(p.groups, virtual_nodes)
        clients = [(c, c, p.group_size, arrival_seed(seed, f"g{c}"))
                   for c in range(p.groups)]
        segs = _open_loop_segments(
            clients, p.rate, duration, 0.0,
            dict(p_global=p.p_global, distribution=p.distribution,
                 n_records=p.n_records))
        keys = segs[0][1].keys
        client = np.concatenate([np.full(len(s[2]), s[0], np.int32)
                                 for s in segs])
        t0 = np.concatenate([s[2] for s in segs])
        key_idx = np.concatenate([s[3] for s in segs])
        kind = np.concatenate([s[4] for s in segs])
        dtype = np.concatenate([s[5] for s in segs])
        fwd = np.concatenate([s[6] for s in segs])
        is_w = kind != READ_CODE
        glob = dtype == GLOBAL_CODE
        serving = client.copy()
        hops = np.zeros(len(t0), np.int32)
        if glob.any():
            owner, h = topo.routes(client[glob], key_idx[glob], keys)
            serving[glob] = owner
            hops[glob] = h

        def bw(pair):
            return np.where(is_w, pair[1], pair[0])
        lf = (~glob) & fwd
        # host copy of the arrival chain, only to fix the per-group scan
        # order and LRU replay order (the program re-derives the values)
        arr = arrival_chain(np, t0, bw(dm.c_req), bw(dm.f_req),
                            bw(dm.sg_req), bw(dm.h_req), lf, glob, hops,
                            int(hops.max()) if len(hops) else 0)
        pens = np.zeros(len(t0))
        # one lexsort per point: (serving, arrival, index) makes every
        # serving group a contiguous, arrival-ordered slice — the same
        # per-group order the fast engine scans in
        order_all = np.lexsort((np.arange(len(t0)), arr, serving))
        sv = serving[order_all]
        cuts = np.flatnonzero(sv[1:] != sv[:-1]) + 1
        for order in np.split(order_all, cuts):
            hit = lru_hit_mask(key_idx[order], capacity)
            pens[order] = np.where(hit, 0.0, dm.seek)
            row_idx.append(offset + order)
            row_tbl.append(pi)
        for name, col in (("t0", t0), ("pens", pens), ("is_w", is_w),
                          ("glob", glob), ("lf", lf),
                          ("remote", glob & (serving != client)),
                          ("hops", hops), ("client", client)):
            cols_op[name].append(col)
        per.append(dict(n=len(t0), offset=offset,
                        seg_len=[len(s[2]) for s in segs],
                        q_ri=(dm.readindex(p.group_size),
                              dm.quorum(p.group_size))))
        offset += len(t0)

    n_total = offset
    # one extra zeroed slot per column backs the row padding
    flat = {k: np.concatenate(v + [np.zeros(1, v[0].dtype)])
            for k, v in cols_op.items()}

    # ---- row-space index: (R, Ls) with padded ragged tails ----
    R = len(row_idx)
    Ls = max(len(r) for r in row_idx)
    gidx = np.full((R, Ls), n_total, np.int32)
    for r, idx in enumerate(row_idx):
        gidx[r, :len(idx)] = idx
    tbl_pt = {name: np.tile(np.asarray(getattr(dm, name), np.float64),
                            (len(points), 1))
              for name in _PAIRS}
    tbl_pt["q_ri"] = np.asarray([d["q_ri"] for d in per], np.float64)
    row_tbl_arr = np.asarray(row_tbl)
    tblr = {name: v[row_tbl_arr] for name, v in tbl_pt.items()}
    max_hops = int(flat["hops"].max()) if n_total else 0
    return dict(tblr=tblr, flat=flat, gidx=gidx, max_hops=max_hops,
                per=per, row_tbl=row_tbl_arr, n_total=n_total)


def _open_args(ob: dict) -> tuple:
    """The open program's device arguments from :func:`_open_build`."""
    return (ob["tblr"], {k: v for k, v in ob["flat"].items()
                         if k != "client"}, ob["gidx"])


# ===================================================== closed-loop sweep
def _closed_point_build(p: SweepPoint, seed: int, dm: _DelayModel,
                        capacity: int, virtual_nodes: int) -> dict:
    """Host-side build of one closed-loop point: the exact schedules,
    routes, and per-op delay components a ``SimEdgeKV(engine="fast")``
    closed-loop run would use (shared extraction:
    :func:`~repro.sim.cluster.closed_loop_plan` +
    :func:`~repro.sim.vectorized.plan_columns`), flattened in (thread,
    op) order — the order that defines heap pid tie-breaks."""
    plan = closed_loop_plan([(gi, f"g{gi}", p.group_size)
                             for gi in range(p.groups)],
                            p.threads, p.ops,
                            dict(p_global=p.p_global,
                                 distribution=p.distribution,
                                 n_records=p.n_records), seed)
    cols = plan_columns(plan, lambda gid: int(gid[1:]))
    client, key_idx = cols["client"], cols["key_idx"]
    bounds = cols["bounds"]
    n = int(bounds[-1])
    is_w = cols["kind"] != READ_CODE
    glob = cols["dtype"] == GLOBAL_CODE
    serving = client.copy()
    hops = np.zeros(n, np.int32)
    if glob.any():
        topo = _topology(p.groups, virtual_nodes)
        owner, h = topo.routes(client[glob], key_idx[glob],
                               plan[0].wl.keys)
        serving[glob] = owner
        hops[glob] = h
    lf = (~glob) & cols["fwd"]
    remote = glob & (serving != client)

    def bw(pair):
        return np.where(is_w, pair[1], pair[0])

    first = np.zeros(n, bool)
    first[bounds[:-1]] = True
    flat = dict(
        c_req=bw(dm.c_req), f_req=bw(dm.f_req), sg_req=bw(dm.sg_req),
        h_req=bw(dm.h_req), sg_resp=bw(dm.sg_resp), g_resp=bw(dm.g_resp),
        f_resp=bw(dm.f_resp), c_resp=bw(dm.c_resp),
        svc_base=np.where(is_w, dm.svc_base[1], dm.svc_base[0]),
        q_ri=np.where(is_w, dm.quorum(p.group_size),
                      dm.readindex(p.group_size)),
        lf=lf, glob=glob, remote=remote, first=first, hops=hops,
        pred=np.maximum(np.arange(n, dtype=np.int64) - 1, 0),
        key=key_idx.astype(np.int64))

    # one row per serving group; a stable sort keyed by serving group
    # keeps members in ascending flat index = (pid, op) order, which is
    # what breaks exact arrival ties the way the heap engine's
    # (arrival, pid) tuples do
    order = np.argsort(serving, kind="stable")
    sv = serving[order]
    cuts = np.flatnonzero(sv[1:] != sv[:-1]) + 1
    rows: List[np.ndarray] = []
    evict = False
    for members in (np.split(order, cuts) if n else []):
        rows.append(members.astype(np.int64))
        # eviction is order-independent: a leader's LRU can only evict
        # when it ever holds more distinct keys than its capacity
        if np.unique(key_idx[members]).size > capacity:
            evict = True
    return dict(flat=flat, rows=rows, n=n, client=client, is_w=is_w,
                glob=glob, hops=hops, evict=evict,
                per_thread=max(1, p.ops // max(1, p.threads)),
                max_hops=int(hops.max()) if n else 0)


def _closed_assemble(blocks: Sequence[dict]) -> dict:
    """Concatenate per-point builds into one device block, rebasing the
    flat op index space (``pred`` and row members shift by offset)."""
    flat: Dict[str, np.ndarray] = {}
    for k in blocks[0]["flat"]:
        parts, off = [], 0
        for b in blocks:
            v = b["flat"][k]
            parts.append(v + off if k == "pred" else v)
            off += b["n"]
        flat[k] = np.concatenate(parts)
    rows: List[np.ndarray] = []
    off = 0
    for b in blocks:
        rows.extend(m + off for m in b["rows"])
        off += b["n"]
    return dict(flat=flat, rows=rows, n=off)


def _closed_pad(blk: dict, n_max: int, R_max: int, Ls_max: int
                ) -> Tuple[Dict[str, np.ndarray], Dict[str, np.ndarray]]:
    """Pad one device block to the fleet-wide shapes and precompute the
    static queue geometry the round program exploits.

    Row membership and keys never change across rounds — only arrival
    *values* do — so everything except the order within each row is
    known here, on the host, once:

    * ``row``  — each op's row (queue) id; pad ops get the one-past-end
      row so a single stable composite sort by ``(row, arrival)`` in op
      space replaces the padded per-row argsort (real ops only — no
      O(R*Ls) slot padding in the sort).
    * ``rank``/``dest`` — sorted *position* -> (queue rank, slot in the
      rectangular scan grid).  Row sizes are static, so position ``p``
      always lands in the same row at the same rank; the sorted
      arrivals scatter into the (R, Ls) max-plus grid through these
      static indices (pad positions index out of bounds and drop).
    * ``seg``  — segment id of each op's (row, key) group, so the
      seen-before LRU mask reduces to one ``segment_min`` over queue
      ranks instead of a sort-by-key round trip.

    Padding is inert by construction: pad ops are first-ops with
    all-zero delay columns (their completions converge to a constant in
    one round), sort after every real row, and never enter the scan
    grid — their departures gather the out-of-bounds fill."""
    n, pad = blk["n"], n_max - blk["n"]
    flat = {}
    for k, v in blk["flat"].items():
        if pad:
            fill = np.full(pad, k == "first") if v.dtype == bool \
                else np.zeros(pad, v.dtype)
            v = np.concatenate([v, fill])
        flat[k] = v
    flat["pred"] = flat["pred"].astype(np.int32)
    row_of = np.full(n_max, R_max, np.int32)
    rank = np.zeros(n_max, np.int32)
    dest = np.full(n_max, R_max * Ls_max, np.int32)
    off = 0
    for r, m in enumerate(blk["rows"]):
        row_of[m] = r
        rank[off:off + len(m)] = np.arange(len(m), dtype=np.int32)
        dest[off:off + len(m)] = r * Ls_max + np.arange(len(m),
                                                        dtype=np.int32)
        off += len(m)
    comp_key = (row_of.astype(np.int64) * (int(flat["key"].max()) + 2)
                + flat["key"] + 1)
    seg = np.unique(comp_key, return_inverse=True)[1].astype(np.int32)
    aux = dict(row=row_of, rank=rank, dest=dest, seg=seg)
    return flat, aux


@lru_cache(maxsize=None)
def _closed_round_fn(max_hops: int, scan_backend: str, interpret: bool,
                     max_rounds: int, seek: float, R: int, Ls: int):
    """The raw (unjitted) fixed-point program for one device block.

    With the exact ``seq`` backend every time is the int64 bit pattern of
    its IEEE double (:mod:`repro.sim.f64bits`), added with integer ops,
    so queue orders and the convergence test are bit-for-bit the host's
    on any device; the closed-form backends compute in float64."""
    if scan_backend == "seq":
        add = partial(f64bits.add, jnp)
        dtype, inf, neg_inf = jnp.int64, f64bits.INF, f64bits.NEG_INF
        seek_v = int(f64bits.to_bits(seek)[0])
    else:
        add = operator.add
        dtype, inf, neg_inf = jnp.float64, jnp.inf, -jnp.inf  # lint: ignore[EDK104] -- every caller traces under enable_x64 (see _run_closed)
        seek_v = seek

    def depart(grid_a, grid_s):
        # leader FIFO commit stage, batched over rows.  "seq" reproduces
        # the engine's exact sequential recurrence (required for the
        # <=1e-9 differential contract — see run_sweep); the closed-form
        # backends are ulp-reassociated
        if scan_backend == "pallas":
            return maxplus_depart(grid_a, grid_s, backend="pallas",
                                  block_rows=8, interpret=interpret)
        if scan_backend == "assoc":
            return maxplus_depart(grid_a, grid_s, backend="assoc")

        def step(d_prev, x):
            d = add(jnp.maximum(x[0], d_prev), x[1])
            return d, d
        _, dep = jax.lax.scan(step, jnp.full((R,), neg_inf, dtype),
                              (grid_a.T, grid_s.T))
        return dep.T

    def one_round(comp, flat, aux, pieces=None):
        n = comp.shape[0]
        t0 = jnp.where(flat["first"], jnp.zeros((), dtype),
                       jnp.take(comp, flat["pred"], mode="clip"))
        cuts = [] if pieces is not None else None
        arr = arrival_chain(jnp, t0, flat["c_req"], flat["f_req"],
                            flat["sg_req"], flat["h_req"], flat["lf"],
                            flat["glob"], flat["hops"], max_hops,
                            cuts=cuts, add=add)
        # one stable composite sort of the real ops by (row, arrival)
        # recovers every leader queue at once: stability breaks exact
        # arrival ties by flat index = (pid, op) order, the heap
        # engine's tie-break, and pad ops sort after every real row
        _, arr_ord, perm = jax.lax.sort(
            (aux["row"], arr, jnp.arange(n, dtype=jnp.int32)),
            num_keys=2, is_stable=True)
        # seen-before page penalties (the no-eviction LRU regime): an op
        # hits iff a same-key op sits earlier in its queue, i.e. its
        # rank exceeds the min rank of its static (row, key) segment;
        # ranks per sorted position are static (row sizes don't change)
        seg_ord = jnp.take(aux["seg"], perm)
        rmin = jax.ops.segment_min(aux["rank"], seg_ord, num_segments=n)
        pens = jnp.where(aux["rank"] > rmin[seg_ord], jnp.zeros((), dtype),
                         jnp.asarray(seek_v, dtype))
        svc_ord = add(jnp.take(flat["svc_base"], perm), pens)
        # scatter the ordered queues into the rectangular (R, Ls) grid
        # through the static position -> slot map (uncovered slots stay
        # +inf/0 and are never gathered back), then the departure scan
        grid_a = jnp.full((R * Ls,), inf, dtype).at[
            aux["dest"]].set(arr_ord, mode="drop").reshape(R, Ls)
        grid_s = jnp.zeros((R * Ls,), dtype).at[
            aux["dest"]].set(svc_ord, mode="drop").reshape(R, Ls)
        dep_grid = depart(grid_a, grid_s)
        dep_ord = jnp.take(dep_grid.reshape(-1), aux["dest"],
                           mode="fill", fill_value=0)
        dep = jnp.zeros((n,), dtype).at[perm].set(dep_ord)
        ccuts = [] if pieces is not None else None
        new = completion_chain(jnp, dep, flat["q_ri"], flat["sg_resp"],
                               flat["g_resp"], flat["f_resp"],
                               flat["c_resp"], flat["lf"], flat["glob"],
                               flat["remote"], cuts=ccuts, add=add)
        if pieces is not None:
            # span-model pieces: service start = max(arrival, previous
            # departure) per queue slot, clamped to the departure (the
            # closed-form scan backends may reassociate by an ulp)
            prev = jnp.concatenate(
                [jnp.full((R, 1), neg_inf, dtype), dep_grid[:, :-1]],
                axis=1)
            start_grid = jnp.minimum(jnp.maximum(grid_a, prev), dep_grid)
            start_ord = jnp.take(start_grid.reshape(-1), aux["dest"],
                                 mode="fill", fill_value=0)
            start = jnp.zeros((n,), dtype).at[perm].set(start_ord)
            pieces.extend([cuts[0], cuts[1], arr, start, dep, ccuts[0]])
        return new

    def run(flat, aux):
        n = flat["c_req"].shape[0]
        comp0 = jnp.full((n,), inf, dtype)

        def cond(carry):
            _, done, r = carry
            return jnp.logical_and(jnp.logical_not(done), r < max_rounds)

        def body(carry):
            comp, _, r = carry
            new = one_round(comp, flat, aux)
            return new, jnp.all(new == comp), r + 1

        comp, done, rounds = jax.lax.while_loop(
            cond, body, (comp0, jnp.asarray(False), jnp.asarray(0)))
        t0 = jnp.where(flat["first"], jnp.zeros((), dtype),
                       jnp.take(comp, flat["pred"], mode="clip"))
        # one idempotent replay of the converged round keeps the span
        # pieces (b_request, b_route, arrival, start, departure,
        # b_replicate) as extra device outputs — no host callbacks
        pieces: list = []
        one_round(comp, flat, aux, pieces=pieces)
        return comp, t0, done, rounds, jnp.stack(pieces)

    return run


@lru_cache(maxsize=None)
def _closed_exe(max_hops: int, scan_backend: str, interpret: bool,
                max_rounds: int, seek: float, R: int, Ls: int,
                devices: int):
    """Cached executable wrappers (jit, or jit of a point-sharded
    ``jax.shard_map`` for ``devices`` > 1) around the round program —
    cached so repeat sweeps reuse the compiled program.
    """
    run = _closed_round_fn(max_hops, scan_backend, interpret, max_rounds,
                           seek, R, Ls)
    if devices == 1:
        return jax.jit(run)
    return _shard_points(run, jax.devices()[:devices])


def _shard_points(run, devs: Sequence) -> object:
    """jit of ``run`` sharded over a leading point-block axis, one block
    per device of ``devs``."""
    from jax.sharding import Mesh, PartitionSpec

    mesh = Mesh(np.asarray(devs), ("pt",))
    spec = PartitionSpec("pt")

    def shard_fn(flat, aux):
        comp, t0, done, r, pieces = run(
            {k: v[0] for k, v in flat.items()},
            {k: v[0] for k, v in aux.items()})
        return comp[None], t0[None], done[None], r[None], pieces[None]

    # check_vma off: each shard runs its own data-dependent while_loop
    # trip count (idempotent past its fixed point, so shards that
    # converge early stay bit-identical to the single-device program)
    return jax.jit(jax.shard_map(shard_fn, mesh=mesh,
                                 in_specs=(spec, spec),
                                 out_specs=(spec,) * 5,
                                 check_vma=False))


def _closed_rounds_host(built: Sequence[dict], capacity: int, seek: float,
                        max_hops: int, max_rounds: int
                        ) -> Tuple[List[np.ndarray], List[np.ndarray],
                                   List[np.ndarray]]:
    """Host-side fixed point for grids in the eviction regime: same
    rounds, same float64 expressions, but page penalties come from the
    exact LRU replay (:func:`~repro.sim.vectorized.lru_hit_mask`, stack
    distances and all) instead of the in-program seen-before mask.

    Also returns the span-model pieces ``(b_request, b_route, arrival,
    start, departure, b_replicate)`` stacked per point: the round that
    detects convergence recomputes them from the already-converged
    completions, so its intermediates ARE the fixed point's.
    """
    comp_pt, t0_pt, pieces_pt = [], [], []
    for b in built:
        flat, n = b["flat"], b["n"]
        comp = np.full(n, np.inf)
        t0 = np.zeros(n)
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
                arr_o = arr[order].tolist()
                svc_o = svc.tolist()
                dep_o = np.empty(len(order))
                start_o = np.empty(len(order))
                d = -np.inf
                # sequential recurrence in the engine's exact float
                # order (start = max(a, free); dep = start + svc) —
                # the closed-form numpy scan reassociates and its ulp
                # drift can flip near-tied queue orders across rounds
                for j, (a_j, s_j) in enumerate(zip(arr_o, svc_o)):
                    st = a_j if a_j > d else d
                    start_o[j] = st
                    d = st + s_j
                    dep_o[j] = d
                dep[order] = dep_o
                start[order] = start_o
            ccuts: list = []
            new = completion_chain(np, dep, flat["q_ri"],
                                   flat["sg_resp"], flat["g_resp"],
                                   flat["f_resp"], flat["c_resp"],
                                   flat["lf"], flat["glob"],
                                   flat["remote"], cuts=ccuts)
            if np.array_equal(new, comp):
                break
            comp = new
        else:
            raise RuntimeError(
                f"closed-loop sweep did not converge in {max_rounds} "
                "rounds (host/LRU path); raise max_rounds")
        comp_pt.append(comp)
        t0_pt.append(t0)
        pieces_pt.append(np.stack([cuts[0], cuts[1], arr, start, dep,
                                   ccuts[0]]))
    return comp_pt, t0_pt, pieces_pt


def _run_closed(points: List[SweepPoint], *, setting: str, seed: int,
                service: Optional[ServiceParams], virtual_nodes: int,
                scan_backend: str, interpret: bool,
                percentiles: Sequence[float], devices: int,
                max_rounds: Optional[int]) -> SweepResult:
    t_wall = walltime()
    for p in points:
        if p.threads < 1 or p.ops < 1:
            raise ValueError(
                "closed-loop points need threads >= 1 and ops >= 1")
    svcp = service or ServiceParams()
    dm = _DelayModel(SETTINGS[setting], svcp)
    capacity = max(1, svcp.page_cache_keys)
    qs = tuple(float(q) for q in percentiles)

    built = [_closed_point_build(p, seed, dm, capacity, virtual_nodes)
             for p in points]
    max_hops = max(b["max_hops"] for b in built)
    if max_rounds is None:
        # the resolved wavefront advances >= 1 op per thread per round;
        # the slack covers order corrections rippling between threads
        max_rounds = 4 * max(b["per_thread"] for b in built) + 64
    seek = float(dm.seek)
    args = (max_hops, scan_backend, bool(interpret), int(max_rounds),
            seek)
    if devices > jax.local_device_count():
        raise ValueError(
            f"devices={devices} but only {jax.local_device_count()} "
            "jax devices visible (on CPU set XLA_FLAGS="
            "--xla_force_host_platform_device_count=N before "
            "importing jax)")

    if any(b["evict"] for b in built):
        comp_pt, t0_pt, pieces_pt = _closed_rounds_host(
            built, capacity, seek, max_hops, max_rounds)
        info: Dict[str, object] = dict(path="host")
    else:
        # one device block per shard; a single block runs unsharded
        D = min(devices, len(points))
        dev_pts = [[pi for pi in range(len(points)) if pi % D == d]
                   for d in range(D)]
        blks = [_closed_assemble([built[pi] for pi in idxs])
                for idxs in dev_pts]
        n_max = max(b["n"] for b in blks)
        R_max = max(len(b["rows"]) for b in blks)
        Ls_max = max(max(len(m) for m in b["rows"]) for b in blks)
        padded = [_closed_pad(b, n_max, R_max, Ls_max) for b in blks]
        exact = scan_backend == "seq"
        if exact:   # times travel as IEEE bit patterns (f64bits)
            padded = [({k: f64bits.to_bits(v) if v.dtype == np.float64
                        else v for k, v in f.items()}, a)
                      for f, a in padded]
        if D == 1:
            flat, aux = padded[0]
        else:   # one leading row per device block, stacked on the host
            flat = {k: np.stack([f[k] for f, _ in padded])
                    for k in padded[0][0]}
            aux = {k: np.stack([a[k] for _, a in padded])
                   for k in padded[0][1]}
        with jax.enable_x64(True):
            exe = _closed_exe(*args, R_max, Ls_max, D)
            t_dev = walltime()
            out = jax.device_get(exe(
                {k: jnp.asarray(v) for k, v in flat.items()},
                {k: jnp.asarray(v) for k, v in aux.items()}))
            device_s = walltime() - t_dev
        if D == 1:
            out = [np.asarray(o)[None] for o in out]
        comp_s, t0_s, done_s, rounds_s, pieces_s = out
        if exact:
            comp_s, t0_s, pieces_s = (f64bits.from_bits(v) for v in
                                      (comp_s, t0_s, pieces_s))
        if not bool(np.all(done_s)):
            raise RuntimeError(
                f"closed-loop sweep did not converge in {max_rounds} "
                "rounds; raise max_rounds")
        comp_pt = [np.empty(0)] * len(points)
        t0_pt = [np.empty(0)] * len(points)
        pieces_pt = [np.empty((6, 0))] * len(points)
        for d, idxs in enumerate(dev_pts):
            off = 0
            for pi in idxs:
                n = built[pi]["n"]
                comp_pt[pi] = comp_s[d, off:off + n]
                t0_pt[pi] = t0_s[d, off:off + n]
                pieces_pt[pi] = pieces_s[d, :, off:off + n]
                off += n
        info = dict(path="device", devices=D,
                    rounds=int(np.max(rounds_s)), device_s=device_s)

    # ---- fold into per-point RecordArray-style aggregates ----
    N = len(points)
    names = ("mean_latency", "read_latency", "update_latency",
             "local_latency", "global_latency", "update_global_latency")
    cols: Dict[str, np.ndarray] = {
        "ops": np.asarray([b["n"] for b in built], np.int64)}
    for name in names:
        cols[name] = np.zeros(N)
    cols["throughput"] = np.zeros(N)
    cols["mean_hops"] = np.zeros(N)
    for stage in OBS_STAGES:
        cols[f"stage_{stage}"] = np.zeros(N)
    tails = np.zeros((len(qs), N))
    for pi, (p, b) in enumerate(zip(points, built)):
        lat = np.asarray(comp_pt[pi]) - np.asarray(t0_pt[pi])
        is_w, glob = b["is_w"], b["glob"]

        # per-stage mean durations from the converged round's pieces;
        # closed points have no lease stage, so that bound repeats
        # b_route (zero duration)
        b_req, b_route, arr, start, dep, b_repl = np.asarray(
            pieces_pt[pi], np.float64)
        bounds9 = (np.asarray(t0_pt[pi]), b_req, b_route, b_route, arr,
                   start, dep, b_repl, np.asarray(comp_pt[pi]))
        for si, stage in enumerate(OBS_STAGES):
            d = bounds9[si + 1] - bounds9[si]
            cols[f"stage_{stage}"][pi] = (float(d.mean()) if len(d)
                                          else float("nan"))

        def mean(m):
            return float(lat[m].mean()) if m.any() else float("nan")

        cols["mean_latency"][pi] = float(lat.mean())
        cols["read_latency"][pi] = mean(~is_w)
        cols["update_latency"][pi] = mean(is_w)
        cols["local_latency"][pi] = mean(~glob)
        cols["global_latency"][pi] = mean(glob)
        cols["update_global_latency"][pi] = mean(is_w & glob)
        cols["mean_hops"][pi] = float(b["hops"].mean())
        # paper-metric throughput: mean of per-client-group rates, spans
        # from the same t_start/latency expressions RecordArray
        # group_stats folds
        ends = np.asarray(t0_pt[pi]) + lat
        rates = []
        for gi in range(p.groups):
            m = b["client"] == gi
            if not m.any():
                continue
            span = ends[m].max() - np.asarray(t0_pt[pi])[m].min()
            if span > 0:
                rates.append(int(m.sum()) / span)
        cols["throughput"][pi] = (sum(rates) / len(rates) if rates
                                  else 0.0)
        if qs:
            tails[:, pi] = np.percentile(lat, qs)
    for q, t in zip(qs, tails):
        cols[f"p{q:g}_latency"] = t
    return SweepResult(points, cols, walltime() - t_wall, info)
