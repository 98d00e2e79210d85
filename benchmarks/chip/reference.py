"""Plain reference of the EdgeKV sweep semantics, for the benchmark's check.

It imports nothing of the program under test.  From a configuration
file's numbers and a traffic grid it draws the same YCSB workload A
schedules from the seed, routes global operations over a Chord ring,
and simulates every operation's path through the edge deployment one
operation at a time:

* client -> edge node (-> leader when the contacted node is a follower)
  for local data; client -> edge node -> gateway -> Chord overlay hops
  -> owner gateway -> owner group's leader for global data;
* each group leader is a first-come-first-served stage of capacity one
  (ties in arrival time go to the lower worker id), whose service time
  pays a cold-page seek when the key is not in the leader's LRU page
  cache;
* writes then wait for the Raft quorum acknowledgement, reads for the
  ReadIndex heartbeat round, and the response retraces the path.

Closed loop: every worker thread issues its next operation the instant
the previous one completes, so the simulation runs on an event heap of
leader arrivals.  Open loop: Poisson arrivals per client group.

``rnd`` rounds every time and every sum of times.  The identity gives
IEEE binary64 (the precision the configuration states); rounding to
float32 (:func:`to_float32`) is the control that the check has to
reject.

Results are folded per grid point into the columns a sweep reports:
mean latencies by kind and data type, the paper's throughput (mean over
client groups of operations over their span), p95/p99 latency, mean
overlay hops (closed loop) and the mean duration of each of the eight
span-model stages.
"""
from __future__ import annotations

import bisect
import hashlib
import heapq
import math
import zlib
from collections import OrderedDict
from itertools import product
from typing import Callable, Dict, Iterable, List, Sequence, Tuple

import numpy as np

RING_BITS = 64
RING = 1 << RING_BITS
STAGES = ("request", "route", "lease", "ingress", "queue", "service",
          "replicate", "response")


def identity(x: float) -> float:
    return x


def to_float32(x: float) -> float:
    return float(np.float32(x))


# ------------------------------------------------------------ the grid
def grid_points(config: dict, traffic: dict) -> List[dict]:
    """Deployment points of a traffic grid: the configuration's settings
    with each combination of the traffic's axes (in the file's order)
    laid over them."""
    base = {k: config[k] for k in ("p_global", "groups", "group_size",
                                   "threads", "ops_per_client",
                                   "n_records", "distribution")}
    base.update(traffic.get("fixed", {}))
    axes = traffic.get("axes", {})
    names = list(axes)
    return [dict(base, **dict(zip(names, combo)))
            for combo in product(*(axes[n] for n in names))]


def request(config: dict, traffic: dict, run_seed: int
            ) -> Tuple[List[dict], int]:
    """The sweep request of one run and its workload seed, both drawn
    from the run's seed: the workload seed from the traffic's
    ``workload_seeds`` (schedules chosen to give the same program shapes
    and round count, so every run does the same amount of work and
    finds its program in the compile cache), and the order of the
    grid's points."""
    seeds = traffic["workload_seeds"]
    rng = np.random.default_rng(run_seed)
    seed = int(seeds[int(rng.integers(len(seeds)))])
    points = grid_points(config, traffic)
    order = rng.permutation(len(points))
    return [points[i] for i in order], seed


def longest_queue(config: dict, traffic: dict, points: Sequence[dict],
                  seed: int) -> int:
    """Most operations any one leader serves in one point of a closed
    sweep request: the length of the longest queue the departure scan
    walks."""
    d = Delays(config, identity)
    out = 0
    for p in points:
        paths, _ = _paths(closed_schedule(p, seed, config), p, d)
        out = max(out, max(np.bincount([q.leader for q in paths])))
    return int(out)


# ------------------------------------------------------------- delays
class Delays:
    """Per-operation delay terms of one configuration: Table 3 link
    transfers (latency + serialisation) and the hosts' service times."""

    def __init__(self, config: dict, rnd: Callable[[float], float]):
        self.rnd = rnd
        links = config["links"]
        svc = config["service"]
        req_b, rec_b, ack_b = (config["request_bytes"],
                               config["record_bytes"], config["ack_bytes"])

        def xfer(kind: str, nbytes: int) -> float:
            lat = links[kind]["latency_ms"] * 1e-3
            bw = links[kind]["bandwidth_mbps"] * 1e6
            return rnd(lat + (8.0 * nbytes) / bw)

        self.xfer = xfer
        # [read, write] request and response sizes
        req = (req_b, req_b + rec_b)
        resp = (req_b + rec_b, req_b)
        self.cli_req = [xfer("cli_st", b) for b in req]
        self.cli_resp = [xfer("cli_st", b) for b in resp]
        self.fwd_req = [xfer("st_st", b) for b in req]
        self.fwd_resp = [xfer("st_st", b) for b in resp]
        self.gw_req = [xfer("st_gw", b) for b in req]
        self.gw_resp = [xfer("st_gw", b) for b in resp]
        self.hop_req = [rnd(xfer("gw_gw", b) + svc["gw_route_s"])
                        for b in req]
        self.hop_resp = [xfer("gw_gw", b) for b in resp]
        self.service = [rnd(svc["read_s"]), rnd(svc["commit_s"])]
        self.seek = rnd(svc["seek_s"])
        self.cache_keys = max(1, int(svc["page_cache_keys"]))
        self.quorum_payload = rec_b + ack_b
        self.ack_b = ack_b
        self.follower_append = svc["follower_append_s"]

    def replicate(self, group_size: int, is_write: bool) -> float:
        """Raft quorum acknowledgement (write) or ReadIndex heartbeat
        round (read); nothing to wait for in a group of one."""
        if group_size // 2 == 0:
            return 0.0
        if is_write:
            return self.rnd(self.rnd(self.xfer("st_st", self.quorum_payload)
                                     + self.follower_append)
                            + self.xfer("st_st", self.ack_b))
        return self.rnd(2 * self.xfer("st_st", self.ack_b))


# -------------------------------------------------------------- Chord
def ring_hash(text: str) -> int:
    return int.from_bytes(hashlib.sha1(text.encode()).digest()[:8],
                          "big") % RING


class Chord:
    """One gateway per group (one virtual node each) on a 64-bit Chord
    ring; finger ``i`` of a node points at the successor of
    ``node + 2**i``.  Lookups start at the client group's gateway."""

    def __init__(self, groups: int):
        self.pos = [ring_hash(f"vnode-0:gw{g}") for g in range(groups)]
        if len(set(self.pos)) != groups:
            raise ValueError("gateway ring positions collide")
        self.sorted = sorted(self.pos)
        self.group_at = {h: g for g, h in enumerate(self.pos)}
        self.fingers = {h: [self.successor((h + (1 << i)) % RING)
                            for i in range(RING_BITS)]
                        for h in self.pos}
        self.memo: Dict[Tuple[int, int], Tuple[int, int]] = {}

    def successor(self, point: int) -> int:
        i = bisect.bisect_left(self.sorted, point)
        return self.sorted[i % len(self.sorted)]

    @staticmethod
    def between(x: int, a: int, b: int) -> bool:
        """x in the open ring interval (a, b)."""
        return a < x < b if a < b else (x > a or x < b)

    def route(self, start: int, key_hash: int) -> Tuple[int, int]:
        """(owner group, overlay hops) of a lookup from ``start``'s
        gateway.  A lookup's path depends on the key only through the
        key's successor node (no node lies between the key and it), so
        paths are memoised per (start, successor)."""
        owner_pos = self.successor(key_hash)
        memo_key = (start, owner_pos)
        hit = self.memo.get(memo_key)
        if hit is not None:
            return hit
        owner = self.group_at[owner_pos]
        path = [start]
        cur = self.pos[start]
        if owner != start:
            while True:
                succ = self.successor((cur + 1) % RING)
                if self.between(key_hash, cur, succ) or key_hash == succ:
                    nxt = succ
                else:
                    nxt = next((f for f in reversed(self.fingers[cur])
                                if self.between(f, cur, key_hash)), succ)
                cur = nxt
                g = self.group_at[cur]
                if path[-1] != g:
                    path.append(g)
                if g == owner:
                    break
        self.memo[memo_key] = (owner, len(path) - 1)
        return self.memo[memo_key]


# ---------------------------------------------------- YCSB schedules
def ycsb_draw(rng: np.random.Generator, count: int, wl_seed: int,
              point: dict, config: dict) -> Tuple[np.ndarray, ...]:
    """``count`` YCSB workload A operations: key index, is-read,
    is-global.  ``zipfian`` is the paper's hot set: a seed-drawn
    ``hotset_fraction`` of the keys takes ``hot_op_fraction`` of the
    requests."""
    n = point["n_records"]
    if point["distribution"] == "uniform":
        key = rng.integers(0, n, size=count)
    elif point["distribution"] == "zipfian":
        perm = np.random.default_rng(np.random.SeedSequence(
            [wl_seed & 0xFFFFFFFF, 0x5E7])).permutation(n)
        k = max(1, int(config["hotset_fraction"] * n))
        hot_set, cold_set = perm[:k], perm[k:]
        hot = rng.random(count) < config["hot_op_fraction"]
        hi = rng.integers(0, len(hot_set), size=count)
        ci = rng.integers(0, len(cold_set), size=count)
        key = np.where(hot, hot_set[hi], cold_set[ci])
    else:
        raise ValueError(f"distribution {point['distribution']!r}")
    is_read = rng.random(count) < config["read_proportion"]
    is_global = rng.random(count) < point["p_global"]
    return key.astype(np.int64), is_read, is_global


def forward_coins(rng: np.random.Generator, count: int, is_global,
                  group_size: int) -> np.ndarray:
    """Local requests reach a follower, which forwards them to the
    leader, with probability (n - 1) / n."""
    return ~is_global & (rng.random(count) < (group_size - 1) / group_size)


def closed_schedule(point: dict, seed: int, config: dict) -> dict:
    """Per worker thread (group-major) op schedules of a closed-loop
    point; group ``g``'s stream is seeded by ``1000 + g + seed``."""
    per_thread = max(1, point["ops_per_client"] // point["threads"])
    total = per_thread * point["threads"]
    cols = {k: [] for k in ("client", "key", "read", "glob", "fwd")}
    for g in range(point["groups"]):
        wl_seed = 1000 + g + seed
        rng = np.random.default_rng(np.random.SeedSequence(
            [wl_seed & 0xFFFFFFFF]))
        key, rd, gl = ycsb_draw(rng, total, wl_seed, point, config)
        fwd = forward_coins(rng, total, gl, point["group_size"])
        for k, v in (("client", np.full(total, g)), ("key", key),
                     ("read", rd), ("glob", gl), ("fwd", fwd)):
            cols[k].append(v)
    out = {k: np.concatenate(v) for k, v in cols.items()}
    out["per_thread"] = per_thread
    return out


def open_schedule(point: dict, seed: int, duration: float,
                  config: dict) -> dict:
    """Poisson arrivals at ``rate`` per client group over ``duration``
    virtual seconds (the arrival that first passes ``duration`` is still
    sent), each group drawing from a stream seeded by its workload seed
    ``2000 + g`` and its arrival seed (crc32 of its id mixed with the
    sweep seed)."""
    rate = point["rate"]
    cols = {k: [] for k in ("client", "t0", "key", "read", "glob", "fwd")}
    for g in range(point["groups"]):
        wl_seed = 2000 + g
        aseed = zlib.crc32(f"g{g}".encode()) ^ (
            ((seed + 1) * 0x9E3779B9) & 0xFFFFFFFF)
        rng = np.random.default_rng(np.random.SeedSequence(
            [wl_seed & 0xFFFFFFFF, aseed]))
        chunk = max(64, int(rate * duration * 1.2) + 8)
        t = np.empty(0)
        while t.size == 0 or t[-1] < duration:
            gaps = rng.exponential(1.0 / rate, size=chunk)
            t = np.concatenate([t, (t[-1] if t.size else 0.0)
                                + np.cumsum(gaps)])
        count = int(np.searchsorted(t, duration, side="left")) + 1
        key, rd, gl = ycsb_draw(rng, count, wl_seed, point, config)
        fwd = forward_coins(rng, count, gl, point["group_size"])
        for k, v in (("client", np.full(count, g)), ("t0", t[:count]),
                     ("key", key), ("read", rd), ("glob", gl),
                     ("fwd", fwd)):
            cols[k].append(v)
    return {k: np.concatenate(v) for k, v in cols.items()}


# ------------------------------------------------------ one operation
class Path:
    """Static facts of one op: its serving leader and every delay term,
    in the order the request and the response pay them."""
    __slots__ = ("leader", "hops", "req", "service", "resp", "is_w",
                 "glob", "read")

    def __init__(self, d: Delays, client: int, read: bool, glob: bool,
                 fwd: bool, owner: int, hops: int, group_size: int):
        w = 0 if read else 1
        self.read, self.glob, self.hops = read, glob, hops
        self.leader = owner if glob else client
        # request terms, grouped by stage: [request], [route], [ingress]
        request = [d.cli_req[w]]
        if glob:
            request.append(d.gw_req[w])
        elif fwd:
            request.append(d.fwd_req[w])
        route = [d.hop_req[w]] * hops
        ingress = [d.gw_req[w]] if glob else []
        self.req = (request, route, ingress)
        self.service = d.service[w]
        # response terms: [replicate], [response]
        back = []
        if glob:
            back.append(d.gw_resp[w])
            if owner != client:
                back.append(d.hop_resp[w])
            back.append(d.gw_resp[w])
        elif fwd:
            back.append(d.fwd_resp[w])
        back.append(d.cli_resp[w])
        self.resp = (d.replicate(group_size, not read), back)


def walk(t: float, terms: Iterable[float], rnd) -> float:
    for x in terms:
        t = rnd(t + x)
    return t


class Leader:
    """A group leader: FCFS stage of capacity one and an LRU page cache
    (a hit refreshes the key, a miss inserts it and may evict)."""

    def __init__(self, capacity: int):
        self.free_at = -math.inf
        self.cache: "OrderedDict[int, None]" = OrderedDict()
        self.capacity = capacity

    def serve(self, arrival: float, key: int, service: float,
              seek: float, rnd) -> Tuple[float, float]:
        if key in self.cache:
            self.cache.move_to_end(key)
            penalty = 0.0
        else:
            self.cache[key] = None
            if len(self.cache) > self.capacity:
                self.cache.popitem(last=False)
            penalty = seek
        start = max(arrival, self.free_at)
        self.free_at = rnd(start + rnd(service + penalty))
        return start, self.free_at


def _paths(sched: dict, point: dict, d: Delays) -> Tuple[List[Path], list]:
    chord = Chord(point["groups"])
    key_hash = [ring_hash(f"user{i:08d}") for i in range(point["n_records"])]
    paths = []
    for client, key, rd, gl, fw in zip(sched["client"].tolist(),
                                       sched["key"].tolist(),
                                       sched["read"].tolist(),
                                       sched["glob"].tolist(),
                                       sched["fwd"].tolist()):
        owner, hops = (chord.route(client, key_hash[key]) if gl
                       else (client, 0))
        paths.append(Path(d, client, rd, gl, fw, owner, hops,
                          point["group_size"]))
    return paths, sched["key"].tolist()


def _bounds(t0: float, p: Path, start: float, dep: float, rnd) -> list:
    """Nine stage boundaries of a served op: start, the ends of
    request, route, lease (none here), ingress, queue, service,
    replicate and response."""
    request, route, ingress = p.req
    b_req = walk(t0, request, rnd)
    b_route = walk(b_req, route, rnd)
    arr = walk(b_route, ingress, rnd)
    rep, back = p.resp
    b_repl = rnd(dep + rep)
    return [t0, b_req, b_route, b_route, arr, start, dep, b_repl,
            walk(b_repl, back, rnd)]


def simulate_closed(point: dict, seed: int, config: dict,
                    rnd=identity) -> Tuple[np.ndarray, np.ndarray, dict]:
    d = Delays(config, rnd)
    sched = closed_schedule(point, seed, config)
    paths, keys = _paths(sched, point, d)
    per = sched["per_thread"]
    n = len(paths)
    leaders = [Leader(d.cache_keys) for _ in range(point["groups"])]
    bounds = np.empty((n, 9))

    def arrival(i: int, t0: float) -> float:
        request, route, ingress = paths[i].req
        return walk(walk(walk(t0, request, rnd), route, rnd), ingress, rnd)

    heap = [(arrival(w * per, 0.0), w, w * per, 0.0)
            for w in range(n // per)]
    heapq.heapify(heap)
    while heap:
        arr, w, i, t0 = heapq.heappop(heap)
        p = paths[i]
        start, dep = leaders[p.leader].serve(arr, keys[i], p.service,
                                             d.seek, rnd)
        b = _bounds(t0, p, start, dep, rnd)
        bounds[i] = b
        if i + 1 < (w + 1) * per:
            heapq.heappush(heap, (arrival(i + 1, b[8]), w, i + 1, b[8]))
    return bounds, sched["client"], dict(sched, paths=paths)


def simulate_open(point: dict, seed: int, duration: float, config: dict,
                  rnd=identity) -> Tuple[np.ndarray, np.ndarray, dict]:
    d = Delays(config, rnd)
    sched = open_schedule(point, seed, duration, config)
    paths, keys = _paths(sched, point, d)
    t0s = [rnd(t) for t in sched["t0"].tolist()]
    arrs = []
    for t0, p in zip(t0s, paths):
        request, route, ingress = p.req
        arrs.append(walk(walk(walk(t0, request, rnd), route, rnd),
                         ingress, rnd))
    leaders = [Leader(d.cache_keys) for _ in range(point["groups"])]
    bounds = np.empty((len(paths), 9))
    # every leader serves in arrival order, ties to the earlier op
    for i in sorted(range(len(paths)), key=lambda i: (arrs[i], i)):
        p = paths[i]
        start, dep = leaders[p.leader].serve(arrs[i], keys[i], p.service,
                                             d.seek, rnd)
        bounds[i] = _bounds(t0s[i], p, start, dep, rnd)
    return bounds, sched["client"], dict(sched, paths=paths)


# --------------------------------------------------------------- fold
def fold(bounds: np.ndarray, client: np.ndarray, sched: dict,
         groups: int, loop: str) -> Dict[str, float]:
    t0, end = bounds[:, 0], bounds[:, 8]
    lat = end - t0
    read, glob = sched["read"], sched["glob"]

    def mean(mask) -> float:
        return float(lat[mask].mean()) if mask.any() else math.nan

    out = {"ops": float(len(lat)),
           "mean_latency": float(lat.mean()),
           "read_latency": mean(read), "update_latency": mean(~read),
           "local_latency": mean(~glob), "global_latency": mean(glob),
           "update_global_latency": mean(~read & glob)}
    rates = []
    for g in range(groups):
        m = client == g
        if m.any():
            span = end[m].max() - t0[m].min()
            if span > 0:
                rates.append(int(m.sum()) / span)
    out["throughput"] = sum(rates) / len(rates) if rates else 0.0
    p95, p99 = np.percentile(lat, (95.0, 99.0))
    out["p95_latency"], out["p99_latency"] = float(p95), float(p99)
    if loop == "closed":
        out["mean_hops"] = float(np.mean([p.hops for p in sched["paths"]]))
    for s, stage in enumerate(STAGES):
        out[f"stage_{stage}"] = float((bounds[:, s + 1] - bounds[:, s])
                                      .mean())
    return out


def reference_sweep(config: dict, traffic: dict, points: Sequence[dict],
                    seed: int, rnd=identity) -> List[Dict[str, float]]:
    """Folded columns of every point of a sweep request."""
    out = []
    for p in points:
        if traffic["loop"] == "closed":
            b, c, s = simulate_closed(p, seed, config, rnd)
        else:
            b, c, s = simulate_open(p, seed, traffic["duration_s"], config,
                                    rnd)
        out.append(fold(b, c, s, p["groups"], traffic["loop"]))
    return out


# ------------------------------------------------------------ compare
def worst_gap(got: Sequence[Dict[str, float]],
              want: Sequence[Dict[str, float]]) -> Tuple[float, str]:
    """Largest relative gap ``|got - want| / |want|`` over every column
    of every point, with where it is.  Both NaN counts as agreement (a
    mean over no operations); a column missing on either side, a NaN on
    one side only, or any gap to an exact zero is an infinite gap."""
    if len(got) != len(want):
        return math.inf, f"{len(got)} points, reference has {len(want)}"
    worst, where = 0.0, ""
    for i, (g_row, w_row) in enumerate(zip(got, want)):
        for name in sorted(set(g_row) | set(w_row)):
            if name not in g_row or name not in w_row:
                return math.inf, f"point {i} column {name} missing"
            g, w = float(g_row[name]), float(w_row[name])
            if math.isnan(g) and math.isnan(w):
                continue
            if g == w:
                continue
            gap = abs(g - w) / abs(w) if w != 0 and math.isfinite(w) \
                else math.inf
            if not gap <= worst:   # a NaN gap is the worst
                worst, where = gap, f"point {i} {name}"
                if math.isnan(gap):
                    return math.inf, where
    return worst, where
