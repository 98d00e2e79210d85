"""Quickstart: the EdgeKV store end to end in 60 lines.

Run: PYTHONPATH=src python examples/quickstart.py
"""
from repro.core import EdgeKVCluster, LOCAL, GLOBAL

# Three edge groups x three storage nodes, gateways on a Chord ring,
# backup groups wired per §7.3.
cluster = EdgeKVCluster([3, 3, 3], seed=42, backup_groups=True,
                        gateway_cache=128)

# --- local data: stays in the client's group (fast path, private) -------
cluster.put("sensor:42:reading", 21.5, LOCAL, client_group="g0")
r = cluster.get("sensor:42:reading", LOCAL, client_group="g0")
print(f"local read from g0: {r.value} (quorum={r.quorum_size})")
print("visible from g1's local store?",
      cluster.get("sensor:42:reading", LOCAL, client_group="g1").value)

# --- global data: consistent-hash placed, visible everywhere ------------
cluster.put("city:temperature", 18.0, GLOBAL, client_group="g0")
for g in ("g0", "g1", "g2"):
    r = cluster.get("city:temperature", GLOBAL, client_group=g)
    print(f"global read from {g}: {r.value} "
          f"(dht_path={getattr(r, 'dht_path', None)})")

# --- strong consistency: update then read-anywhere ----------------------
cluster.put("city:temperature", 18.5, GLOBAL, client_group="g2")
assert cluster.get("city:temperature", GLOBAL,
                   client_group="g1").value == 18.5
print("linearizable update visible everywhere: ok")

# --- fault tolerance: kill a minority of the owner group ----------------
owner_gw = cluster.ring.locate("city:temperature")
owner = cluster.gateways[owner_gw].group
victims = owner.crash_minority()
r = cluster.get("city:temperature", GLOBAL, client_group="g0")
print(f"after crashing {victims} in owner group {owner.id}: "
      f"read still ok -> {r.value}")

# --- §7.3: kill the majority, reads fail over to the backup group -------
owner.crash_majority()
r = cluster.get("city:temperature", GLOBAL, client_group="g0")
print(f"after majority loss: value={r.value} "
      f"from_backup={getattr(r, 'from_backup', False)}")
w = cluster.put("city:temperature", 99.0, GLOBAL, client_group="g0")
print(f"writes while owner down are rejected: ok={w.ok} "
      "(backup stays read-only so states never diverge)")

# --- testbed emulation: the same protocol under YCSB load ---------------
# Engine matrix:
#   engine="oracle"  one Python generator per client thread stepped by the
#                    event heap — the semantic ground truth.
#   engine="fast"    vectorized backend (batched numpy op schedules + a
#                    per-group max-plus commit-stage scan via
#                    repro.kernels.maxplus_scan) — bit-identical latency
#                    traces to the oracle on closed-loop runs, ~10x less
#                    wall clock. Open loop + churn runs statistically.
#   run_sweep(...)   the sweep engine: N open-loop configurations
#                    jit-compiled into ONE JAX array program — each point
#                    identical to an engine="fast" run on the same seeds.
from repro.sim import SimEdgeKV

sim = SimEdgeKV(setting="edge", seed=0, engine="fast")
sim.run_closed_loop(threads_per_client=100, ops_per_client=1000,
                    workload_kw=dict(p_global=0.5))
print(f"emulated 300 clients x YCSB-A at 50% global: "
      f"write latency {1e3 * sim.mean_latency(kind='update'):.1f} ms, "
      f"p99 {1e3 * sim.tail_latency(99):.1f} ms, "
      f"throughput {sim.throughput():.0f} ops/s "
      f"({len(sim.records)} ops, vectorized engine)")

# --- parameter sweeps: a whole what-if grid as one array program --------
# EdgeKV's evaluation is a grid of scenarios; run_sweep evaluates a
# p_global x contention x rate x groups grid in a single jitted call
# (scan_backend="pallas" with interpret=True routes the departure scan
# through the Pallas kernel in interpret mode).
from repro.sim import SweepPoint, run_sweep

grid = [SweepPoint(p_global=pg, rate=rate, groups=3)
        for pg in (0.0, 0.5, 1.0) for rate in (200.0, 400.0)]
res = run_sweep(grid, duration=2.0, seed=0)
print(f"swept {len(res)} configs in one jitted program "
      f"({res.walltime_s:.2f}s):")
for row in res.rows():
    print(f"  p_global={row['p_global']:.1f} rate={row['rate']:.0f}: "
          f"mean {1e3 * row['mean_latency']:.1f} ms, "
          f"p99 {1e3 * row['p99_latency']:.1f} ms, "
          f"tput {row['throughput']:.0f} ops/s")
