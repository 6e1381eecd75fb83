"""End-to-end driver (the paper's kind is a database => serving):

Graph500 RMAT graph -> snapshot persistence -> continuous-batching query
serving with the QueryServer (the TPU analog of RedisGraph's threadpool)
under Poisson open-loop arrivals, measuring queries/sec, p50/p99 latency,
plan-cache hit rate and packed-lane utilization for the paper's k-hop
workload.

  PYTHONPATH=src python examples/serve_queries.py [--scale 11] [--queries 300]
"""
import argparse
import os
import sys
import tempfile
import time

import numpy as np

from repro import compile_cache
from repro.engine import QueryServer, load_snapshot, save_snapshot
from repro.graph.datagen import rmat_graph

ap = argparse.ArgumentParser()
ap.add_argument("--scale", type=int, default=11)
ap.add_argument("--queries", type=int, default=300)
ap.add_argument("--k", type=int, default=2)
ap.add_argument("--rate", type=float, default=2000.0,
                help="offered Poisson arrival rate, queries/sec")
args = ap.parse_args()
compile_cache.enable()

print(f"[1/4] generating Graph500 RMAT scale={args.scale} ...")
g = rmat_graph(scale=args.scale, edge_factor=8, seed=0, fmt="bsr", block=128)
print(f"      {g.n} vertices, {g.nnz} edges")

print("[2/4] snapshot round-trip (RDB analog) ...")
snap = os.path.join(tempfile.mkdtemp(prefix="repro_rdb_"), "g500.npz")
save_snapshot(g, snap)
g = load_snapshot(snap, fmt="bsr", block=128)
print(f"      restored from {snap}")

print(f"[3/4] serving {args.queries} k={args.k}-hop queries "
      f"(Poisson open-loop @ {args.rate:.0f} q/s) ...")
rng = np.random.default_rng(0)
seeds = rng.integers(0, g.n, size=args.queries)
arrivals = np.cumsum(rng.exponential(1.0 / args.rate, size=args.queries))
srv = QueryServer(g)
template = (f"MATCH (a)-[:KNOWS*1..{args.k}]->(b) "
            f"RETURN count(DISTINCT b)")

out, qids = {}, []
i = 0
t0 = time.perf_counter()
while len(out) < args.queries:
    now = time.perf_counter() - t0
    while i < args.queries and arrivals[i] <= now:
        qids.append(srv.submit(template, seeds=[int(seeds[i])],
                               arrival_s=t0 + arrivals[i]))
        i += 1
    if srv.pending:
        out.update(srv.pump())
    elif i < args.queries:
        time.sleep(min(arrivals[i] - now, 1e-3))
dt = time.perf_counter() - t0

print("[4/4] results:")
errors = [(q, out[q].error) for q in qids if out[q].error is not None]
if errors:
    for q, err in errors[:10]:
        print(f"      query {q} failed: {err}", file=sys.stderr)
    sys.exit(f"{len(errors)} of {len(qids)} queries failed")
counts = [out[q].scalar() for q in qids]
lat = np.array([m.latency_s for m in srv.log])
p50, p99 = np.percentile(lat, [50, 99])
print(f"      batches={srv.stats['batches']} "
      f"(width {srv.stats['batched_width_total']}, "
      f"max {srv.stats['batch_width_max']}, "
      f"pack ratio {srv.stats['pack_ratio']:.2f})")
print(f"      plan cache: {srv.stats['plan_cache_hits']} hits / "
      f"{srv.stats['plan_cache_misses']} misses "
      f"(hit rate {srv.stats['plan_cache_hit_rate']:.2f})")
print(f"      total {dt * 1e3:.1f} ms, {args.queries / dt:.0f} queries/s, "
      f"latency p50={p50 * 1e3:.1f} ms p99={p99 * 1e3:.1f} ms")
print(f"      count stats: min={min(counts)} max={max(counts)} "
      f"mean={np.mean(counts):.1f}")
