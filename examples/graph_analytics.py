"""Graph analytics over semirings: PageRank (plus_times), SSSP (min_plus),
WCC (min-seed boolean closures), triangles (plus_pair) — each a different
GraphBLAS semiring on the same stored graph — then the same k-hop run on a
device mesh through `grb.distribute` (zero algorithm changes; wide boolean
frontiers ride the bitmap-packed path automatically).

  PYTHONPATH=src python examples/graph_analytics.py
"""
import numpy as np

from repro import algorithms as alg, compile_cache
from repro.core import grb
from repro.graph.datagen import rmat_edges
from repro.graph.graph import GraphBuilder

compile_cache.enable()
src, dst, n = rmat_edges(scale=10, edge_factor=8, seed=1)
keep = src != dst
src, dst = src[keep], dst[keep]
rng = np.random.default_rng(0)
w = rng.uniform(0.5, 3.0, size=src.shape[0]).astype(np.float32)
g = GraphBuilder(n).add_edges("E", src, dst, w).build(fmt="bsr", block=128)
rel = g.relations["E"]
print(f"graph: {n} vertices, {rel.nnz} edges")

pr = np.asarray(alg.pagerank(rel, iters=40))
top = np.argsort(-pr)[:5]
print(f"pagerank (plus_times): top-5 hubs {top.tolist()}, "
      f"mass {pr[top].sum():.3f}")

dist = np.asarray(alg.sssp(rel, [0]))[:, 0]
reach = np.isfinite(dist)
print(f"sssp (min_plus) from 0: reaches {reach.sum()} vertices, "
      f"max dist {dist[reach].max():.2f}")

cc = np.asarray(alg.wcc(rel))
print(f"wcc (min-label): {len(np.unique(cc))} components")

# triangles need a symmetric graph
s2 = np.concatenate([src, dst])
d2 = np.concatenate([dst, src])
gu = GraphBuilder(n).add_edges("E", s2, d2).build(fmt="bsr", block=128)
t = int(alg.triangle_count(gu.relations["E"]))
print(f"triangles (plus_pair, GraphChallenge): {t}")

# the distributed surface: re-home the graph onto a mesh (ELL rows shard
# over "data") and run the unchanged algorithm — each or_and hop all-gathers
# a bitmap-packed frontier (128 seeds = 4 uint32 words per row, 32x less
# wire than float32 indicators).
import jax  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

ge = GraphBuilder(n).add_edges("E", src, dst, w).build(fmt="ell")
mesh = Mesh(np.array(jax.devices()).reshape(-1, 1, 1),
            ("data", "pod", "model"))
sharded = grb.distribute(ge.relations["E"].A, mesh)
seeds = np.arange(128)
local = np.asarray(alg.khop_counts(ge.relations["E"], seeds, k=2))
dist = np.asarray(alg.khop_counts(sharded, seeds, k=2))
assert (local == dist).all(), "sharded khop diverged"
print(f"sharded khop (mesh of {mesh.devices.size}, packed frontiers): "
      f"bit-identical over {len(seeds)} seeds")
