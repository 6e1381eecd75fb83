"""Graph algorithms vs pure-python oracles on random graphs."""
import heapq
from collections import deque

import jax.numpy as jnp
import numpy as np
import pytest

from repro import algorithms as alg
from repro.graph.graph import GraphBuilder

N = 220


def rand_digraph(n=N, m=1400, seed=0, weighted=False):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, size=m)
    dst = rng.integers(0, n, size=m)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    w = rng.uniform(0.5, 3.0, size=src.shape[0]).astype(np.float32) if weighted else None
    return src, dst, w


def adj_list(src, dst, n, w=None):
    out = [[] for _ in range(n)]
    for i in range(len(src)):
        out[src[i]].append((int(dst[i]), float(w[i]) if w is not None else 1.0))
    return out


def py_bfs(adj, seed, n):
    lvl = [float("inf")] * n
    lvl[seed] = 0
    q = deque([seed])
    while q:
        u = q.popleft()
        for v, _ in adj[u]:
            if lvl[v] == float("inf"):
                lvl[v] = lvl[u] + 1
                q.append(v)
    return lvl


def py_dijkstra(adj, seed, n):
    dist = [float("inf")] * n
    dist[seed] = 0.0
    h = [(0.0, seed)]
    while h:
        d, u = heapq.heappop(h)
        if d > dist[u]:
            continue
        for v, w in adj[u]:
            nd = d + w
            if nd < dist[v] - 1e-9:
                dist[v] = nd
                heapq.heappush(h, (nd, v))
    return dist


@pytest.fixture(scope="module", params=["bsr", "ell"])
def graph_fixture(request):
    src, dst, _ = rand_digraph(seed=1)
    g = GraphBuilder(N).add_edges("R", src, dst).build(fmt=request.param, block=64)
    # oracle adjacency from the *deduped* edges the builder kept
    D = np.asarray(g.relations["R"].A.to_dense())
    r, c = np.nonzero(D)
    return g, adj_list(r, c, N)


def test_bfs_levels(graph_fixture):
    g, adj = graph_fixture
    seeds = [0, 5, 77, 123]
    got = np.asarray(alg.bfs_levels(g.relations["R"], seeds, max_iter=N))
    for j, s in enumerate(seeds):
        want = np.array(py_bfs(adj, s, g.n))
        np.testing.assert_array_equal(got[:, j], want, err_msg=f"seed {s}")


@pytest.mark.parametrize("k", [1, 2, 3, 6])
def test_khop_counts(graph_fixture, k):
    g, adj = graph_fixture
    seeds = [3, 50, 199]
    got = np.asarray(alg.khop_counts(g.relations["R"], seeds, k=k))
    for j, s in enumerate(seeds):
        lv = py_bfs(adj, s, g.n)
        want = sum(1 for v in range(g.n) if 1 <= lv[v] <= k)
        assert got[j] == want, f"seed {s} k {k}"


def test_sssp_vs_dijkstra():
    src, dst, w = rand_digraph(seed=2, weighted=True)
    g = GraphBuilder(N).add_edges("R", src, dst, w).build(fmt="bsr", block=64)
    D = np.asarray(g.relations["R"].A.to_dense())
    r, c = np.nonzero(D)
    adj = [[] for _ in range(N)]
    for i in range(len(r)):
        adj[r[i]].append((int(c[i]), float(D[r[i], c[i]])))
    seeds = [0, 10, 111]
    got = np.asarray(alg.sssp(g.relations["R"], seeds))
    for j, s in enumerate(seeds):
        want = np.array(py_dijkstra(adj, s, g.n))
        np.testing.assert_allclose(got[:, j], want, rtol=1e-4, atol=1e-4)


def test_pagerank_sums_to_one_and_matches_numpy():
    src, dst, _ = rand_digraph(seed=3)
    g = GraphBuilder(N).add_edges("R", src, dst).build(fmt="bsr", block=64)
    rel = g.relations["R"]
    got = np.asarray(alg.pagerank(rel, iters=60))
    assert abs(got.sum() - 1.0) < 1e-4
    # numpy power iteration oracle
    D = np.asarray(rel.A.to_dense())
    deg = D.sum(1)
    P = np.where(deg[:, None] > 0, D / np.maximum(deg[:, None], 1e-30), 0.0)
    r = np.full(N, 1.0 / N)
    for _ in range(60):
        dmass = r[deg == 0].sum() / N
        r = (1 - 0.85) / N + 0.85 * (P.T @ r + dmass)
    np.testing.assert_allclose(got, r, rtol=1e-3, atol=1e-6)


def test_pagerank_edge_list_oracle_matches_algorithm():
    """`query.reference.pagerank_ref` (the oracle the chip smoke run checks
    CALL algo.pagerank against) agrees with the algorithm, dangling
    vertices included."""
    from repro.query.reference import pagerank_ref
    src, dst, _ = rand_digraph(seed=8)
    src, dst = src[src % 7 != 0], dst[src % 7 != 0]     # dangling rows
    g = GraphBuilder(N).add_edges("R", src, dst).build(fmt="ell")
    pairs = np.unique(np.stack([src, dst], 1), axis=0)
    want = pagerank_ref(pairs[:, 0], pairs[:, 1], N, iters=45)
    got = np.asarray(alg.pagerank(g.relations["R"], iters=45))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-7)


def test_wcc_matches_union_find():
    rng = np.random.default_rng(5)
    # a few disjoint clusters with random internal edges
    sizes = [40, 80, 25, 75]
    offs = np.cumsum([0] + sizes)
    src_all, dst_all = [], []
    for i, sz in enumerate(sizes):
        # random spanning path + extra edges keeps each cluster connected
        perm = rng.permutation(sz) + offs[i]
        src_all += list(perm[:-1])
        dst_all += list(perm[1:])
        e = rng.integers(0, sz, size=(sz, 2)) + offs[i]
        src_all += list(e[:, 0])
        dst_all += list(e[:, 1])
    n = offs[-1]
    src, dst = np.array(src_all), np.array(dst_all)
    keep = src != dst
    g = GraphBuilder(n).add_edges("R", src[keep], dst[keep]).build(fmt="bsr", block=64)
    rel = g.relations["R"]
    labels = np.asarray(alg.wcc(rel))
    for i, sz in enumerate(sizes):
        comp = labels[offs[i]:offs[i + 1]]
        assert (comp == comp[0]).all(), f"cluster {i} split"
    assert len(np.unique(labels)) == len(sizes)


def test_triangle_count_vs_bruteforce():
    rng = np.random.default_rng(6)
    n = 96
    e = rng.integers(0, n, size=(600, 2))
    e = e[e[:, 0] != e[:, 1]]
    # symmetrize
    src = np.concatenate([e[:, 0], e[:, 1]])
    dst = np.concatenate([e[:, 1], e[:, 0]])
    g = GraphBuilder(n).add_edges("R", src, dst).build(fmt="bsr", block=32)
    A = g.relations["R"].A
    got = int(alg.triangle_count(A))
    D = np.asarray(A.to_dense()) != 0
    want = int(np.trace((D.astype(np.int64) @ D @ D)) // 6)
    assert got == want


# -- triangle goldens: known graphs + pure-NumPy counter ----------------------
def _tri_numpy(src, dst, n) -> int:
    """Independent counter: trace(A^3)/6 on a dense bool adjacency."""
    D = np.zeros((n, n), dtype=np.int64)
    D[src, dst] = 1
    D[dst, src] = 1
    np.fill_diagonal(D, 0)
    return int(np.trace(D @ D @ D) // 6)


def _sym_graph(src, dst, n, fmt, block=32):
    s = np.concatenate([src, dst])
    d = np.concatenate([dst, src])
    return GraphBuilder(n).add_edges("R", s, d).build(fmt=fmt, block=block)


PETERSEN_EDGES = [(0, 1), (1, 2), (2, 3), (3, 4), (4, 0),       # outer C5
                  (5, 7), (7, 9), (9, 6), (6, 8), (8, 5),       # inner star
                  (0, 5), (1, 6), (2, 7), (3, 8), (4, 9)]       # spokes

GOLDEN_GRAPHS = {
    # complete graph K4: C(4,3) = 4 triangles
    "K4": ([(i, j) for i in range(4) for j in range(i + 1, 4)], 4, 4),
    # 5-cycle: girth 5, no triangles
    "C5": ([(i, (i + 1) % 5) for i in range(5)], 5, 0),
    # Petersen graph: girth 5, no triangles
    "petersen": (PETERSEN_EDGES, 10, 0),
    # complete bipartite K33: bipartite graphs are triangle-free
    "K33": ([(i, 3 + j) for i in range(3) for j in range(3)], 6, 0),
}


@pytest.mark.parametrize("name", sorted(GOLDEN_GRAPHS))
@pytest.mark.parametrize("fmt", ["bsr", "dense"])
def test_triangle_count_golden(name, fmt):
    edges, n, want = GOLDEN_GRAPHS[name]
    src = np.array([e[0] for e in edges])
    dst = np.array([e[1] for e in edges])
    assert _tri_numpy(src, dst, n) == want          # the golden is golden
    g = _sym_graph(src, dst, n, fmt, block=8)
    assert int(alg.triangle_count(g.relations["R"].A)) == want


@pytest.mark.parametrize("fmt", ["bsr", "dense"])
def test_triangle_count_rmat_golden(fmt):
    from repro.graph.datagen import rmat_edges
    src, dst, n = rmat_edges(scale=7, edge_factor=6, seed=123)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    g = _sym_graph(src, dst, n, fmt, block=32)
    assert int(alg.triangle_count(g.relations["R"].A)) == \
        _tri_numpy(src, dst, n)
