"""Graph500 kernel-0 data and the plain k-hop reference.

Everything here is a copy, not an import, so that no change to the program
can move the yardstick: the R-MAT generator of
`repro.graph.datagen.rmat_edges` with the Graph500 vertex relabelling of
`rmat_graph(relabel=True)`, and the edge list, CSR and NumPy BFS of
`chip_smoke.py`. The BFS follows edges out, in or both ways, and also
answers over a live edge set (`LiveEdges`): CSR bases with whole rows
replaced where writes touched them.
"""
from __future__ import annotations

import numpy as np


def rmat_edges(scale: int, edge_factor: int, seed: int, abc):
    """Vectorized R-MAT: the Graph500 kernel-0 generator (raw pairs)."""
    a, b, c = abc
    rng = np.random.default_rng(seed)
    n = 1 << scale
    m = n * edge_factor
    src = np.zeros(m, dtype=np.int64)
    dst = np.zeros(m, dtype=np.int64)
    ab, abc_ = a + b, a + b + c
    for _ in range(scale):
        u = rng.uniform(size=m)
        src_bit = (u >= ab).astype(np.int64)
        dst_bit = (((u >= a) & (u < ab)) | (u >= abc_)).astype(np.int64)
        src = (src << 1) | src_bit
        dst = (dst << 1) | dst_bit
    return src, dst, n


def edge_list(cfg: dict, seed: int):
    """The deduplicated (src, dst) pairs of a configuration, sorted
    by src * n + dst. The R-MAT draw takes the configuration's fixed
    `rmat_seed`; `seed` draws the relabelling, so every seed gives the same
    degrees under other vertex ids (with rmat_seed == seed, the pairs
    `rmat_graph(seed=seed, relabel=True)` stores)."""
    src, dst, n = rmat_edges(cfg["scale"], cfg["edge_factor"],
                             cfg["rmat_seed"], cfg["rmat_abc"])
    if cfg["relabel"]:
        perm = np.random.default_rng(seed + 1).permutation(n)
        src, dst = perm[src], perm[dst]
    key = np.unique(src * n + dst)
    return key // n, key % n, n


def csr(src, dst, n):
    order = np.argsort(src, kind="stable")
    indptr = np.zeros(n + 1, np.int64)
    indptr[1:] = np.cumsum(np.bincount(src, minlength=n))
    return indptr, dst[order]


class Rows:
    """The rows of a directed edge set: a CSR of the initial edges plus
    whole replacement rows for every vertex a write has touched. Rows are
    kept sorted, so draws from them are deterministic."""

    def __init__(self, src, dst, n: int):
        self.indptr, self.indices = csr(src, dst, n)
        self.rows: dict = {}

    def row(self, v: int) -> np.ndarray:
        r = self.rows.get(v)
        if r is not None:
            return r
        return self.indices[self.indptr[v]:self.indptr[v + 1]]

    def insert(self, v: int, t: int) -> None:
        o = self.row(v)
        self.rows[v] = np.insert(o, np.searchsorted(o, t), t)

    def remove(self, v: int, t: int) -> None:
        o = self.row(v)
        self.rows[v] = o[o != t]

    def gather(self, front: np.ndarray) -> list:
        """The rows of the vertices in `front`, as a list of arrays."""
        hit = np.array([int(v) in self.rows for v in front], bool)
        parts = [self.rows[int(v)] for v in front[hit]]
        front = front[~hit]
        lens = self.indptr[front + 1] - self.indptr[front]
        if lens.sum():
            pos = (np.repeat(self.indptr[front] - np.cumsum(lens) + lens,
                             lens) + np.arange(lens.sum()))
            parts.append(self.indices[pos])
        return parts


def bfs_count(views, n: int, s: int, k: int) -> int:
    """Vertices first reached at hop 1..k from s (s itself never counts),
    stepping along every `Rows` in `views` at each hop: the engine's
    count(DISTINCT b) for (a)-[*1..k]->(b) over the out-rows, and for
    (a)-[*1..k]-(b) over the out- and in-rows."""
    seen = np.zeros(n, bool)
    seen[s] = True
    front, total = np.array([s]), 0
    for _ in range(k):
        parts = [p for v in views for p in v.gather(front)]
        if not parts:
            break
        nb = np.unique(np.concatenate(parts))
        front = nb[~seen[nb]]
        seen[front] = True
        total += front.size
    return total


class LiveEdges:
    """A directed edge set under single-edge CREATE / DELETE, kept as its
    out-rows and its in-rows, so a k-hop count can follow edges out, in
    or both ways."""

    def __init__(self, src, dst, n: int):
        self.n = n
        self.fwd = Rows(src, dst, n)
        self.rev = Rows(dst, src, n)
        self.views = {"out": (self.fwd,), "in": (self.rev,),
                      "both": (self.fwd, self.rev)}

    def out(self, v: int) -> np.ndarray:
        return self.fwd.row(v)

    def has(self, s: int, t: int) -> bool:
        o = self.out(s)
        j = np.searchsorted(o, t)
        return bool(j < len(o) and o[j] == t)

    def apply(self, kind: str, s: int, t: int) -> None:
        if kind == "create":
            self.fwd.insert(s, t)
            self.rev.insert(t, s)
        else:
            self.fwd.remove(s, t)
            self.rev.remove(t, s)

    def khop(self, s: int, k: int, direction: str) -> int:
        return bfs_count(self.views[direction], self.n, s, k)


def storage_bytes(g) -> int:
    """Bytes of the graph's adjacency storage on the device (each relation
    and the untyped union, forward and stored transpose). Leaves shared
    between handles count once. A copy of `chip_smoke.device_bytes`,
    extended to delta-served handles."""
    import jax
    seen, total = set(), 0
    for r in [*g.relations.values(), g.adj]:
        for h in (r.A, r.A.T):
            # a delta-served handle: its frozen base is what the device
            # holds; pending deltas live on the host
            store = getattr(h.store, "base", h.store)
            for x in jax.tree_util.tree_leaves(store):
                if id(x) not in seen and hasattr(x, "nbytes"):
                    seen.add(id(x))
                    total += x.nbytes
    return total
