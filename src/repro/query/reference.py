"""Pure-python reference interpreter for the Cypher subset — the differential
oracle for the algebraic executor (same BFS distinct-vertex semantics)."""
from __future__ import annotations

from collections import deque
from typing import List

import numpy as np

from repro.graph.graph import Graph
from repro.query import qast as A
from repro.query.executor import ExecutionContext, Result, _colname, _prop
from repro.query.parser import parse
from repro.query.planner import plan


def _edges(store):
    """(rows, cols) of the nonzero entries, read from sparse storage as
    COO so the reference never densifies an (n, n) matrix."""
    if hasattr(store, "to_coo"):
        rr, cc, vv = store.to_coo()
        keep = np.asarray(vv) != 0
        return np.asarray(rr)[keep], np.asarray(cc)[keep]
    return np.nonzero(np.asarray(store))


def _adj(graph: Graph, rel, direction) -> list:
    r = graph.relation(rel)
    pairs = []
    if direction in (A.OUT, A.BOTH):
        pairs.append(_edges(r.A.store))
    if direction in (A.IN, A.BOTH):
        pairs.append(_edges(r.A_T.store))
    n = graph.n
    out = [set() for _ in range(n)]
    for rr, cc in pairs:
        for i, j in zip(rr.tolist(), cc.tolist()):
            out[i].add(j)
    return out


def _bfs_range(adj, seeds: set, minh: int, maxh: int, allowed_dst) -> set:
    lvl = {s: 0 for s in seeds}
    q = deque(seeds)
    reach = set()
    while q:
        u = q.popleft()
        if lvl[u] >= maxh:
            continue
        for v in adj[u]:
            if v not in lvl:
                lvl[v] = lvl[u] + 1
                q.append(v)
                if minh <= lvl[v] <= maxh and allowed_dst[v]:
                    reach.add(v)
    return reach


def pagerank_ref(src, dst, n: int, alpha: float = 0.85,
                 iters: int = 50) -> np.ndarray:
    """PageRank by float64 power iteration over a unit-weight edge list —
    the oracle for `algorithms.pagerank` / ``CALL algo.pagerank``, with the
    same rule for dangling vertices (their mass spreads evenly)."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    deg = np.bincount(src, minlength=n).astype(np.float64)
    r = np.full(n, 1.0 / n)
    for _ in range(iters):
        push = np.where(deg > 0, r / np.maximum(deg, 1.0), 0.0)
        pulled = np.bincount(dst, weights=push[src], minlength=n)
        dmass = r[deg == 0].sum() / n
        r = (1.0 - alpha) / n + alpha * (pulled + dmass)
    return r


def execute_ref(graph: Graph, query) -> Result:
    q = parse(query) if isinstance(query, str) else query
    p = plan(q)
    if p.semiring != "or_and":
        raise NotImplementedError("reference covers distinct semantics only")

    ctx = ExecutionContext(graph)
    src_mask = ctx.node_mask(p.src_label, p.var_preds.get(p.src_var))
    if p.seeds is not None:
        seeds = [s for s in sorted(set(p.seeds)) if src_mask[s]]
    else:
        seeds = list(np.nonzero(src_mask)[0])

    per_seed: List[set] = []
    for s in seeds:
        cur = {int(s)}
        for e in p.expands:
            adj = _adj(graph, e.rel, e.direction)
            dst_mask = ctx.node_mask(e.dst_label,
                                     p.var_preds.get(e.dst_var))
            cur = _bfs_range(adj, cur, e.min_hops, e.max_hops, dst_mask)
        per_seed.append(cur)

    cols = [_colname(r) for r in p.returns]
    src_var = p.src_var
    returns_src = any(r.var == src_var and r.kind != "count" for r in p.returns)
    only_counts = all(r.kind == "count" for r in p.returns)

    rows = []
    if only_counts and not returns_src:
        total = sum(len(c) for c in per_seed)
        rows = [tuple(total for _ in p.returns)]
    elif only_counts or (returns_src and all(r.kind == "count" or r.var == src_var
                                             for r in p.returns)):
        for j, s in enumerate(seeds):
            vals = []
            for r in p.returns:
                if r.kind == "count":
                    vals.append(len(per_seed[j]))
                elif r.kind == "prop":
                    vals.append(_prop(graph, r.prop, int(s)))
                else:
                    vals.append(int(s))
            rows.append(tuple(vals))
    else:
        for j, s in enumerate(seeds):
            for d in sorted(per_seed[j]):
                vals = []
                for r in p.returns:
                    node = int(s) if r.var == src_var else int(d)
                    if r.kind == "prop":
                        vals.append(_prop(graph, r.prop, node))
                    else:
                        vals.append(node)
                rows.append(tuple(vals))
        rows.sort()
    if p.limit is not None:
        rows = rows[: p.limit]
    return Result(cols, rows)
