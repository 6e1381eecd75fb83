"""The plain reference: the copied BFS against a brute-force count, the
live edge set against a rebuilt one, and the copied generator against the
program's own `rmat_graph(relabel=True)`."""
from __future__ import annotations

import json

import numpy as np
import pytest

from bench import graph500
from bench.tests.conftest import BENCH


def config(scale: int) -> dict:
    cfg = json.loads((BENCH / "configs" / "graph500-s15.json").read_text())
    cfg["scale"] = scale
    return cfg


def brute_khop(src, dst, n: int, s: int, k: int, both=False) -> int:
    """Vertices at distance 1..k from s, by boolean matrix powers, along
    the edges out, or (`both`) either way."""
    A = np.zeros((n, n), bool)
    A[src, dst] = True
    if both:
        A |= A.T
    reach = np.zeros(n, bool)
    front = np.zeros(n, bool)
    front[s] = True
    for _ in range(k):
        front = A[front].any(axis=0)
        reach |= front
    reach[s] = False
    return int(reach.sum())


@pytest.mark.parametrize("scale,k,way", [
    (6, 1, "out"), (7, 2, "out"), (7, 6, "out"), (8, 3, "out"),
    (6, 1, "both"), (7, 2, "both"), (8, 3, "both")])
def test_bfs_matches_brute_force(scale, k, way):
    src, dst, n = graph500.edge_list(config(scale), seed=2**31 + 11)
    live = graph500.LiveEdges(src, dst, n)
    for s in np.unique(np.concatenate([src, dst]))[::7]:
        assert live.khop(int(s), k, way) == \
            brute_khop(src, dst, n, int(s), k, way == "both")


def test_seed_relabels_the_same_degrees():
    a, _, n = graph500.edge_list(config(9), seed=5)
    b, _, _ = graph500.edge_list(config(9), seed=6)
    assert not np.array_equal(a, b)
    assert sorted(np.bincount(a, minlength=n)) == \
        sorted(np.bincount(b, minlength=n))


def test_live_edges_match_a_rebuilt_edge_set():
    src, dst, n = graph500.edge_list(config(7), seed=5)
    live = graph500.LiveEdges(src, dst, n)
    edges = set(zip(src.tolist(), dst.tolist()))
    rng = np.random.default_rng(0)
    for i in range(300):
        s = int(rng.choice(src))
        if i % 2:
            t = int(live.out(s)[0]) if len(live.out(s)) else None
            if t is None:
                continue
            live.apply("delete", s, t)
            edges.discard((s, t))
        else:
            t = int(rng.integers(0, n))
            if live.has(s, t):
                continue
            live.apply("create", s, t)
            edges.add((s, t))
    es, ed = (np.array(x) for x in zip(*sorted(edges)))
    for s in np.unique(es)[::5]:
        assert live.khop(int(s), 2, "out") == brute_khop(es, ed, n, int(s), 2)
        assert live.khop(int(s), 2, "both") == \
            brute_khop(es, ed, n, int(s), 2, both=True)


def test_edge_list_is_what_the_program_generates():
    from repro.graph.datagen import rmat_graph
    seed = 2**31 + 3
    cfg = dict(config(9), rmat_seed=seed)
    src, dst, n = graph500.edge_list(cfg, seed)
    g = rmat_graph(scale=9, edge_factor=16, seed=seed, relabel=True,
                   fmt="ell")
    r, c, _ = g.relations["KNOWS"].A.to_coo()
    order = np.argsort(np.asarray(r) * n + np.asarray(c))
    np.testing.assert_array_equal(np.asarray(r)[order], src)
    np.testing.assert_array_equal(np.asarray(c)[order], dst)
