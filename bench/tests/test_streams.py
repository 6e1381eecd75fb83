"""The traffic generator: the same seed gives the same streams, phases
and seeds give different ones, and writes keep the edge set valid."""
from __future__ import annotations

import json

import numpy as np

from bench import graph500, streams
from bench.tests.conftest import BENCH


def mix(name: str) -> dict:
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def graph(seed: int = 3):
    cfg = json.loads((BENCH / "configs" / "graph500-s15.json").read_text())
    cfg["scale"] = 9
    src, dst, n = graph500.edge_list(cfg, seed)
    return src, dst, n


def draw(m: dict, seed: int, phase: str = "window", reads: int = 500,
         writes: int = 200):
    src, dst, n = graph()
    w = None
    if "writes" in m:
        w = streams.Writes(m["writes"], seed, graph500.LiveEdges(src, dst, n),
                           np.unique(src))
    t = streams.Traffic(m, seed, np.unique(src), phase, w)
    out = ([t.seeds.next() for _ in range(reads)],
           [t.kind.next() for _ in range(reads)],
           [t.gap.next() for _ in range(reads)])
    if t.writes is not None:
        out += ([t.writes.next() for _ in range(writes)],)
    return out


def test_same_seed_same_streams():
    for name in ("khop6-uniform", "ycsbB-khop2-zipf"):
        a, b = draw(mix(name), 2**31 + 5), draw(mix(name), 2**31 + 5)
        assert [np.asarray(x).tolist() for x in a] == \
            [np.asarray(x).tolist() for x in b]


def test_other_seed_or_phase_differs():
    m = mix("ycsbB-khop2-zipf")
    a = draw(m, 7)
    assert draw(m, 8)[0] != a[0]
    assert draw(m, 7, phase="warm")[0] != a[0]


def test_zipf_is_skewed_and_scrambled():
    src, _, _ = graph()
    items = np.unique(src)
    c = streams.Chooser(items, {"dist": "zipf", "theta": 0.99},
                        streams.rng_for(1, "t"))
    x = c.draw(20000)
    counts = np.sort(np.bincount(x))[::-1]
    assert counts[0] > 20 * np.median(counts[counts > 0])
    top = np.argmax(np.bincount(x))
    assert top != items[0]          # rank 0 is not the smallest id


def test_writes_alternate_and_hold_the_edge_count():
    src, dst, n = graph()
    live = graph500.LiveEdges(src, dst, n)
    ref = set(zip(src.tolist(), dst.tolist()))
    w = streams.Writes(mix("ycsbB-khop2-zipf")["writes"], 9, live,
                       np.unique(src))
    for i in range(400):
        kind, s, t = w.next()
        assert kind == ("create" if i % 2 == 0 else "delete")
        if kind == "create":
            assert (s, t) not in ref and s != t
            ref.add((s, t))
        else:
            assert (s, t) in ref
            ref.discard((s, t))
    assert len(ref) == len(src)


def test_seed_changes_the_order_of_the_work_not_its_amount():
    m = mix("ycsbB-khop2-zipf")
    n = streams.CHUNK
    a = draw(m, 11, reads=n, writes=0)
    b = draw(m, 12, reads=n, writes=0)
    assert sum(np.asarray(a[1]) >= 0) == sum(np.asarray(b[1]) >= 0) == \
        round(m["read_share"] * n)
    assert sorted(a[2]) == sorted(b[2]) and a[2] != b[2]
    assert abs(sum(a[2]) / n - 1.0) < 1e-3


def test_reads_of_several_kinds_keep_their_shares_and_k():
    m = dict(mix("ycsbB-khop2-zipf"), reads=[
        dict(query="MATCH (a)-[:KNOWS*1..{k}]-(b) RETURN count(DISTINCT b)",
             k=k, direction="both", share=share)
        for k, share in ((1, 300), (2, 300), (3, 10), (6, 10))])
    src, dst, n = graph()
    t = streams.Traffic(m, 4, np.unique(src), "window", streams.Writes(
        m["writes"], 4, graph500.LiveEdges(src, dst, n), np.unique(src)))
    sent = []
    got = [t.next_read(lambda *w: sent.append(w))
           for _ in range(streams.CHUNK // 2)]
    ks = np.bincount([r.k for r, _ in got], minlength=7)[[1, 2, 3, 6]]
    assert all(f"1..{r.k}]" in r.text for r, _ in got)
    assert abs(ks[0] / ks[1] - 1) < 0.15 and 0 < ks[2] < ks[0] / 10
    assert abs(len(sent) / len(got) - 0.05 / 0.95) < 0.01
