#!/usr/bin/env python3
"""Chip smoke run: the served Graph500 k-hop path end to end on a TPU.

  python chip_smoke.py [--seed 0]          # one chip, every phase
  python chip_smoke.py --chips 4           # the sharded serving path only

One chip: generate Graph500 R-MAT at scale 15, edge factor 16 (the largest
Graph500 graph the ELL store holds on one 16 GB chip), bulk-load it with
`Database.load_graph`, and serve the paper's k-hop protocol through
`Database.server` (submit / pump): k = 1 and 2 with 48 seeds each, k = 3
and 6 with 10 seeds each. Every count must equal a host NumPy BFS over the
same edge list. Then one `Database.query` MATCH with a WHERE predicate
(against `query.reference.execute_ref`), `CALL algo.pagerank` (against
`query.reference.pagerank_ref`), a CREATE and a DELETE read back on the
delta path, and a check that the served sweep's compiled HLO holds the
packed Pallas kernel (`tpu_custom_call`), not the interpreter or the XLA
reference.

`--chips 4`: serve the same k-hop queries on the same graph sharded over a
4-device ("pod", "data", "model") mesh (`QueryServer(mesh=)`, ShardedELL
rows over "data"), check the arrays sit on 4 devices, and check every
answer against the one-device answers computed in the same process.

Any failed check raises. Wall and compile times are set-up figures, not a
benchmark. The last line of stdout is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402

SCALE, EDGE_FACTOR = 15, 16
# (k, seeds): the TigerGraph k-hop protocol, seed counts cut for a smoke run
KHOP = ((1, 48), (2, 48), (3, 10), (6, 10))
KHOP_TEXT = "MATCH (a)-[:KNOWS*1..{k}]->(b) RETURN count(DISTINCT b)"


class Clock:
    """Phase wall time and the backend compile seconds inside it."""

    def __init__(self, jax):
        self.compile_s = 0.0
        self.compiles = 0

        def on_event(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_s += duration
                self.compiles += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)

    @contextlib.contextmanager
    def phase(self, name: str):
        c0, n0, t0 = self.compile_s, self.compiles, time.perf_counter()
        yield
        print(f"setup phase={name} wall_s={time.perf_counter() - t0:.3f} "
              f"compile_s={self.compile_s - c0:.3f} "
              f"compiles={self.compiles - n0}", flush=True)


def edge_list(scale: int, seed: int):
    """The deduplicated (src, dst) pairs `rmat_graph` stores."""
    from repro.graph.datagen import rmat_edges
    src, dst, n = rmat_edges(scale, EDGE_FACTOR, seed)
    key = np.unique(src * n + dst)
    return key // n, key % n, n


def csr(src, dst, n):
    order = np.argsort(src, kind="stable")
    indptr = np.zeros(n + 1, np.int64)
    indptr[1:] = np.cumsum(np.bincount(src, minlength=n))
    return indptr, dst[order]


def bfs_count(indptr, indices, s: int, k: int) -> int:
    """Vertices first reached at hop 1..k from s (s itself never counts):
    the engine's count(DISTINCT b) for (a)-[*1..k]->(b)."""
    seen = np.zeros(len(indptr) - 1, bool)
    seen[s] = True
    front, total = np.array([s]), 0
    for _ in range(k):
        lens = indptr[front + 1] - indptr[front]
        if not lens.sum():
            break
        pos = (np.repeat(indptr[front] - np.cumsum(lens) + lens, lens)
               + np.arange(lens.sum()))
        nb = np.unique(indices[pos])
        front = nb[~seen[nb]]
        seen[front] = True
        total += front.size
    return total


def pick_seeds(src, n: int, count: int, rng) -> np.ndarray:
    """Seeds among vertices with out-edges, so answers are not all zero."""
    live = np.unique(src)
    return rng.choice(live, size=count, replace=False)


def serve(srv, k: int, seeds) -> list:
    """Submit one single-seed query per seed, pump until all are back."""
    qids = [srv.submit(KHOP_TEXT.format(k=k), seeds=[int(s)]) for s in seeds]
    out = {}
    while srv.pending:
        out.update(srv.pump())
    counts = []
    for q, s in zip(qids, seeds):
        r = out[q]
        if r.error is not None:
            raise RuntimeError(f"k={k} seed {s}: served error {r.error}")
        if len(r.rows) != 1:
            raise AssertionError(f"k={k} seed {s}: rows {r.rows}")
        counts.append(int(r.rows[0][0]))
    return counts


def check_equal(what: str, got, want, seeds) -> None:
    bad = [(int(s), g, w) for s, g, w in zip(seeds, got, want) if g != w]
    if bad:
        raise AssertionError(f"{what}: {len(bad)} of {len(got)} differ, "
                             f"first (seed, got, want) = {bad[0]}")


def device_bytes(g) -> int:
    """Bytes of the graph's adjacency storage on the device (each relation
    and the untyped union, forward and stored transpose)."""
    import jax
    seen, total = set(), 0
    for r in [*g.relations.values(), g.adj]:
        for h in (r.A, r.A.T):
            for x in jax.tree_util.tree_leaves(h.store):
                if id(x) not in seen:
                    seen.add(id(x))
                    total += x.nbytes
    return total


def load(clock, seed: int):
    from repro.engine import Database
    from repro.graph.datagen import rmat_graph
    with clock.phase("generate_and_build"):
        g = rmat_graph(scale=SCALE, edge_factor=EDGE_FACTOR, seed=seed,
                       fmt="auto")
        src, dst, n = edge_list(SCALE, seed)
    kinds = {r.name or "(union)": (r.A.fmt, r.A.T.fmt)
             for r in [*g.relations.values(), g.adj]}
    print(f"setup graph n={g.n} edges={g.nnz} storage={kinds}", flush=True)
    if len(src) != g.nnz:
        raise AssertionError(f"oracle edge list {len(src)} != stored {g.nnz}")
    db = Database()
    with clock.phase("load_graph"):
        db.load_graph("g500", g)
    return db, g, src, dst, n


def run_one_chip(args, clock, jax) -> None:
    from repro.query.reference import execute_ref, pagerank_ref

    db, g, src, dst, n = load(clock, args.seed)
    dev = jax.devices()[0]
    print(f"setup device_bytes_storage={device_bytes(g)} "
          f"bytes_in_use={(dev.memory_stats() or {}).get('bytes_in_use')}",
          flush=True)
    indptr, indices = csr(src, dst, n)
    rng = np.random.default_rng(args.seed)

    srv = db.server("g500")
    for k, count in KHOP:
        seeds = pick_seeds(src, n, count, rng)
        with clock.phase(f"serve_k{k}_x{count}"):
            got = serve(srv, k, seeds)
        check_equal(f"served k={k}", got,
                    [bfs_count(indptr, indices, int(s), k) for s in seeds],
                    seeds)
        print(f"setup k={k} seeds={count} counts_min={min(got)} "
              f"counts_max={max(got)} equal_to_numpy_bfs=True", flush=True)
    if srv.stats["errors"]:
        raise AssertionError(f"server counted {srv.stats['errors']} errors")

    with clock.phase("compile_packed_sweep"):
        check_packed_sweep(db, n, jax)

    s = int(pick_seeds(src, n, 1, rng)[0])
    text = (f"MATCH (a)-[:KNOWS*1..2]->(b) WHERE id(a) = {s} AND "
            f"id(b) < {n // 2} RETURN count(DISTINCT b)")
    with clock.phase("query_where"):
        got = db.query("g500", text)
    want = execute_ref(g, text)
    if got.error is not None or got.rows != want.rows:
        raise AssertionError(f"WHERE query: {got} != reference {want.rows}")
    print(f"setup where_query rows={got.rows} equal_to_reference=True",
          flush=True)

    with clock.phase("call_pagerank"):
        res = db.query("g500", "CALL algo.pagerank(rel: KNOWS) "
                               "YIELD node, score")
    got = np.array([sc for _, sc in sorted(res.rows)])
    np.testing.assert_allclose(got, pagerank_ref(src, dst, n),
                               rtol=1e-3, atol=1e-7)
    print(f"setup pagerank n={len(got)} sum={got.sum():.6f} "
          f"equal_to_reference=True", flush=True)

    with clock.phase("delta_writes"):
        delta_phase(db, args.seed)


def check_packed_sweep(db, n: int, jax) -> None:
    """The served sweep — grb's packed hop on the stored transpose — must
    compile to the Pallas kernel."""
    from repro.core import grb
    M = db.context("g500").matrix("KNOWS")
    words = jax.numpy.zeros((n, 1), jax.numpy.uint32)
    # the stored transpose goes in as an argument: closed over, its arrays
    # would be baked into the executable as constants
    hlo = jax.jit(grb.mxm_words).lower(M.T.store, words).compile().as_text()
    if "tpu_custom_call" not in hlo:
        raise AssertionError("served sweep compiled without the Pallas "
                             "kernel (no tpu_custom_call in its HLO)")
    print(f"setup packed_sweep storage={M.T.fmt} tpu_custom_call=True",
          flush=True)


def delta_phase(db, seed: int) -> None:
    """Build a graph through CREATE, then CREATE and DELETE one edge each
    and read both back: served from a DeltaMatrix over the frozen base,
    with no rebuild."""
    src, dst, n = edge_list(10, seed + 1)
    src, dst = src[src != dst], dst[src != dst]   # a seed never reaches itself
    for i in range(0, len(src), 1024):
        db.query("live", "CREATE " + ", ".join(
            f"({a})-[:KNOWS]->({b})"
            for a, b in zip(src[i:i + 1024], dst[i:i + 1024])))
    deg = np.bincount(src, minlength=n)
    s = int(np.argmax(deg))
    nbrs = set(dst[src == s].tolist())
    u = min(nbrs)
    t = min(set(range(n)) - nbrs - {s})
    one_hop = f"MATCH (a)-[:KNOWS]->(b) WHERE id(a) = {s} RETURN b"
    two_hop = KHOP_TEXT.format(k=2)
    before = {r[0] for r in db.query("live", one_hop).rows}
    if before != nbrs:
        raise AssertionError("live graph 1-hop read differs from its edges")
    db.query("live", f"CREATE ({s})-[:KNOWS]->({t})")
    db.query("live", f"DELETE ({s})-[:KNOWS]->({u})")
    after = {r[0] for r in db.query("live", one_hop).rows}
    if after != (nbrs - {u}) | {t}:
        raise AssertionError(f"writes not read back: {sorted(after ^ nbrs)}")
    keep = ~((src == s) & (dst == u))
    indptr, indices = csr(np.append(src[keep], s), np.append(dst[keep], t), n)
    want = bfs_count(indptr, indices, s, 2)
    got = serve(db.server("live"), 2, [s])[0]
    if got != want:
        raise AssertionError(f"served 2-hop after writes {got} != {want}")
    mg = db._graph("live")
    store = db.context("live").matrix("KNOWS")
    if mg.rebuilds != 1 or store.fmt != "delta":
        raise AssertionError(f"writes not on the delta path: rebuilds="
                             f"{mg.rebuilds} storage={store.fmt}")
    print(f"setup delta writes_read_back=True served_2hop={got} "
          f"rebuilds={mg.rebuilds} storage={store.fmt}/"
          f"{type(store.store.base).__name__}", flush=True)


def run_mesh(args, clock, jax, chips: int) -> None:
    from jax.sharding import Mesh
    devices = jax.devices()
    if len(devices) < chips:
        raise SystemExit(f"--chips {chips}: only {len(devices)} devices")
    mesh = Mesh(np.array(devices[:chips]).reshape(1, chips, 1),
                ("pod", "data", "model"))
    db, g, src, dst, n = load(clock, args.seed)
    one, sharded = db.server("g500"), db.server("g500", mesh=mesh)
    rng = np.random.default_rng(args.seed)
    for k, count in KHOP:
        seeds = pick_seeds(src, n, count, rng)
        with clock.phase(f"serve_k{k}_x{count}_one_device"):
            want = serve(one, k, seeds)
        with clock.phase(f"serve_k{k}_x{count}_mesh{chips}"):
            got = serve(sharded, k, seeds)
        check_equal(f"sharded k={k}", got, want, seeds)
        print(f"setup k={k} seeds={count} sharded_equal_to_one_device=True",
              flush=True)
    M = sharded._refresh().matrix("KNOWS")
    for h in (M, M.T):
        # ShardedELL (indices/mask/values) or ShardedBitELL (tiles/cols)
        arrays = [getattr(h.store, a, None) for a in
                  ("indices", "mask", "values", "tiles", "cols")]
        for x in filter(lambda a: a is not None, arrays):
            on = {sh.device for sh in x.addressable_shards}
            rows = x.addressable_shards[0].data.shape[0]
            if len(on) != chips or rows * chips != x.shape[0]:
                raise AssertionError(f"{h.fmt} array {x.shape}: shards of "
                                     f"{rows} rows on {len(on)} devices")
    print(f"setup sharded storage={M.fmt}/{M.T.fmt} rows_over_devices={chips}",
          flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: serve sharded over a 4-chip mesh, compare "
                         "with one device, and run no other phase")
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{dev.platform} ({dev.device_kind})")
    from repro import compile_cache
    print(f"setup compile_cache={compile_cache.enable()}", flush=True)
    print("setup note: times below are set-up (compile included), not a "
          "benchmark", flush=True)
    clock = Clock(jax)
    t0 = time.perf_counter()
    if args.chips == 1:
        run_one_chip(args, clock, jax)
    else:
        run_mesh(args, clock, jax, args.chips)
    print(f"setup total_wall_s={time.perf_counter() - t0:.3f} "
          f"compile_s={clock.compile_s:.3f}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
