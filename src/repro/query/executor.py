"""Physical execution: plans -> GraphBLAS ops on the graph's matrices.

The binding state is a frontier matrix B (n, F): column j is the reachable
set (or walk counts) of source binding j. Each Expand is min..max masked
semiring hops through the `repro.core.grb` surface (mask/complement/transpose
ride in a Descriptor); node predicates become diagonal masks applied between
hops. This is the paper's Cypher->linear-algebra translation. Structural
(or_and) expands over a wide seed batch ride grb's bitmap-packed frontier
route automatically (docs/API.md §Bitmap) — nothing here opts in.

`ExecutionContext` is the public execution surface: `node_mask`,
`seed_frontier`, `expand`, `traverse`, and `project` are the primitives a
scheduler composes — the continuous-batching server (`repro.engine.server`)
drives them directly to answer many pattern-compatible queries with one
frontier traversal (`traverse` returns the frontier unmaterialized, so the
server overlaps host-side scheduling with device execution). `execute()` is
the solo driver over the same context; `resolve_seeds` is the ONE seed
semantics both paths share (or_and dedupes bindings, plus_times keeps the
seed multiset), so batched and solo answers are definitionally equal.

Public contract: a context reads one *frozen* Graph (CREATE / DELETE raise
TypeError — writes go through `engine.Database`); unknown relations raise
ValueError naming the ones that exist. Frozen means snapshot-consistent,
not necessarily rebuilt: `engine.Database` serves views whose relation
handles may be delta-backed (`core.delta.DeltaMatrix` — a frozen base plus
pending writes), and every grb call here composes those deltas exactly, so
a context opened before a writer batch never sees its edits and a context
opened after sees all of them with zero rebuild. `impl` and `mesh` are
resolved once per context, never per call; with `mesh` set every relation
handle is distributed on first use (`grb.distribute` — which raises
TypeError unless the graph was frozen as ELL; `engine.Database` freezes
sharded-mode graphs as ELL *with deltas compacted* for exactly this
reason) and traversal hops run as mesh collectives. `project` materializes rows host-side by design (results are
Python values); `node_mask` evaluates predicates host-side on node
property columns.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core import bitmap as _bitmap, grb, semiring as S
from repro.core.grb import Descriptor
from repro.graph.graph import Graph
from repro.query import qast as A
from repro.query.parser import parse
from repro.query.planner import PROC_COLUMNS, CallPlan, Plan, plan


@dataclasses.dataclass
class Result:
    columns: List[str]
    rows: List[tuple]
    # serving error isolation: a query that failed inside a batch reports
    # here ("ValueError: no relation ...") instead of poisoning its batch
    error: Optional[str] = None

    def scalar(self):
        assert len(self.rows) == 1 and len(self.rows[0]) == 1
        return self.rows[0][0]


def empty_result(p: Plan) -> Result:
    """The no-seeds-survived answer — shared by the solo driver and the
    batched server so an all-masked-out seed list means the same thing
    (zero rows, NOT a zero-count row) on both paths."""
    return Result([_colname(r) for r in p.returns], [])


def resolve_seeds(p: Plan, src_mask: np.ndarray) -> np.ndarray:
    """Seed ids a seeded plan actually starts from — the ONE definition the
    solo driver and the batched server share. or_and (distinct
    reachability) binds each seed vertex once: sorted, deduped.
    plus_times counts walks from the seed *multiset*: duplicates are
    distinct walk sources and written order is kept, so
    `id(a) IN [3, 3, 5]` contributes vertex 3's walks twice. Seeds failing
    the source label/predicate mask drop their column entirely."""
    if p.semiring == "or_and":
        seeds = np.asarray(sorted(set(p.seeds)), dtype=np.int64)
    else:
        seeds = np.asarray(list(p.seeds), dtype=np.int64)
    n = len(src_mask)
    if seeds.size and (seeds.min() < 0 or seeds.max() >= n):
        raise ValueError(f"seed id out of range 0..{n - 1}: "
                         f"{[int(s) for s in seeds if s < 0 or s >= n]}")
    return seeds[src_mask[seeds]]


# -- predicate evaluation -----------------------------------------------------
def _operand_vec(graph: Graph, side, n: int):
    if side[0] == "lit":
        return np.full(n, side[1], dtype=np.float64), None
    if side[0] == "id":
        return np.arange(n, dtype=np.float64), None
    if side[0] == "prop":
        col = graph.node_props.get(side[2])
        if col is None:
            return np.full(n, np.nan), np.zeros(n, dtype=bool)
        col = np.asarray(col, dtype=np.float64)
        return col, ~np.isnan(col)
    raise TypeError(side)


_CMP = {"<": np.less, "<=": np.less_equal, ">": np.greater,
        ">=": np.greater_equal, "=": np.equal, "<>": np.not_equal}


def eval_pred(graph: Graph, node, n: int) -> np.ndarray:
    if isinstance(node, A.Comparison):
        lv, lp = _operand_vec(graph, node.lhs, n)
        rv, rp = _operand_vec(graph, node.rhs, n)
        with np.errstate(invalid="ignore"):
            out = _CMP[node.op](lv, rv)
        for present in (lp, rp):
            if present is not None:
                out &= present
        return out
    if isinstance(node, A.BoolExpr):
        parts = [eval_pred(graph, a, n) for a in node.args]
        if node.op == "AND":
            return np.logical_and.reduce(parts)
        if node.op == "OR":
            return np.logical_or.reduce(parts)
        if node.op == "NOT":
            return ~parts[0]
    if isinstance(node, A.InSeeds):
        m = np.zeros(n, dtype=bool)
        m[node.seeds] = True
        return m
    raise TypeError(node)


# -- CALL procedures ----------------------------------------------------------
# The `CALL algo.*` surface: each procedure is a (device, rows) pair that
# plugs into the SAME scheduler hooks MATCH plans use — `device` is the
# traverse analog (seeds -> an unmaterialized (n, F) device array whose
# columns belong to seed columns), `rows` is the project analog (the
# member's column slice -> row tuples in canonical column order). Seeded
# procedures batch: the server concatenates signature-equal members'
# source lists into ONE device call and slices each member's columns back
# out in finish — so `CALL algo.closeness(sources: [3])` and
# `(sources: [5])` cost one BFS sweep, exactly like two seeded MATCHes.
# Source-less calls are global (label-scan analog: every vertex) and ride
# alone. Unseeded procedures (pagerank, wcc, ...) return one shared
# column; numpy slice-clamping makes the server's per-member column
# slicing a no-op on them.

@dataclasses.dataclass(frozen=True)
class Procedure:
    columns: tuple                      # canonical yield columns, in order
    seeded: bool                        # accepts a `sources:` list
    defaults: dict                      # allowed args + default values
    device: object                      # (ctx, args, seeds) -> jnp (n, F)
    rows: object                        # (ctx, args, seeds, Bn) -> [tuple]


def _proc_M(ctx: "ExecutionContext", args: dict) -> grb.GBMatrix:
    return ctx.matrix(args["rel"])


def _pagerank_device(ctx, a, seeds):
    from repro.algorithms import pagerank
    return pagerank(_proc_M(ctx, a), alpha=float(a["alpha"]),
                    iters=int(a["iters"]))[:, None]


def _betweenness_device(ctx, a, seeds):
    from repro.algorithms import brandes_parts
    return brandes_parts(_proc_M(ctx, a), seeds)


def _levels_device(ctx, a, seeds):
    from repro.algorithms import bfs_levels
    return bfs_levels(_proc_M(ctx, a), seeds,
                      max_iter=int(a.get("max_hops", 0)))


def _similarity_device(ctx, a, seeds):
    from repro.algorithms import similarity
    return similarity(_proc_M(ctx, a), seeds, kind=a["kind"])


def _wcc_device(ctx, a, seeds):
    from repro.algorithms import wcc
    return wcc(_proc_M(ctx, a))[:, None]


def _labelprop_device(ctx, a, seeds):
    from repro.algorithms import label_propagation
    return label_propagation(_proc_M(ctx, a),
                             max_iter=int(a["max_iter"]))[:, None]


def _triangles_device(ctx, a, seeds):
    from repro.algorithms import triangle_count
    return triangle_count(_proc_M(ctx, a)).reshape(1, 1)


def _node_float_rows(ctx, a, seeds, Bn):
    col = Bn[:, 0]
    return [(i, float(col[i])) for i in range(Bn.shape[0])]


def _node_int_rows(ctx, a, seeds, Bn):
    col = Bn[:, 0]
    return [(i, int(col[i])) for i in range(Bn.shape[0])]


def _betweenness_rows(ctx, a, seeds, Bn):
    # a member's score is the dependency sum over ITS source columns —
    # batched members each sum their own slice, so batched == solo
    bc = Bn.sum(axis=1)
    return [(i, float(bc[i])) for i in range(Bn.shape[0])]


def _closeness_rows(ctx, a, seeds, Bn):
    from repro.algorithms import closeness_from_levels
    scores = np.asarray(closeness_from_levels(jnp.asarray(Bn)))
    return [(int(s), float(scores[j])) for j, s in enumerate(seeds)]


def _similarity_rows(ctx, a, seeds, Bn):
    rows = [(int(seeds[j]), int(i), float(Bn[i, j]))
            for i, j in zip(*np.nonzero(Bn > 0))]
    rows.sort()
    return rows


def _bfs_rows(ctx, a, seeds, Bn):
    rows = [(int(seeds[j]), int(i), int(Bn[i, j]))
            for i, j in zip(*np.nonzero(np.isfinite(Bn)))]
    rows.sort()
    return rows


def _triangles_rows(ctx, a, seeds, Bn):
    return [(int(Bn[0, 0]),)]


PROCEDURES = {
    "algo.pagerank": Procedure(
        PROC_COLUMNS["algo.pagerank"], False,
        {"rel": None, "alpha": 0.85, "iters": 50},
        _pagerank_device, _node_float_rows),
    "algo.betweenness": Procedure(
        PROC_COLUMNS["algo.betweenness"], True,
        {"rel": None}, _betweenness_device, _betweenness_rows),
    "algo.closeness": Procedure(
        PROC_COLUMNS["algo.closeness"], True,
        {"rel": None}, _levels_device, _closeness_rows),
    "algo.similarity": Procedure(
        PROC_COLUMNS["algo.similarity"], True,
        {"rel": None, "kind": "jaccard"},
        _similarity_device, _similarity_rows),
    "algo.wcc": Procedure(
        PROC_COLUMNS["algo.wcc"], False,
        {"rel": None}, _wcc_device, _node_int_rows),
    "algo.labelprop": Procedure(
        PROC_COLUMNS["algo.labelprop"], False,
        {"rel": None, "max_iter": 50}, _labelprop_device, _node_int_rows),
    "algo.triangles": Procedure(
        PROC_COLUMNS["algo.triangles"], False,
        {"rel": None}, _triangles_device, _triangles_rows),
    "algo.bfs": Procedure(
        PROC_COLUMNS["algo.bfs"], True,
        {"rel": None, "max_hops": 0}, _levels_device, _bfs_rows),
}
assert set(PROCEDURES) == set(PROC_COLUMNS) and all(
    p.columns == PROC_COLUMNS[k] for k, p in PROCEDURES.items()), \
    "planner.PROC_COLUMNS out of sync with executor.PROCEDURES"


def _procedure(name: str) -> Procedure:
    proc = PROCEDURES.get(name)
    if proc is None:
        # raised at EXECUTION, not planning: the server turns this into a
        # per-query error Result instead of failing the submitter
        raise ValueError(f"no procedure {name!r} "
                         f"(have: {sorted(PROCEDURES)})")
    return proc


def _call_args(name: str, proc: Procedure, args: dict) -> dict:
    unknown = sorted(set(args) - set(proc.defaults))
    if unknown:
        takes = sorted(proc.defaults) + (["sources"] if proc.seeded else [])
        raise ValueError(f"{name}: unknown argument(s) {unknown} "
                         f"(takes: {takes})")
    out = dict(proc.defaults)
    out.update(args)
    return out


# -- public execution surface -------------------------------------------------
class ExecutionContext:
    """Execution primitives over one frozen Graph.

    node_mask  label + predicate scan -> bool (n,) diagonal
    expand     one variable-length traversal step on a frontier matrix
    traverse   seeds -> final frontier for a plan (unmaterialized device work)
    project    frontier matrix -> Result rows per the plan's RETURN clause
    run        parse/plan/execute a full read query (also accepts a Plan)

    The adjacency handles come from the graph's relations; `impl` re-resolves
    their execution policy once per context (not per call). With `mesh` set,
    every relation handle is distributed onto it (`grb.distribute`) and the
    same expand/run calls lower to mesh collectives — the context carries
    the mesh exactly like it carries `impl`; no primitive takes a sharding
    argument. Needs ELL-stored relations (grb raises a TypeError naming the
    expected kinds otherwise; `engine.Database` freezes sharded-mode graphs
    as ELL for this reason).
    """

    # multi-hop SpGEMM fast path is only planned for adjacencies up to this
    # many vertices (hop-matrix fill grows with hop count)
    SPGEMM_EXPAND_MAX_N = 16384

    def __init__(self, graph: Graph, impl: str = "auto",
                 spgemm_expand: bool = True, mesh=None):
        self.graph = graph
        self.impl = impl
        self.spgemm_expand = spgemm_expand
        self.mesh = mesh
        self._mats: Dict[str, grb.GBMatrix] = {}
        self._hops: Dict[tuple, grb.GBMatrix] = {}

    # -- primitives ----------------------------------------------------------
    def matrix(self, rel: Optional[str]) -> grb.GBMatrix:
        """Relation adjacency handle under this context's execution policy."""
        try:
            r = self.graph.relation(rel)
        except KeyError:
            r = None
        if r is None:
            raise ValueError(f"no relation {rel!r} "
                             f"(have: {sorted(self.graph.relations)})")
        m = self._mats.get(r.name)
        if m is None:
            m = r.A.with_impl(self.impl)
            if self.mesh is not None:
                m = grb.distribute(m, self.mesh)
            self._mats[r.name] = m
        return m

    def node_mask(self, label, preds=None) -> np.ndarray:
        """bool (n,): vertices carrying `label` and passing all predicates.
        The label's mask lives on the device; its read (span `repro.mask`)
        waits for the device work queued ahead of it, such as a sweep in
        flight."""
        n = self.graph.n
        with tracing.span("repro.mask"):
            m = np.asarray(self.graph.label_mask(label))
        for p in preds or []:
            m = m & eval_pred(self.graph, p, n)
        return m

    def seed_frontier(self, seeds, keep=None) -> jnp.ndarray:
        """One-hot (n, F) frontier from seed ids; columns where keep is False
        stay empty (filtered seeds still occupy their result column)."""
        seeds = np.asarray(seeds, dtype=np.int64)
        f = len(seeds)
        if keep is None:
            keep = np.ones(f, dtype=bool)
        B = jnp.zeros((self.graph.n, f), dtype=jnp.float32)
        return B.at[jnp.asarray(np.where(keep, seeds, 0)),
                    jnp.arange(f)].set(jnp.asarray(keep.astype(np.float32)))

    def _hop_matrix(self, rel, transpose: bool,
                    max_hops: int) -> grb.GBMatrix:
        """Union of walk matrices OR_{h=1..max} Mt^h over or_and, built once
        per (relation, direction, max_hops) via masked BSR x BSR SpGEMM and
        cached — one sparse handle that answers a whole multi-hop pattern."""
        key = (rel, transpose, max_hops)
        P = self._hops.get(key)
        if P is None:
            from repro.core.bsr import bsr_union, spgemm
            M = self.matrix(rel)
            Mt = (M.T if transpose else M).store
            acc = walk = Mt
            for _ in range(max_hops - 1):
                walk = spgemm(walk, Mt, S.OR_AND, impl=M.impl)
                acc = bsr_union(acc, walk)
            P = self._hops[key] = grb.GBMatrix(acc, impl=self.impl,
                                               name=f"{rel}^1..{max_hops}")
        return P

    def _expand_spgemm_ok(self, e, sr: S.Semiring, transposes) -> bool:
        """The hop-matrix rewrite is exact only for structural reachability
        starting at hop 1 in a single direction (walk-union == first-reach
        union once the seed columns are masked back out)."""
        return (self.spgemm_expand and sr.name == "or_and"
                and e.min_hops == 1 and e.max_hops > 1
                and len(transposes) == 1
                and self.matrix(e.rel).fmt == "bsr"
                and self.graph.n <= self.SPGEMM_EXPAND_MAX_N)

    def expand(self, B: jnp.ndarray, e, sr: S.Semiring,
               dst_mask: np.ndarray) -> jnp.ndarray:
        """min..max-hop traversal of B along e.rel in e.direction."""
        M = self.matrix(e.rel)
        transposes = {A.OUT: (True,), A.IN: (False,),
                      A.BOTH: (True, False)}[e.direction]
        structural = sr.name == "or_and"
        if self._expand_spgemm_ok(e, sr, transposes):
            # one masked mxm against the precomputed 1..max hop matrix
            # replaces max_hops sequential hops; <!seeds> removes the
            # closed-walk returns the loop's visited mask would have blocked
            P = self._hop_matrix(e.rel, transposes[0], e.max_hops)
            seeds0 = (B > 0).astype(jnp.float32)
            reach = grb.mxm(P, B, sr,
                            Descriptor(mask=seeds0, complement=True))
            reach = reach * jnp.asarray(dst_mask, dtype=jnp.float32)[:, None]
            return (reach > 0).astype(jnp.float32)
        if structural and grb.words_route_ok(M, B.shape[1]):
            # word-resident hop loop: pack once, hop on uint32 words with
            # word-wise visited blends ((a & ~v) | (b & ~v) == (a | b) & ~v),
            # unpack once at the end — no per-hop pack/unpack/gather
            f = B.shape[1]
            fw = _bitmap.pack(B)
            vw = fw
            reach_w = jnp.zeros_like(fw)
            for h in range(1, e.max_hops + 1):
                nw = None
                for t in transposes:
                    step = grb.mxm_words(M, fw, transpose_a=t)
                    nw = step if nw is None else _bitmap.word_or(nw, step)
                fw = _bitmap.word_andnot(nw, vw)
                vw = _bitmap.word_or(vw, fw)
                if h >= e.min_hops:
                    reach_w = _bitmap.word_or(reach_w, fw)
            reach = _bitmap.unpack(reach_w, f)
            return reach * jnp.asarray(dst_mask, dtype=jnp.float32)[:, None]
        reach = jnp.zeros_like(B)
        frontier = B
        visited = (B > 0).astype(jnp.float32)
        for h in range(1, e.max_hops + 1):
            nxt = None
            for t in transposes:
                d = Descriptor(mask=visited if structural else None,
                               complement=True, transpose_a=t)
                step = grb.mxm(M, frontier, sr, d)
                nxt = step if nxt is None else _sr_add(sr, nxt, step)
            frontier = nxt
            if structural:
                visited = jnp.maximum(visited,
                                      (frontier > 0).astype(jnp.float32))
            if h >= e.min_hops:
                reach = _sr_add(sr, reach, frontier)
        # destination label/property diagonal
        reach = reach * jnp.asarray(dst_mask, dtype=jnp.float32)[:, None]
        if structural:
            reach = (reach > 0).astype(jnp.float32)
        return reach

    def traverse(self, p: Plan, seeds, keep=None) -> jnp.ndarray:
        """Seeds -> final (n, F) frontier for a plan: the device half of
        `run`, and the batch hook the server composes (it concatenates many
        compatible members' seed columns into one call, padding lanes with
        keep=False columns). The frontier comes back UNmaterialized — under
        jax async dispatch the caller keeps scheduling host-side while the
        device sweeps. A CallPlan dispatches to its procedure's device
        half instead (same contract: columns belong to seed columns, so
        the server's per-member slicing works identically; padding lanes
        compute and get sliced away)."""
        if isinstance(p, CallPlan):
            return self._call_device(p, seeds)
        sr = S.get(p.semiring)
        B = self.seed_frontier(seeds, keep=keep)
        for e in p.expands:
            dst_mask = self.node_mask(e.dst_label, p.var_preds.get(e.dst_var))
            B = self.expand(B, e, sr, dst_mask)
        return B

    def project(self, p: Plan, seeds: np.ndarray, B: jnp.ndarray) -> Result:
        """Materialize RETURN rows from the final frontier matrix."""
        if isinstance(p, CallPlan):
            return self._call_project(p, seeds, np.asarray(B))
        Bn = np.asarray(B)
        cols = [_colname(r) for r in p.returns]
        src_var = p.src_var
        graph = self.graph

        returns_src = any(r.var == src_var and r.kind != "count"
                          for r in p.returns)
        only_counts = all(r.kind == "count" for r in p.returns)

        rows: List[tuple] = []
        if only_counts and not returns_src:
            # global aggregate: one row
            vals = []
            for r in p.returns:
                tot = ((Bn > 0).sum()
                       if r.distinct or p.semiring == "or_and" else Bn.sum())
                vals.append(int(tot))
            rows = [tuple(vals)]
        elif only_counts or (returns_src
                             and all(r.kind == "count" or r.var == src_var
                                     for r in p.returns)):
            # grouped by seed
            for j, s in enumerate(seeds):
                vals = []
                for r in p.returns:
                    if r.kind == "count":
                        tot = ((Bn[:, j] > 0).sum()
                               if (r.distinct or p.semiring == "or_and")
                               else Bn[:, j].sum())
                        vals.append(int(tot))
                    elif r.kind == "prop":
                        vals.append(_prop(graph, r.prop, int(s)))
                    else:
                        vals.append(int(s))
                rows.append(tuple(vals))
        else:
            # materialize (seed, dst) bindings
            dst_rows, seed_cols = np.nonzero(Bn > 0)
            for d, j in zip(dst_rows, seed_cols):
                vals = []
                for r in p.returns:
                    node = int(seeds[j]) if r.var == src_var else int(d)
                    if r.kind == "prop":
                        vals.append(_prop(graph, r.prop, node))
                    else:
                        vals.append(node)
                rows.append(tuple(vals))
            rows.sort()
        if p.limit is not None:
            rows = rows[: p.limit]
        return Result(cols, rows)

    # -- CALL dispatch -------------------------------------------------------
    def _call_device(self, p: CallPlan, seeds) -> jnp.ndarray:
        """Device half of a procedure call (traverse analog). Seeded
        procedures compute one column per seed; unseeded ones return a
        single shared column and reject an explicit `sources:` list."""
        proc = _procedure(p.proc)
        a = _call_args(p.proc, proc, p.args)
        if p.seeds is not None and not proc.seeded:
            raise ValueError(f"{p.proc} takes no sources "
                             f"(it is a whole-graph procedure)")
        return proc.device(self, a, np.asarray(seeds, dtype=np.int64))

    def _call_project(self, p: CallPlan, seeds, Bn: np.ndarray) -> Result:
        """Host half (project analog): the member's column slice -> YIELD
        rows. YIELD selects/renames/reorders the procedure's canonical
        columns; an unknown yield name raises (per-member, isolated)."""
        proc = _procedure(p.proc)
        a = _call_args(p.proc, proc, p.args)
        rows = proc.rows(self, a, np.asarray(seeds, dtype=np.int64), Bn)
        cols, idx = [], []
        for r in p.returns:
            if r.var not in proc.columns:
                raise ValueError(f"{p.proc} yields {list(proc.columns)}, "
                                 f"not {r.var!r}")
            cols.append(r.alias or r.var)
            idx.append(proc.columns.index(r.var))
        rows = [tuple(row[i] for i in idx) for row in rows]
        if p.limit is not None:
            rows = rows[: p.limit]
        return Result(cols, rows)

    # -- solo driver ---------------------------------------------------------
    def run(self, query) -> Result:
        """Execute a read query: text, MatchQuery AST, or an already-built
        Plan (the server's cached-plan path — no re-parse)."""
        if isinstance(query, (Plan, CallPlan)):
            p = query
        else:
            q = parse(query) if isinstance(query, str) else query
            if isinstance(q, (A.CreateQuery, A.DeleteQuery)):
                kw = "CREATE" if isinstance(q, A.CreateQuery) else "DELETE"
                raise TypeError(f"{kw} goes through engine.Database, not a "
                                f"read ExecutionContext")
            p = plan(q)

        src_mask = self.node_mask(p.src_label, p.var_preds.get(p.src_var))
        if p.seeds is not None:
            seeds = resolve_seeds(p, src_mask)
        else:
            seeds = np.nonzero(src_mask)[0]
        if len(seeds) == 0:
            return empty_result(p)
        return self.project(p, seeds, self.traverse(p, seeds))


def _sr_add(sr: S.Semiring, a, b):
    return jnp.maximum(a, b) if sr.name == "or_and" else a + b


# -- top level ----------------------------------------------------------------
def execute(graph: Graph, query, impl: str = "auto", mesh=None) -> Result:
    return ExecutionContext(graph, impl=impl, mesh=mesh).run(query)


def _colname(r: A.ReturnItem) -> str:
    if r.alias:
        return r.alias
    if r.kind == "count":
        return f"count({'DISTINCT ' if r.distinct else ''}{r.var})"
    if r.kind == "prop":
        return f"{r.var}.{r.prop}"
    return r.var


def _prop(graph: Graph, prop: str, node: int):
    col = graph.node_props.get(prop)
    if col is None:
        return None
    v = float(np.asarray(col)[node])
    return None if np.isnan(v) else v


def explain(graph: Graph, query) -> str:
    q = parse(query) if isinstance(query, str) else query
    return plan(q).explain()
