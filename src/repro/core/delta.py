"""Delta matrices: live mutations over a frozen base — the fifth storage kind.

RedisGraph's production write path (the paper's design) never rebuilds the
adjacency on a write: each relation keeps small *delta* matrices — pending
additions and pending deletions — that are lazily merged into the main
matrix, so reads stay fast while writes stream in. :class:`DeltaMatrix` is
that form here: a frozen base (BSR / ELL / dense jnp array) plus two small
host-side COO sets,

  plus   entries added (or overwritten) since the base froze,
  minus  base entries deleted since the base froze,

with the effective matrix defined as ``(base \\ minus) overridden-by plus``.
The shape may be *larger* than the base's — node creation grows the matrix
without touching the frozen storage (rows/cols past the base are served
entirely from the deltas).

Dispatch lives behind ``grb.GBMatrix`` like every other kind (fmt
``"delta"``). The matmul family composes with **zero rebuild**: result row i
depends only on matrix row i, so ``mxm(D, B) = where(touched_row,
mxm(patch, B), mxm(base, B))`` where ``patch`` (:meth:`DeltaMatrix.patch`)
is a small ELL holding the exact effective content of just the delta-touched
rows. The same row decomposition serves plus/or reductions; transposes are
maintained *incrementally* (the graph layer appends swapped deltas to the
linked twin — never a runtime flip). The element-wise family and the SpGEMM
route fall back to a lazily cached :meth:`materialize` of the effective
matrix in the base's own format — the delta analog of the sharded
gather-to-host fallback (docs/API.md §Delta).

Updates are **functional**: :meth:`apply_ops` returns a new DeltaMatrix
sharing the base (and its host-side entry index), so a reader holding an
earlier handle keeps a snapshot-consistent view while a writer streams
edits — the Redis fork-snapshot spirit without the fork.

Compaction: once the pending-entry count crosses
``AUTO_DELTA_COMPACT * base_nnz`` (:func:`needs_compaction`; measured by
``benchmarks/bench_mutations.py``), composing per read costs more than one
rebuild amortizes — callers (``engine.MutableGraph.freeze``) then fold the
deltas back into a fresh base via :meth:`compact`.

Invariants (maintained by :meth:`apply_ops`):
  * ``minus`` keys are all present in the base; ``plus`` and ``minus`` are
    disjoint; ``plus`` values are nonzero (stored == nonzero, repo-wide).
  * adding an entry with value 0, or deleting it, are the same operation.
  * nnz is exact: ``base.nnz - |minus| + |plus keys not in base|``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core.bsr import BSR
from repro.core.ell import ELL

# -- compaction policy ---------------------------------------------------------
# Measured by benchmarks/bench_mutations.py (RMAT scale 12, edge_factor 8,
# plus_times mxv reads, XLA-CPU reference host): delta-served reads stay
# within ~1.3-1.4x of compacted-base reads up to a pending fraction of 0.05
# of base nnz, then cliff to >4x at 0.1 — past ~5% random edits nearly every
# row is touched, so the patch becomes a second full-height ELL whose width
# buckets up to the hub degree. One compaction costs ~0.3 reads (10-20ms vs
# a 39ms read), so folding at the cliff's base amortizes within a single
# read while keeping the write path O(pending) below it. See docs/API.md
# §Delta dispatch and the crossover_ratio* rows of the bench.
AUTO_DELTA_COMPACT = 0.05


def needs_compaction(d: "DeltaMatrix") -> bool:
    """Measured compaction policy: pending deltas past this fraction of the
    base's stored entries cost more per read than a rebuild amortizes."""
    return d.pending > AUTO_DELTA_COMPACT * max(d.base_nnz, 1)


BaseStorage = Union[BSR, ELL, jnp.ndarray]

# one edit: ("add", row, col, value) | ("del", row, col, 0.0)
Op = Tuple[str, int, int, float]


class _BaseIndex:
    """Host-side entry index of a frozen base, built once and shared by every
    DeltaMatrix over that base (functional updates reuse it — the one-time
    O(nnz) host extraction is paid per *freeze*, not per write)."""

    def __init__(self, store: BaseStorage):
        if isinstance(store, (BSR, ELL)):
            r, c, v = store.to_coo()
        else:
            a = np.asarray(store)
            r, c = np.nonzero(a)
            v = a[r, c]
        self.rows = np.asarray(r, dtype=np.int64)
        self.cols = np.asarray(c, dtype=np.int64)
        self.vals = np.asarray(v, dtype=np.float32)
        # row-sorted view for O(deg) touched-row gathers
        order = np.argsort(self.rows, kind="stable")
        self.r_sorted = self.rows[order]
        self.c_sorted = self.cols[order]
        self.v_sorted = self.vals[order]
        self.nnz = len(self.rows)

    def keys(self, ncols: int) -> np.ndarray:
        """Sorted entry keys under a (possibly grown) column extent."""
        k = self.rows * int(ncols) + self.cols
        return np.sort(k)

    def row_slice(self, rows: np.ndarray):
        """(rows, cols, vals) of base entries whose row is in `rows`
        (unique), via binary search on the row-sorted view."""
        lo = np.searchsorted(self.r_sorted, rows, side="left")
        hi = np.searchsorted(self.r_sorted, rows, side="right")
        take = np.concatenate(
            [np.arange(a, b) for a, b in zip(lo, hi)]
        ) if len(rows) else np.zeros(0, np.int64)
        take = take.astype(np.int64)
        return (self.r_sorted[take], self.c_sorted[take],
                self.v_sorted[take])


def _shape_of(store: BaseStorage) -> Tuple[int, int]:
    return tuple(store.shape)


def _in_sorted(sorted_keys: np.ndarray, query: np.ndarray) -> np.ndarray:
    """Membership of `query` keys in a sorted key array."""
    if len(sorted_keys) == 0:
        return np.zeros(len(query), dtype=bool)
    j = np.clip(np.searchsorted(sorted_keys, query), 0,
                len(sorted_keys) - 1)
    return sorted_keys[j] == query


@dataclasses.dataclass(eq=False)
class DeltaMatrix:
    """Frozen base + pending plus/minus COO deltas (see module docstring).

    Treat instances as immutable: every mutation goes through
    :meth:`apply_ops` / :meth:`resize`, which return a new DeltaMatrix
    sharing the base and its host index. The composed views (`patch`,
    `materialize`) are cached per instance.
    """
    base: BaseStorage
    shape: Tuple[int, int]
    plus_r: np.ndarray          # int64 rows of added/overridden entries
    plus_c: np.ndarray          # int64 cols
    plus_v: np.ndarray          # f32 values (all nonzero)
    minus_r: np.ndarray         # int64 rows of deleted base entries
    minus_c: np.ndarray         # int64 cols

    def __post_init__(self):
        self._index: Optional[_BaseIndex] = None
        self._patch = None            # (ELL, touched bool (n,)) or (None, None)
        self._mat: Optional[BaseStorage] = None

    # -- construction ----------------------------------------------------------
    @classmethod
    def wrap(cls, store: BaseStorage,
             shape: Optional[Tuple[int, int]] = None) -> "DeltaMatrix":
        """Empty-delta view over a frozen base. `shape` >= base shape grows
        the matrix (new rows/cols served purely from future deltas)."""
        if isinstance(store, DeltaMatrix):
            return store if shape is None else store.resize(shape)
        from repro.core.bitadj import BitELL
        if isinstance(store, BitELL):
            # bit-tiles have no row-patch composition (a delta write lands
            # mid-word): mutate over the cached ELL materialization — the
            # same fallback the weighted-semiring dispatch takes
            store = store.to_ell()
        if not isinstance(store, (BSR, ELL)):
            store = jnp.asarray(store)
        bshape = _shape_of(store)
        shape = bshape if shape is None else tuple(shape)
        if shape[0] < bshape[0] or shape[1] < bshape[1]:
            raise ValueError(f"DeltaMatrix shape {shape} smaller than base "
                             f"{bshape} — deltas grow, never shrink")
        z = np.zeros(0, dtype=np.int64)
        return cls(store, shape, z, z, np.zeros(0, np.float32), z.copy(),
                   z.copy())

    def _with(self, **kw) -> "DeltaMatrix":
        d = dataclasses.replace(self, **kw)
        d._index = self._index           # base is shared; so is its index
        return d

    # -- introspection -----------------------------------------------------------
    @property
    def index(self) -> _BaseIndex:
        if self._index is None:
            self._index = _BaseIndex(self.base)
        return self._index

    @property
    def base_nnz(self) -> int:
        if isinstance(self.base, (BSR, ELL)):
            return self.base.nnz
        return int(np.count_nonzero(np.asarray(self.base)))

    @property
    def pending(self) -> int:
        """Pending delta entries (the compaction-policy quantity)."""
        return len(self.plus_r) + len(self.minus_r)

    @property
    def nnz(self) -> int:
        """Exact effective stored-entry count."""
        if self.pending == 0:
            return self.base_nnz
        m = self.shape[1]
        bk = self.index.keys(m)
        new = ~_in_sorted(bk, self.plus_r * m + self.plus_c)
        return self.base_nnz - len(self.minus_r) + int(new.sum())

    @property
    def fmt(self) -> str:
        """Base storage format the deltas compact back into."""
        if isinstance(self.base, BSR):
            return "bsr"
        if isinstance(self.base, ELL):
            return "ell"
        return "dense"

    def __repr__(self) -> str:
        n, m = self.shape
        return (f"DeltaMatrix {n}x{m} base={self.fmt}{_shape_of(self.base)} "
                f"+{len(self.plus_r)}/-{len(self.minus_r)} nnz={self.nnz}")

    # -- mutation (functional) ----------------------------------------------------
    def resize(self, shape: Tuple[int, int]) -> "DeltaMatrix":
        shape = tuple(shape)
        if shape == self.shape:
            return self
        if shape[0] < self.shape[0] or shape[1] < self.shape[1]:
            raise ValueError(f"DeltaMatrix resize {self.shape} -> {shape}: "
                             f"deltas grow, never shrink")
        return self._with(shape=shape)

    def apply_ops(self, ops: Sequence[Op],
                  grow_to: Optional[Tuple[int, int]] = None) -> "DeltaMatrix":
        """One ordered batch of edits -> a new DeltaMatrix (self unchanged).

        ops: ("add", i, j, w) sets entry (i, j) to w (w == 0 deletes);
             ("del", i, j, _) deletes it (a no-op if absent). Later ops win.
        """
        out = self if grow_to is None else self.resize(grow_to)
        if not ops:
            return out
        n, m = out.shape
        plus = {(int(r), int(c)): float(v)
                for r, c, v in zip(out.plus_r, out.plus_c, out.plus_v)}
        minus = set(zip(out.minus_r.tolist(), out.minus_c.tolist()))
        # base membership for the delete/nnz invariants
        bk = self.index.keys(m)
        for kind, i, j, w in ops:
            i, j = int(i), int(j)
            if i >= n or j >= m or i < 0 or j < 0:
                raise ValueError(f"delta op {kind} ({i}, {j}) out of bounds "
                                 f"for shape {(n, m)}")
            key = (i, j)
            if kind == "add" and w != 0.0:
                minus.discard(key)
                plus[key] = float(w)
            else:                         # delete (or add of an explicit 0)
                plus.pop(key, None)
                if _in_sorted(bk, np.asarray([i * m + j]))[0]:
                    minus.add(key)
        pk = sorted(plus)
        mk = sorted(minus)
        return out._with(
            plus_r=np.asarray([k[0] for k in pk], dtype=np.int64),
            plus_c=np.asarray([k[1] for k in pk], dtype=np.int64),
            plus_v=np.asarray([plus[k] for k in pk], dtype=np.float32),
            minus_r=np.asarray([k[0] for k in mk], dtype=np.int64),
            minus_c=np.asarray([k[1] for k in mk], dtype=np.int64))

    def add_entries(self, rows, cols, vals=None) -> "DeltaMatrix":
        rows = np.asarray(rows).ravel()
        vals = np.ones(len(rows), np.float32) if vals is None \
            else np.asarray(vals, np.float32).ravel()
        return self.apply_ops([("add", i, j, w) for i, j, w in
                               zip(rows, np.asarray(cols).ravel(), vals)])

    def delete_entries(self, rows, cols) -> "DeltaMatrix":
        return self.apply_ops([("del", i, j, 0.0) for i, j in
                               zip(np.asarray(rows).ravel(),
                                   np.asarray(cols).ravel())])

    # -- composition --------------------------------------------------------------
    def touched_rows(self) -> np.ndarray:
        """Unique rows any pending delta touches."""
        return np.unique(np.concatenate([self.plus_r, self.minus_r]))

    def patch(self):
        """(ELL patch, scatter rows): the exact effective content of the
        delta-touched rows — the row half of the mxm/reduce composition.

        The patch holds ONLY the touched rows (t of them, bucketed up to a
        power of two), so composing it costs O(t * deg) regardless of the
        matrix size; ``rows`` maps patch row -> matrix row, padded with the
        out-of-bounds index n so consumers scatter the patch product with
        ``.at[rows].set(..., mode="drop")``. Both the row count and the ELL
        width are power-of-two bucketed: each distinct shape is a fresh XLA
        compile on the serving path, bucketing caps a live-write stream at
        O(log^2 n) patch compilations. (None, None) if no deltas pending."""
        if self._patch is None:
            if self.pending == 0:
                self._patch = (None, None)
            else:
                with tracing.span("repro.delta.patch"):
                    n, m = self.shape
                    rows = self.touched_rows()
                    br, bc, bv = self.index.row_slice(rows)
                    k = br * m + bc
                    drop = _in_sorted(
                        np.sort(self.minus_r * m + self.minus_c), k)
                    drop |= _in_sorted(
                        np.sort(self.plus_r * m + self.plus_c), k)
                    er = np.concatenate([br[drop == False],  # noqa: E712
                                         self.plus_r])
                    ec = np.concatenate([bc[~drop], self.plus_c])
                    ev = np.concatenate([bv[~drop], self.plus_v])
                    er = np.searchsorted(rows, er)      # patch-local row ids
                    t, tp = len(rows), 8
                    while tp < t:
                        tp *= 2
                    md = (int(np.bincount(er, minlength=1).max())
                          if len(er) else 1)
                    pad = 8
                    while pad < md:
                        pad *= 2
                    scatter = np.full(tp, n, dtype=np.int32)
                    scatter[:t] = rows
                    # the cache outlives any trace that triggers the build
                    # (e.g. sssp's while_loop body) — arrays must be
                    # concrete, never trace-bound tracers (same rule as
                    # GBMatrix.T)
                    with jax.ensure_compile_time_eval():
                        self._patch = (ELL.from_coo(er, ec, ev, (tp, m),
                                                    pad_deg_to=pad),
                                       jnp.asarray(scatter))
        return self._patch

    def effective_coo(self):
        """(rows, cols, vals) of the effective matrix — base minus deletions,
        overridden/extended by the plus set."""
        m = self.shape[1]
        idx = self.index
        k = idx.rows * m + idx.cols
        drop = _in_sorted(np.sort(self.minus_r * m + self.minus_c), k)
        drop |= _in_sorted(np.sort(self.plus_r * m + self.plus_c), k)
        return (np.concatenate([idx.rows[~drop], self.plus_r]),
                np.concatenate([idx.cols[~drop], self.plus_c]),
                np.concatenate([idx.vals[~drop], self.plus_v]))

    def materialize(self) -> BaseStorage:
        """Effective matrix composed into the base's own format (cached) —
        the fallback the element-wise family and SpGEMM dispatch use, and
        the compaction product. Deterministic: identical entries produce
        storage identical to a from-scratch build of the same format."""
        if self._mat is None:
            # cached past the current trace — keep the arrays concrete
            # (same rule as patch() above and GBMatrix.T)
            with jax.ensure_compile_time_eval():
                if self.pending == 0 and self.shape == _shape_of(self.base):
                    self._mat = self.base
                elif isinstance(self.base, BSR):
                    r, c, v = self.effective_coo()
                    self._mat = BSR.from_coo(r, c, v, self.shape,
                                             block=self.base.block)
                elif isinstance(self.base, ELL):
                    r, c, v = self.effective_coo()
                    self._mat = ELL.from_coo(r, c, v, self.shape)
                else:
                    d = np.zeros(self.shape, dtype=np.float32)
                    bn, bm = _shape_of(self.base)
                    d[:bn, :bm] = np.asarray(self.base)
                    if len(self.minus_r):
                        d[self.minus_r, self.minus_c] = 0.0
                    if len(self.plus_r):
                        d[self.plus_r, self.plus_c] = self.plus_v
                    self._mat = jnp.asarray(d)
        return self._mat

    def compact(self) -> "DeltaMatrix":
        """Fold the deltas into a fresh base (empty-delta DeltaMatrix)."""
        return DeltaMatrix.wrap(self.materialize())

    # -- storage protocol (what GBMatrix forwards) ---------------------------------
    def to_dense(self) -> jnp.ndarray:
        if isinstance(self.base, (BSR, ELL)):
            d = np.zeros(self.shape, dtype=np.float32)
            r, c, v = self.effective_coo()
            d[r, c] = v
            return jnp.asarray(d)
        return self.materialize()        # dense base: the scatter above

    def to_coo(self):
        r, c, v = self.effective_coo()
        order = np.argsort(r * self.shape[1] + c)
        return (r[order].astype(np.int64), c[order].astype(np.int64),
                v[order].astype(np.float32))

    def transpose(self) -> "DeltaMatrix":
        """Transposed delta view. The graph layer never calls this on the
        hot path — it maintains linked twins incrementally by applying
        swapped deltas (engine.MutableGraph); this exists so an unlinked
        ``.T`` on a bare delta handle still resolves correctly."""
        bt = self.base.T if isinstance(self.base, jnp.ndarray) \
            else self.base.transpose()
        d = DeltaMatrix(bt, (self.shape[1], self.shape[0]),
                        self.plus_c.copy(), self.plus_r.copy(),
                        self.plus_v.copy(), self.minus_c.copy(),
                        self.minus_r.copy())
        return d
