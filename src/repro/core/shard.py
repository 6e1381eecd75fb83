"""ShardedELL: row-sharded ELL storage behind the GBMatrix surface.

The fourth GBMatrix kind (dense / BSR / ELL / *sharded*): the same ELL
(indices, mask, values) row layout, but laid out over a ``jax.sharding.Mesh``
instead of one device —

  * adjacency rows           -> the mesh's "data" axis (row blocks),
  * frontier/query columns F -> the "pod" x "model" axes (query scale-out,
    the paper's threadpool claim at pod scale),
  * padded rows (mask-false) square the row count up to a multiple of the
    "data" axis so every shard_map spec divides evenly.

Storage only lives here; the *operations* stay where they always were:
``grb.mxm``/``mxv``/``reduce`` dispatch on the format tag and lower to the
explicit-collective shard_map bodies in ``repro.distr.graph2d`` (one frontier
all-gather per hop in row form, a psum_scatter of row blocks in transposed
form), so algorithms and the query executor run unchanged on a mesh. Wide
or_and frontiers cross the mesh bitmap-packed (``core.bitmap`` uint32
words — 32x less all-gather payload; grb sets ``packed=`` from its policy,
this module only pads/packs/unpacks at the lowering boundary).
``apply``/``select`` are embarrassingly local (stored-entry value maps) and
run right on the sharded arrays below. eWiseAdd/Mult, mask restricts,
column extract/assign, and min/max reduce are *also* mesh-resident now:
two identically-meshed operands merge shard-locally through the
slot-alignment pass in ``distr.graph2d.ewise_2d`` (rows live whole on one
shard, so COO set algebra is row-local). Only genuinely cross-shard
requests — row-subset extract/assign, a mask sharded on a *different*
mesh — still gather to host, and every such gather bumps
``core.xfer.host_transfers()`` (surfaced as ``grb.host_transfers()``).

Public contract: construction needs a Mesh with a "data" axis (TypeError /
ValueError otherwise); ``to_ell``/``to_dense``/``to_coo``/``transpose``
gather to host by design *and are counted*; everything in the "local
stored-entry ops" section is collective-free. Mixed sharded/unsharded
operand TypeErrors are raised one layer up, in ``repro.core.grb``, which
owns the pairing rules.

Handles over this storage are host-side objects like every GBMatrix; the
sharded jnp arrays are what flows through jit. The padded row block is an
internal detail: logical ``shape`` and stored-entry ``nnz`` never include it.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import xfer
from repro.core.ell import ELL

ROW_AXIS = "data"                      # adjacency rows shard over this axis
FRONTIER_AXES = ("pod", "model")       # frontier columns shard over these


def frontier_axes(mesh: Mesh) -> tuple:
    """The mesh axes (in canonical order) that shard the frontier's F dim."""
    return tuple(a for a in FRONTIER_AXES if a in mesh.axis_names)


def frontier_spec(mesh: Mesh):
    """PartitionSpec entry for the frontier's F dimension on this mesh."""
    fr = frontier_axes(mesh)
    if not fr:
        return None
    return fr if len(fr) > 1 else fr[0]


def _check_mesh(mesh: Mesh) -> Mesh:
    if not isinstance(mesh, Mesh):
        raise TypeError(f"ShardedELL needs a jax.sharding.Mesh, got "
                        f"{type(mesh).__name__}")
    if ROW_AXIS not in mesh.axis_names:
        raise ValueError(f"ShardedELL needs a mesh with a {ROW_AXIS!r} axis "
                         f"(rows shard over it); got axes {mesh.axis_names}")
    return mesh


class ShardedELL:
    """Row-sharded ELL storage over a mesh (see module doc).

    indices/mask/values are (n_pad, max_deg) device arrays placed with
    NamedSharding(mesh, P("data", None)); n_pad rounds the logical row count
    up to a multiple of the "data" axis size, the extra rows all mask-false.
    """
    __slots__ = ("shape", "mesh", "indices", "mask", "values", "nnz", "n_pad")

    def __init__(self, shape: Tuple[int, int], mesh: Mesh, indices, mask,
                 values, nnz: int):
        self.shape = tuple(shape)
        self.mesh = _check_mesh(mesh)
        self.indices = indices
        self.mask = mask
        self.values = values
        self.nnz = int(nnz)
        self.n_pad = int(indices.shape[0])

    # -- construction --------------------------------------------------------
    @classmethod
    def from_ell(cls, e: ELL, mesh: Mesh) -> "ShardedELL":
        """Pad the row block to the "data" axis and scatter it over the mesh."""
        _check_mesh(mesh)
        dsz = mesh.shape[ROW_AXIS]
        n, m = e.shape
        n_pad = n + (-n) % dsz
        idx = np.zeros((n_pad, e.max_deg), np.int32)
        msk = np.zeros((n_pad, e.max_deg), bool)
        val = np.zeros((n_pad, e.max_deg), np.float32)
        idx[:n] = np.asarray(e.indices)
        msk[:n] = np.asarray(e.mask)
        val[:n] = np.asarray(e.values)
        sh = NamedSharding(mesh, P(ROW_AXIS, None))
        # host arrays straight to their shards: no whole copy on one device
        return cls((n, m), mesh, jax.device_put(idx, sh),
                   jax.device_put(msk, sh), jax.device_put(val, sh),
                   nnz=e.nnz)

    @classmethod
    def from_coo(cls, rows, cols, vals, shape, mesh: Mesh) -> "ShardedELL":
        return cls.from_ell(ELL.from_coo(rows, cols, vals, shape), mesh)

    @classmethod
    def from_dense(cls, A, mesh: Mesh) -> "ShardedELL":
        return cls.from_ell(ELL.from_dense(A), mesh)

    # -- mesh geometry -------------------------------------------------------
    @property
    def max_deg(self) -> int:
        return self.indices.shape[1]

    @property
    def data_size(self) -> int:
        return self.mesh.shape[ROW_AXIS]

    @property
    def frontier_size(self) -> int:
        """Number of shards the frontier's F dimension splits into."""
        return int(np.prod([self.mesh.shape[a]
                            for a in frontier_axes(self.mesh)] or [1]))

    # -- gather-to-host conversions ------------------------------------------
    def to_ell(self) -> ELL:
        """Gather the row shards back to one host-side ELL (drops padding).
        Counted: this is *the* device->host choke point (to_dense/to_coo/
        transpose all route through it), so every remaining gather fallback
        shows up in grb.host_transfers()."""
        xfer.record("sharded_gather")
        n, m = self.shape
        return ELL(shape=(n, m),
                   indices=jnp.asarray(np.asarray(self.indices)[:n]),
                   mask=jnp.asarray(np.asarray(self.mask)[:n]),
                   values=jnp.asarray(np.asarray(self.values)[:n]),
                   nnz=self.nnz)

    def to_dense(self) -> jnp.ndarray:
        return self.to_ell().to_dense()

    def to_coo(self):
        return self.to_ell().to_coo()

    def transpose(self) -> "ShardedELL":
        """Host-gathered transpose, re-sharded onto the same mesh. Graph
        relations link explicitly-built transposes instead (grb.distribute),
        and un-linked handles never call this on the mxm path — the
        transposed (psum_scatter) lowering reads the forward rows."""
        return ShardedELL.from_ell(self.to_ell().transpose(), self.mesh)

    # -- local (collective-free) stored-entry ops ----------------------------
    def apply_stored(self, f) -> "ShardedELL":
        """f over stored entries, zero results dropped — runs shard-local on
        the mesh (values/mask are elementwise over the same row layout)."""
        vals = jnp.where(self.mask, f(self.values),
                         jnp.zeros_like(self.values))
        mask = self.mask & (vals != 0)
        vals = jnp.where(mask, vals, jnp.zeros_like(vals))
        return ShardedELL(self.shape, self.mesh, self.indices, mask, vals,
                          nnz=int(jnp.sum(mask)))

    def select_stored(self, pred) -> "ShardedELL":
        """Stored entries passing pred, shard-local (mask surgery only)."""
        mask = self.mask & jnp.asarray(pred(self.values)) & (self.values != 0)
        vals = jnp.where(mask, self.values, jnp.zeros_like(self.values))
        return ShardedELL(self.shape, self.mesh, self.indices, mask, vals,
                          nnz=int(jnp.sum(mask)))

    def __repr__(self) -> str:
        n, m = self.shape
        axes = "x".join(f"{a}:{self.mesh.shape[a]}"
                        for a in self.mesh.axis_names)
        return (f"ShardedELL {n}x{m} mesh=({axes}) nnz={self.nnz} "
                f"max_deg={self.max_deg}")


# ---------------------------------------------------------------------------
# op execution: pad, run the graph2d lowering, slice — what grb dispatches to
# ---------------------------------------------------------------------------
def _pad_frontier(s: ShardedELL, X: jnp.ndarray, x_rows: int):
    """Pad an (x_rows, F) frontier to the mesh-divisible (x_rows_pad, F_pad)."""
    dsz = s.data_size
    r_pad = (-x_rows) % dsz
    f_pad = (-X.shape[1]) % s.frontier_size
    if r_pad or f_pad:
        X = jnp.pad(X.astype(jnp.float32), ((0, r_pad), (0, f_pad)))
    return X.astype(jnp.float32)


def _pad_frontier_packed(s: ShardedELL, X: jnp.ndarray, x_rows: int):
    """Pack an (x_rows, F) frontier into uint32 words and pad both axes to
    the mesh: rows to the "data" axis, words to the frontier shard count."""
    from repro.core import bitmap
    Xw = bitmap.pack(X)
    r_pad = (-x_rows) % s.data_size
    w_pad = (-Xw.shape[1]) % s.frontier_size
    if r_pad or w_pad:
        Xw = jnp.pad(Xw, ((0, r_pad), (0, w_pad)))
    return Xw


def mxm(s: ShardedELL, X: jnp.ndarray, sr, transposed: bool = False,
        packed: bool = False):
    """Y = A (x) X (or A^T (x) X) on the mesh. X: dense (k, F) global array
    (k = A's columns in row form, A's rows in transposed form); the result is
    a global (rows, F) array, row-sharded over "data" under GSPMD.

    packed=True (or_and only, set by grb's bitmap policy): X crosses the
    mesh as core.bitmap uint32 words — the frontier all-gather moves 32x
    fewer bytes in row form; the transposed form psum_scatters summable
    nibble words (8x) up to bitmap.NIBBLE_MAX_SHARDS row shards, beyond
    which graph2d.mxm_2d itself builds the unpacked-psum_scatter body
    (same word signature — the limit is enforced at the lowering, not
    here).
    """
    from repro.core import bitmap
    from repro.distr import graph2d                 # lazy: core never pulls
    n, m = s.shape                                  # distr at import time
    dsz = s.data_size
    if transposed:
        fn = graph2d.mxm_2d(s.mesh, sr, transposed=True,
                            out_rows=m + (-m) % dsz, packed=packed)
        Xp = (_pad_frontier_packed(s, X, n) if packed
              else _pad_frontier(s, X, n))          # x rides A's row shards
        out_rows = m
    else:
        fn = graph2d.mxm_2d(s.mesh, sr, packed=packed)
        Xp = (_pad_frontier_packed(s, X, m) if packed
              else _pad_frontier(s, X, m))          # x rows are A's columns
        out_rows = n
    Y = fn(s.indices, s.mask, s.values, Xp)
    if packed:
        return bitmap.unpack(Y[:out_rows], X.shape[1])
    return Y[:out_rows, :X.shape[1]]


def _pad_words(s: ShardedELL, Xw: jnp.ndarray, x_rows: int):
    """Pad an already-packed (x_rows, W) word frontier to the mesh: rows to
    the "data" axis, words to the frontier shard count. Device-side jnp.pad —
    word-resident loops never bounce through pack/unpack here."""
    r_pad = (-x_rows) % s.data_size
    w_pad = (-Xw.shape[1]) % s.frontier_size
    if r_pad or w_pad:
        Xw = jnp.pad(Xw, ((0, r_pad), (0, w_pad)))
    return Xw


def mxm_words(s: ShardedELL, Xw: jnp.ndarray, transposed: bool = False):
    """or_and mxm with the frontier already in uint32 words: words in, words
    out — the packed-in/packed-out entry word-resident hop loops thread
    through (no pack/unpack at the call boundary, grb.mxm_words dispatches
    here). Beyond bitmap.NIBBLE_MAX_SHARDS row shards the transposed
    lowering itself swaps the nibble psum for the unpacked psum_scatter
    body (graph2d.mxm_2d detects the mesh width at build time), so the
    word-in/word-out contract holds at any shard count."""
    from repro.core import semiring as S
    from repro.distr import graph2d
    n, m = s.shape
    dsz = s.data_size
    if transposed:
        fn = graph2d.mxm_2d(s.mesh, S.OR_AND, transposed=True,
                            out_rows=m + (-m) % dsz, packed=True)
        Xp = _pad_words(s, Xw, n)
        out_rows = m
    else:
        fn = graph2d.mxm_2d(s.mesh, S.OR_AND, packed=True)
        Xp = _pad_words(s, Xw, m)
        out_rows = n
    Y = fn(s.indices, s.mask, s.values, Xp)
    return Y[:out_rows, :Xw.shape[1]]


def reduce_stored(s: ShardedELL, monoid, axis):
    """plus/or stored-entry reduction via the graph2d psum lowering; min/max
    go through :func:`reduce_minmax` (dense semantics, still mesh-resident);
    anything else gathers via the counted dense fallback in grb.reduce."""
    from repro.distr import graph2d
    n, m = s.shape
    fn = graph2d.reduce_2d(s.mesh, monoid.name, axis, m)
    out = fn(s.indices, s.mask, s.values)
    if axis == 1:
        return out[:n]
    return out


def reduce_minmax(s: ShardedELL, monoid, axis):
    """min/max reduction with dense semantics (absent entries render 0),
    mesh-resident: stored-entry pmin/pmax over "data" + a stored-count
    compare to fold the implicit zeros back in (graph2d.reduce_minmax_2d).
    Replaces the old gather-to-host special case in grb.reduce."""
    from repro.distr import graph2d
    n, m = s.shape
    fn = graph2d.reduce_minmax_2d(s.mesh, monoid.name, axis, n, m)
    out = fn(s.indices, s.mask, s.values)
    if axis == 1:
        return out[:n]
    return out


# ---------------------------------------------------------------------------
# shard-local element-wise family — the slot-aligned merge grb dispatches to
# ---------------------------------------------------------------------------
def _pair_check(a: ShardedELL, b: ShardedELL, what: str):
    if a.shape != b.shape:
        raise ValueError(f"{what}: shape mismatch {a.shape} vs {b.shape}")
    if a.mesh is not b.mesh and a.mesh != b.mesh:
        raise TypeError(f"{what}: operands live on different meshes")


def merge_stored(a: ShardedELL, b: ShardedELL, op, mode: str) -> ShardedELL:
    """Shard-local merge of two identically-meshed operands (see
    graph2d._ewise_merge for the slot-alignment pass and mode semantics).
    Same shape + mesh implies the same padded row count, so the row blocks
    align shard-for-shard; the merged layout is the concatenated slot width.
    """
    from repro.distr import graph2d
    _pair_check(a, b, f"merge_stored[{mode}]")
    fn = graph2d.ewise_2d(a.mesh, mode, op)
    idx, msk, val = fn(a.indices, a.mask, a.values,
                       b.indices, b.mask, b.values)
    return ShardedELL(a.shape, a.mesh, idx, msk, val, nnz=int(jnp.sum(msk)))


def restrict_dense(a: ShardedELL, dense_mask, complement: bool) -> ShardedELL:
    """Keep a's stored entries where a dense (n, m) mask is nonzero (or zero,
    complemented) — shard-local per-slot gather (graph2d.restrict_dense_2d).
    The mask row block is padded to the mesh like every operand."""
    from repro.distr import graph2d
    dm = jnp.asarray(dense_mask)
    r_pad = a.n_pad - dm.shape[0]
    if r_pad:
        dm = jnp.pad(dm, ((0, r_pad), (0, 0)))
    fn = graph2d.restrict_dense_2d(a.mesh, bool(complement))
    idx, msk, val = fn(a.indices, a.mask, a.values, dm)
    return ShardedELL(a.shape, a.mesh, idx, msk, val, nnz=int(jnp.sum(msk)))


def extract_cols(a: ShardedELL, cols) -> ShardedELL:
    """Column-subset extract (rows stay put): relabel stored columns through
    a replicated LUT, shard-local. Row subsets re-partition the "data" axis
    and stay on the counted gather fallback in grb.extract."""
    from repro.distr import graph2d
    cols = np.asarray(cols, np.int64)
    lut = np.full((a.shape[1],), -1, np.int32)
    lut[cols] = np.arange(len(cols), dtype=np.int32)
    fn = graph2d.extract_cols_2d(a.mesh)
    idx, msk, val = fn(a.indices, a.mask, a.values, jnp.asarray(lut))
    return ShardedELL((a.shape[0], len(cols)), a.mesh, idx, msk, val,
                      nnz=int(jnp.sum(msk)))


def relabel_cols(a: ShardedELL, new_cols, ncols_out: int) -> ShardedELL:
    """Map every stored column j -> new_cols[j] (all >= 0), producing an
    (n, ncols_out) operand — the inverse relabel assign(:, J) needs to put a
    region operand back into global coordinates. Shard-local LUT gather."""
    from repro.distr import graph2d
    lut = np.asarray(new_cols, np.int32)
    fn = graph2d.extract_cols_2d(a.mesh)
    idx, msk, val = fn(a.indices, a.mask, a.values, jnp.asarray(lut))
    return ShardedELL((a.shape[0], ncols_out), a.mesh, idx, msk, val,
                      nnz=int(jnp.sum(msk)))
