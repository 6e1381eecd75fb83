"""BitELL: bit-packed structural adjacency — the sixth storage kind.

Bit-GraphBLAS (arXiv 2201.08560) observes that for *unweighted* relations
the adjacency matrix itself is boolean, so storing float32 edge weights
wastes 31/32 of the memory and bandwidth exactly like unpacked frontiers
did before ``core.bitmap``. BitELL packs the structure into uint32
bit-tiles: rows are grouped into 32-row *panels*, each panel keeps an
ELL-style list of occupied 32-column *tile slots*, and one tile — a whole
32x32 block of edges — lives in 32 machine words:

    tiles  (P, S, 32) uint32   bit b of tiles[p, s, r] <=> edge
                               (p*32 + r,  cols[p, s]*32 + b)
    cols   (P, S)     int32    column-tile id per slot (sentinel C = empty)

with P = ceil(n/32) panels and S the widest panel's slot count. Payload is
4 bytes per 32 potential edges vs ELL's ~9 bytes per stored edge — for
tiles above ~2% fill the structure is >= 8x smaller, and the or_and matmul
family becomes word-AND + OR over the packed frontier words of PR 5
(``core.bitmap``), so BFS / k-hop / WCC hop loops run uint32 in, uint32
out, with zero float intermediates. Triangle counting is AND + SWAR
popcount over tile pairs. Weighted semirings, the element-wise family, and
delta mutation have no bit-level form and take a cached materialize-to-ELL
fallback — the exact dispatch contract DeltaMatrix already uses
(docs/API.md §BitAdj).

``ShardedBitELL`` is the mesh twin behind ``grb.distribute``: panels shard
over the "data" axis, the per-hop frontier all-gather carries packed words
over bit-packed panels (the ``distr.graph2d.bit_mxm_2d`` lowering), and
``grb.distribute`` force-builds + links the transpose twin so
``transpose_a`` always serves from stored panels — there is no transposed
bit-scatter lowering. Gather-to-host conversions are counted via
``core.xfer`` like every other storage kind's.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitmap, xfer
from repro.core.ell import ELL, HostCount, checked_nnz

Array = jnp.ndarray

TILE = bitmap.WORD_BITS     # 32-row panels x 32-column tiles, one uint32/row

# -- impl="auto" crossover policy ---------------------------------------------
# Measured by benchmarks/calibrate.py::calibrate_bitadj_fill (RMAT-style
# random structure, n=2048, occupied-tile fill swept 0.005->0.25, or_and
# mxm at F=128, XLA-CPU reference host): the bit route crosses below ELL
# at ~0.01-0.02 occupied-tile fill and wins 3-6x by 0.1 — one padded slot
# costs 132 bytes against ~9 bytes per ELL entry, so ~15 edges per
# occupied tile (fill 0.014) is also the memory break-even. Committed at
# the measured speed crossover step 0.02. AUTO_BITADJ_MAX_SLOTS caps the
# ELL-style slot padding: past ~64 occupied column tiles in the widest
# panel the padded (P, S, 32) payload outgrows the ELL it replaces on the
# skewed panels this host measured (calibrate_bitadj_slots).
AUTO_BITADJ_MIN_FILL = 0.02   # occupied-tile fill below this: ELL wins
AUTO_BITADJ_MAX_SLOTS = 64    # widest-panel slots above this: padding loses


def _tile_stats(rows, cols, shape):
    """(occupied-tile fill, widest-panel slot count) of a COO structure."""
    rows = np.asarray(rows, dtype=np.int64)
    cols = np.asarray(cols, dtype=np.int64)
    if rows.size == 0:
        return 0.0, 0
    n_ct = -(-int(shape[1]) // TILE)
    key = np.unique((rows // TILE) * n_ct + (cols // TILE))
    slots = int(np.bincount((key // n_ct).astype(np.int64)).max())
    fill = rows.size / (len(key) * TILE * TILE)
    return fill, slots


def auto_bitadj_ok(rows, cols, vals, shape) -> bool:
    """Construction-time side of the BitELL auto policy: a *boolean*
    relation (all stored values 1.0 — structure is the payload) whose
    occupied 32x32 tiles are dense enough for the word route to win
    (AUTO_BITADJ_MIN_FILL) without slot-padding blowup on skewed panels
    (AUTO_BITADJ_MAX_SLOTS)."""
    if vals is not None and not np.all(np.asarray(vals) == 1.0):
        return False
    if np.asarray(rows).size == 0:
        return False
    fill, slots = _tile_stats(rows, cols, shape)
    return fill >= AUTO_BITADJ_MIN_FILL and slots <= AUTO_BITADJ_MAX_SLOTS


@jax.tree_util.register_pytree_node_class
class BitELL:
    """``nnz`` is an exact host int, read without touching the device; the
    pytree's static data is the shape alone (see `core.ell.HostCount`)."""

    def __init__(self, shape: Tuple[int, int], tiles: Array, cols: Array,
                 nnz: Optional[int]):
        self.shape = shape
        self.tiles = tiles  # (P, S, 32) uint32 bit-tiles (see module doc)
        self.cols = cols    # (P, S) i32 column-tile per slot; sentinel = n_ctiles
        self._nnz = None if nnz is None else int(nnz)
        # cached ELL materialization (the weighted/ewise/delta fallback
        # target); host-side cache like GBMatrix._T, never part of the
        # traced pytree
        self._ell: Optional[ELL] = None

    @property
    def nnz(self) -> int:
        return checked_nnz("BitELL", self._nnz)

    def tree_flatten(self):
        return (self.tiles, self.cols), (self.shape, HostCount(self._nnz))

    @classmethod
    def tree_unflatten(cls, aux, children):
        shape, count = aux
        return cls(shape, *children, nnz=count.rebuilt(children))

    # -- geometry ------------------------------------------------------------
    @property
    def n_panels(self) -> int:
        return self.tiles.shape[0]

    @property
    def n_slots(self) -> int:
        return self.tiles.shape[1]

    @property
    def n_ctiles(self) -> int:
        return -(-self.shape[1] // TILE)

    @property
    def payload_bytes(self) -> int:
        """Adjacency payload (tiles + slot index) — what the >= 8x-vs-ELL
        regression and benchmarks/bench_bitadj.py account."""
        return int(self.tiles.size) * 4 + int(self.cols.size) * 4

    # -- construction --------------------------------------------------------
    @staticmethod
    def from_coo(rows, cols, vals, shape, pad_slots_to: int = 1) -> "BitELL":
        """Structural build: every (row, col) pair is an edge. ``vals`` must
        be None or all-ones — BitELL stores no weights (TypeError names the
        materialize-to-ELL escape hatch for weighted relations)."""
        if vals is not None and not np.all(np.asarray(vals) == 1.0):
            raise TypeError(
                "BitELL is structural (boolean) storage and cannot carry "
                "edge weights; build fmt='ell' (or let fmt='auto' pick) for "
                "weighted relations")
        rows = np.asarray(rows, dtype=np.int64)
        cols = np.asarray(cols, dtype=np.int64)
        n, k = int(shape[0]), int(shape[1])
        P = max(-(-n // TILE), 1)
        C = max(-(-k // TILE), 1)
        key = rows // TILE * C + cols // TILE          # global tile id
        order = np.argsort(key, kind="stable")
        rows, cols, key = rows[order], cols[order], key[order]
        ukey, inv = np.unique(key, return_inverse=True)
        up = (ukey // C).astype(np.int64)              # panel of each tile
        # slot position of each occupied tile within its panel
        pdeg = np.bincount(up, minlength=P)
        S = int(pdeg.max()) if pdeg.size and pdeg.max() > 0 else 1
        S = S + (-S) % max(pad_slots_to, 1)
        starts = np.zeros(P + 1, dtype=np.int64)
        starts[1:] = np.cumsum(pdeg)
        slot = np.arange(len(ukey)) - starts[up]
        colsA = np.full((P, S), C, dtype=np.int32)     # sentinel = zero X tile
        colsA[up, slot] = (ukey % C).astype(np.int32)
        tiles = np.zeros(P * S * TILE, dtype=np.uint32)
        word = (up[inv] * S + slot[inv]) * TILE + rows % TILE
        np.bitwise_or.at(tiles, word,
                         np.uint32(1) << (cols % TILE).astype(np.uint32))
        # duplicate edges collapse into the same bit; count the set bits
        nnz = int(np.asarray(
            bitmap.popcount(jnp.asarray(tiles)).sum()))
        return BitELL(shape=(n, k),
                      tiles=jnp.asarray(tiles.reshape(P, S, TILE)),
                      cols=jnp.asarray(colsA), nnz=nnz)

    @staticmethod
    def from_ell(e: ELL) -> "BitELL":
        """Structural view of an ELL's stored pattern (values dropped)."""
        idx = np.asarray(e.indices)
        msk = np.asarray(e.mask)
        r, s = np.nonzero(msk)
        return BitELL.from_coo(r, idx[r, s], None, e.shape)

    @staticmethod
    def from_dense(A) -> "BitELL":
        A = np.asarray(A)
        r, c = np.nonzero(A)
        return BitELL.from_coo(r, c, None, A.shape)

    # -- gather-to-host conversions (counted, like every storage kind's) -----
    def to_coo(self):
        """Host-side COO of the stored structure (vals are unit weights)."""
        t = np.asarray(self.tiles)
        c = np.asarray(self.cols)
        p, s, r = np.nonzero(t)
        w = t[p, s, r]
        rows, cols = [], []
        for b in range(TILE):
            hit = (w >> np.uint32(b)) & 1 != 0
            rows.append(p[hit] * TILE + r[hit])
            cols.append(c[p[hit], s[hit]].astype(np.int64) * TILE + b)
        rows = np.concatenate(rows) if rows else np.zeros(0, np.int64)
        cols = np.concatenate(cols) if cols else np.zeros(0, np.int64)
        return rows.astype(np.int64), cols, np.ones(len(rows), np.float32)

    def to_ell(self) -> ELL:
        """Cached ELL materialization — the fallback target for weighted
        semirings, the element-wise family, and delta mutation (mirrors
        DeltaMatrix.materialize). Counted once: the bit-tiles leave the
        device to rebuild the padded neighbor lists."""
        if self._ell is None:
            xfer.record("bitadj_materialize")
            r, c, v = self.to_coo()
            # the first caller may sit inside a lax loop trace (e.g. a
            # weighted-semiring hop in a while_loop body); eval eagerly so
            # the cache holds concrete arrays, not leaked tracers
            with jax.ensure_compile_time_eval():
                self._ell = ELL.from_coo(r, c, v, self.shape)
        return self._ell

    def to_dense(self) -> Array:
        return self.to_ell().to_dense()

    def transpose(self) -> "BitELL":
        """Host-side rebuild from COO (grb caches the result on the handle;
        graph relations link explicitly-built twins instead)."""
        r, c, _ = self.to_coo()
        return BitELL.from_coo(c, r, None, (self.shape[1], self.shape[0]))

    def __repr__(self) -> str:
        n, k = self.shape
        return (f"BitELL {n}x{k} nnz={self._nnz} panels={self.n_panels} "
                f"slots={self.n_slots} payload={self.payload_bytes}B")


# ---------------------------------------------------------------------------
# or_and word kernels — the XLA reference (CPU + shard_map local bodies)
# ---------------------------------------------------------------------------
def _pad_query_tiles(Xw: Array, k: int) -> Array:
    """(>=k, W) packed frontier words -> (C+1, 32, W) query tiles: rows
    squared up to the column-tile grid plus one all-zero sentinel tile that
    empty slots (cols == C) gather harmlessly."""
    C = max(-(-k // TILE), 1)
    Xw = Xw[:min(Xw.shape[0], C * TILE)]
    Xw = jnp.pad(Xw, ((0, (C + 1) * TILE - Xw.shape[0]), (0, 0)))
    return Xw.reshape(C + 1, TILE, Xw.shape[1])


def panels_mxm_words(tiles: Array, cols: Array, Xw: Array, k: int,
                     slot_chunk: int = 8) -> Array:
    """Yw[p*32+r] = OR over slots s and bits b with tiles[p,s,r] bit b set
    of Xw[cols[p,s]*32 + b] — the or_and matmul on bit-tiles against a
    packed frontier, word-AND + OR all the way (no float intermediates).
    Slot chunking bounds the (P, sc, 32, 32, W) bit-spread intermediate.
    This is the XLA reference for ``kernels.bitadj_mxv.bitadj_mxv_packed``
    and the shard-local body of ``distr.graph2d.bit_mxm_2d``."""
    Pn, Sn, _ = tiles.shape
    W = Xw.shape[1]
    Xt = _pad_query_tiles(Xw, k)                       # (C+1, 32, W)
    shifts = jnp.arange(TILE, dtype=jnp.uint32)
    acc = jnp.zeros((Pn, TILE, W), dtype=jnp.uint32)
    for s0 in range(0, Sn, slot_chunk):
        tc = tiles[:, s0:s0 + slot_chunk]              # (P, sc, 32)
        cc = cols[:, s0:s0 + slot_chunk]               # (P, sc)
        G = Xt[cc]                                     # (P, sc, 32, W)
        bits = jnp.bitwise_and(
            jnp.right_shift(tc[:, :, :, None], shifts), jnp.uint32(1))
        term = jnp.where(bits[..., None] != 0,         # (P, sc, 32r, 32b, W)
                         G[:, :, None, :, :], jnp.uint32(0))
        acc = jnp.bitwise_or(
            acc, jax.lax.reduce(term, jnp.uint32(0),
                                jax.lax.bitwise_or, (1, 3)))
    return acc.reshape(Pn * TILE, W)


def mxm_words(b: BitELL, Xw: Array) -> Array:
    """(k-rows, W) packed frontier words -> (n, W) result words."""
    return panels_mxm_words(b.tiles, b.cols, Xw, b.shape[1])[:b.shape[0]]


def reduce_stored(s, monoid, axis) -> Array:
    """plus/or reduction over the stored structure, straight off the
    bit-tiles (SWAR popcounts — never materializes). Works unchanged on
    ShardedBitELL's global arrays: GSPMD inserts the mesh collectives."""
    tiles, cols = s.tiles, s.cols
    n, k = s.shape
    C = -(-k // TILE)
    if axis == 1:
        per = jnp.sum(bitmap.popcount(tiles), axis=1)  # (P, 32) row counts
        out = per.reshape(-1)[:n].astype(jnp.float32)
    elif axis == 0:
        shifts = jnp.arange(TILE, dtype=jnp.uint32)
        bits = jnp.bitwise_and(
            jnp.right_shift(tiles[:, :, :, None], shifts), jnp.uint32(1))
        per = jnp.sum(bits, axis=2).astype(jnp.float32)   # (P, S, 32b)
        seg = jax.ops.segment_sum(per.reshape(-1, TILE),
                                  cols.reshape(-1).astype(jnp.int32),
                                  num_segments=C + 1)     # sentinel bucket
        out = seg[:C].reshape(-1)[:k]
    else:
        tot = jnp.sum(bitmap.popcount(tiles)).astype(jnp.float32)
        return (tot > 0).astype(jnp.float32) if monoid.name == "or" else tot
    return (out > 0).astype(jnp.float32) if monoid.name == "or" else out


def triangle_count(s, slot_chunk: int = 4) -> Array:
    """Triangles of a symmetric structural adjacency as AND + popcount over
    tile pairs: for every stored edge bit (i, j), the common-neighbor count
    is the popcount of ``rowbits[i] & rowbits[j]`` summed over column
    tiles; the masked plus_pair matmul the float route runs is exactly that
    intersection, so the total divides by 6 identically. Stays on device
    (and mesh-resident under GSPMD for ShardedBitELL arrays)."""
    tiles, cols = s.tiles, s.cols
    n, k = s.shape
    if n != k:
        raise ValueError("triangle_count needs a square adjacency")
    Pn, Sn, _ = tiles.shape
    C = -(-k // TILE)
    # row-bit matrix: Brows[p, r, c] = 32 column bits of row p*32+r, tile c
    ids = (jnp.arange(Pn, dtype=jnp.int32)[:, None] * (C + 1)
           + cols).reshape(-1)
    seg = jax.ops.segment_sum(tiles.reshape(-1, TILE).astype(jnp.uint32),
                              ids, num_segments=Pn * (C + 1))
    Brows = seg.reshape(Pn, C + 1, TILE)[:, :C].transpose(0, 2, 1)
    # neighbor-row panels gather via the slot's column tile (square: column
    # tile c == row panel c); sentinel slots hit an all-zero panel
    Bpad = jnp.concatenate(
        [Brows, jnp.zeros((max(C + 1 - Pn, 1), TILE, C), jnp.uint32)])
    shifts = jnp.arange(TILE, dtype=jnp.uint32)
    acc = jnp.float32(0.0)
    for s0 in range(0, Sn, slot_chunk):
        tc = tiles[:, s0:s0 + slot_chunk]              # (P, sc, 32)
        cc = cols[:, s0:s0 + slot_chunk]               # (P, sc)
        G = Bpad[cc]                                   # (P, sc, 32b, C)
        inter = bitmap.popcount(
            Brows[:, None, :, None, :] & G[:, :, None, :, :])
        inter = jnp.sum(inter, axis=-1).astype(jnp.float32)  # (P,sc,32r,32b)
        bits = jnp.bitwise_and(
            jnp.right_shift(tc[:, :, :, None], shifts), jnp.uint32(1))
        acc = acc + jnp.sum(inter * bits.astype(jnp.float32))
    return acc / 6.0


# ---------------------------------------------------------------------------
# ShardedBitELL — the mesh twin behind grb.distribute
# ---------------------------------------------------------------------------
class ShardedBitELL:
    """BitELL panels sharded over the mesh's "data" axis (see module doc).

    tiles/cols are global device arrays placed with NamedSharding; P_pad
    rounds the panel count up to a multiple of the "data" axis, the extra
    panels all-sentinel. Built by :meth:`from_bitell` (grb.distribute);
    transpose_a is always served from the linked twin grb.distribute builds
    — there is no transposed bit-scatter lowering."""
    __slots__ = ("shape", "mesh", "tiles", "cols", "nnz", "p_pad", "_ell2d")

    def __init__(self, shape, mesh, tiles, cols, nnz):
        from repro.core import shard as _shard
        self.shape = tuple(shape)
        self.mesh = _shard._check_mesh(mesh)
        self.tiles = tiles
        self.cols = cols
        self.nnz = int(nnz)
        self.p_pad = int(tiles.shape[0])
        self._ell2d = None          # cached ShardedELL materialization

    @classmethod
    def from_bitell(cls, b: BitELL, mesh) -> "ShardedBitELL":
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import shard as _shard
        _shard._check_mesh(mesh)
        dsz = mesh.shape[_shard.ROW_AXIS]
        Pn, Sn, _ = b.tiles.shape
        p_pad = Pn + (-Pn) % dsz
        t = np.zeros((p_pad, Sn, TILE), np.uint32)
        c = np.full((p_pad, Sn), b.n_ctiles, np.int32)
        t[:Pn] = np.asarray(b.tiles)
        c[:Pn] = np.asarray(b.cols)
        return cls(b.shape, mesh,
                   jax.device_put(jnp.asarray(t),
                                  NamedSharding(mesh,
                                                P(_shard.ROW_AXIS,
                                                  None, None))),
                   jax.device_put(jnp.asarray(c),
                                  NamedSharding(mesh,
                                                P(_shard.ROW_AXIS, None))),
                   nnz=b.nnz)

    # -- mesh geometry -------------------------------------------------------
    @property
    def data_size(self) -> int:
        from repro.core import shard as _shard
        return self.mesh.shape[_shard.ROW_AXIS]

    @property
    def frontier_size(self) -> int:
        from repro.core import shard as _shard
        return int(np.prod([self.mesh.shape[a]
                            for a in _shard.frontier_axes(self.mesh)] or [1]))

    @property
    def n_ctiles(self) -> int:
        return -(-self.shape[1] // TILE)

    @property
    def payload_bytes(self) -> int:
        return int(self.tiles.size) * 4 + int(self.cols.size) * 4

    # -- gather-to-host conversions (counted) --------------------------------
    def to_bitell(self) -> BitELL:
        """Gather the panel shards back to one host-side BitELL (drops
        padding panels). Counted like ShardedELL.to_ell."""
        xfer.record("bitadj_gather")
        Pn = -(-self.shape[0] // TILE)
        return BitELL(shape=self.shape,
                      tiles=jnp.asarray(np.asarray(self.tiles)[:Pn]),
                      cols=jnp.asarray(np.asarray(self.cols)[:Pn]),
                      nnz=self.nnz)

    def to_ell(self) -> ELL:
        return self.to_bitell().to_ell()

    def to_dense(self) -> Array:
        return self.to_ell().to_dense()

    def to_coo(self):
        return self.to_bitell().to_coo()

    def transpose(self) -> "ShardedBitELL":
        return ShardedBitELL.from_bitell(self.to_bitell().transpose(),
                                         self.mesh)

    def materialize_sharded(self):
        """Cached ShardedELL on the same mesh — the sharded fallback target
        for weighted semirings / ewise / assign-extract (one counted gather
        to rebuild neighbor lists, then mesh-resident again; the sharded
        analog of BitELL.to_ell)."""
        from repro.core.shard import ShardedELL
        if self._ell2d is None:
            self._ell2d = ShardedELL.from_ell(self.to_ell(), self.mesh)
        return self._ell2d

    def __repr__(self) -> str:
        n, k = self.shape
        axes = "x".join(f"{a}:{self.mesh.shape[a]}"
                        for a in self.mesh.axis_names)
        return (f"ShardedBitELL {n}x{k} mesh=({axes}) nnz={self.nnz} "
                f"slots={self.cols.shape[1]}")


def sharded_mxm_words(s: ShardedBitELL, Xw: Array) -> Array:
    """Row-form or_and mxm on the mesh with a packed frontier: one packed
    all-gather of Xw over "data" per call (the >= 8x payload cut the HLO
    regression pins), then the shard-local word kernel on each panel block.
    Words in, words out — what grb.mxm_words dispatches to."""
    from repro.distr import graph2d
    n, k = s.shape
    r_pad = (-k) % s.data_size
    w_pad = (-Xw.shape[1]) % s.frontier_size
    Xp = jnp.pad(Xw, ((0, r_pad), (0, w_pad))) if (r_pad or w_pad) else Xw
    fn = graph2d.bit_mxm_2d(s.mesh, s.cols.shape[1], k)
    Y = fn(s.tiles, s.cols, Xp)
    return Y[:n, :Xw.shape[1]]
