"""Distributed lowerings for the 2D-sharded ELL layout (explicit collectives).

Layout (DESIGN.md §5):
  * adjacency rows (ELL indices/mask)  -> "data" axis (within a pod, the
    graph is row-partitioned; pods replicate the graph),
  * frontier/query columns F           -> ("pod", "model") — queries scale
    out across pods, the paper's threadpool claim at pod scale,
  * between hops, each data-shard owns the new frontier rows it produced;
    an all-gather over "data" rebuilds the full frontier for the next
    gather step (the explicit collective the roofline reads).

Two kinds of exports:

  * **Reusable op lowerings** — :func:`mxm_2d` and :func:`reduce_2d` are the
    shard_map bodies `grb` dispatches to when a GBMatrix holds ShardedELL
    storage (core.shard). Row form: one frontier all-gather over "data" +
    local ELL gather-reduce; with `packed=True` (or_and, set by grb's
    bitmap policy) both sides of the collective carry `core.bitmap` uint32
    words — 32x less wire payload. Transposed form (`A^T (x) x` with no
    stored transpose): local scatter-accumulate + a psum_scatter of row
    blocks (pmin/pmax for the tropical semirings; summable nibble words,
    8x less payload, when packed). Engine / query / algorithm layers never
    call these directly — they go through `grb`.
  * **Dry-run probes** — :func:`khop_counts_2d` (with the bitmap-packed and
    sentinel perf variants, packing via the same public `core.bitmap`
    route the ops use) and :func:`pagerank_2d` keep whole-algorithm
    loops fused in one shard_map so `launch.dryrun` can compile a single
    cell and read its collective bytes off the HLO. They are lowering-
    analysis tools, not an algorithm surface: the engine runs the same
    algorithms through `grb` ops on sharded handles.

Public contract: every callable here is mesh-resident and collective-
explicit — nothing gathers to host (the gather-to-host fallbacks live in
`grb`). Inputs must arrive pre-padded to the mesh (core.shard owns that);
mis-padded `out_rows` or a packed call on a non-indicator semiring raise
ValueError / NotImplementedError at trace time. The packed transposed
form's nibble-lane compression is valid only up to
`bitmap.NIBBLE_MAX_SHARDS` row shards; wider data axes are detected at
build time here and served by an unpacked-psum_scatter body with the same
word-in/word-out signature (see mxm_2d). shard_map
keeps the collectives explicit — `lowered.as_text()` shows exactly one
all-gather per hop plus the final reduce, which is what the payload
regression in tests/test_bitmap.py pins.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import ops as _core_ops
from repro.core import semiring as S
from repro.core.ell import ELL
# single source of truth for the frontier-axis convention (F over pod x
# model) — shared with the ShardedELL storage this module lowers for
from repro.core.shard import frontier_axes as _frontier_axes
from repro.core.shard import frontier_spec as _fr_spec

def _smap(body, mesh, in_specs, out_specs):
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _ell_words(local: ELL, xw):
    """Shard-local packed gather-reduce: on a TPU the one-device route's
    Pallas kernel (`kernels.bitmap_mxv`), whose working set is the edges;
    elsewhere the XLA reference, whose (rows, deg, W) gather would not fit
    a chip at deployment widths."""
    if jax.default_backend() == "tpu":
        from repro.kernels import bitmap_mxv     # lazy: kernels import core
        return bitmap_mxv.ell_mxv_packed(local, xw)
    return _core_ops.ell_mxm_packed(local, xw)


def ell_shard_inputs(A, sentinel: bool = False):
    """Host (indices, mask) arrays for the row-sharded ELL layout.

    Accepts the `grb` surface's handles — a Relation, a GBMatrix, or raw ELL
    storage. Every kernel in this module pulls (rows of A^T), so a Relation
    resolves to its stored transpose; pass a GBMatrix (`rel.A` / `rel.A_T`)
    explicitly to pick a direction yourself. With sentinel=True, padded
    slots index the dedicated all-zero row (id = shape[1]) instead of
    carrying the mask.
    """
    if hasattr(A, "A") and hasattr(A, "name"):   # Relation -> pull layout
        A = A.A_T
    store = getattr(A, "store", A)               # GBMatrix -> storage
    if not hasattr(store, "indices"):
        raise TypeError(f"2D sharding needs ELL rows, got {type(store).__name__}")
    idx = np.asarray(store.indices)
    msk = np.asarray(store.mask)
    if sentinel:
        idx = np.where(msk, idx, store.shape[1]).astype(np.int32)
    return idx, msk


# ---------------------------------------------------------------------------
# reusable op lowerings — what grb dispatches sharded GBMatrix ops to
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def mxm_2d(mesh: Mesh, sr: S.Semiring, transposed: bool = False,
           out_rows: int = 0, packed: bool = False):
    """One semiring matmul over the mesh: (idx, msk, val, x) -> y.

    Row form (transposed=False): y = A (x) x. idx/msk/val are A's row-padded
    ELL arrays "data"-sharded; x is the (col_pad, F_pad) frontier, rows over
    "data", F over pod x model. One all-gather of x over "data", then each
    shard runs the local ELL gather-reduce (core.ops.ell_mxm) on its rows.

    Transposed form (transposed=True): y = A^T (x) x *without a stored
    transpose* — x rides A's row shards, each shard scatter-accumulates its
    edges' contributions over all `out_rows` output rows (A's column count,
    row-padded), and a psum_scatter over "data" hands every shard its own
    output row block (pmin/pmax + local slice for the tropical add monoids,
    which have no scatter-reduce collective).

    packed=True (or_and only — `core.shard.mxm` sets it from grb's bitmap
    policy): x and y are core.bitmap uint32 word arrays, (rows, W) with W
    sharded where F was. Row form all-gathers the *words* — 32x less wire
    payload per hop — and ORs them through the packed gather-reduce.
    Transposed form still sums: the local partial bits are re-packed into
    summable nibble words (8 lanes/word, 4 bits each) so one psum_scatter
    carries an 8x-smaller payload without bit carries. Nibble lanes
    saturate at 15, so with more than `bitmap.NIBBLE_MAX_SHARDS` row
    shards a 16th shard's contribution would carry into the next lane —
    detected here at build time and served by the unpacked psum_scatter
    body instead (full float partials on the wire, identical word-in/
    word-out signature, bit-identical results).

    The jitted callable is lru-cached per (mesh, semiring, direction,
    packing) — repeated hops recompile only on new operand shapes.
    """
    fr = _fr_spec(mesh)
    dsz = mesh.shape["data"]
    if packed and sr.mode != "dot_indicator":
        raise NotImplementedError(
            f"packed mxm_2d is or_and/any_pair only (mode dot_indicator); "
            f"got {sr.mode}")

    if not transposed and packed:
        def body(idx_l, msk_l, val_l, xw_l):
            xw = jax.lax.all_gather(xw_l, "data", axis=0, tiled=True)
            local = ELL(shape=(idx_l.shape[0], xw.shape[0]), indices=idx_l,
                        mask=msk_l, values=val_l, nnz=0)
            return _ell_words(local, xw)
    elif not transposed:
        def body(idx_l, msk_l, val_l, x_l):
            x = jax.lax.all_gather(x_l, "data", axis=0, tiled=True)
            local = ELL(shape=(idx_l.shape[0], x.shape[0]), indices=idx_l,
                        mask=msk_l, values=val_l, nnz=0)
            return _core_ops.ell_mxm(local, x, sr)
    elif packed:
        from repro.core import bitmap
        if out_rows <= 0 or out_rows % dsz:
            raise ValueError(f"transposed mxm_2d needs out_rows padded to "
                             f"the data axis ({dsz}); got {out_rows}")
        # Nibble lanes sum carry-free only while every shard contributes at
        # most 1 to a 4-bit lane: dsz shards can reach dsz <= 15. Past
        # NIBBLE_MAX_SHARDS the compression is wrong, not just slow —
        # detect at build time (dsz is mesh geometry, static) and keep the
        # word-in/word-out contract via full float partials on the wire.
        nibble_ok = dsz <= bitmap.NIBBLE_MAX_SHARDS

        def body(idx_l, msk_l, val_l, xw_l):
            # edge (i -> j) at local row i ORs x's words at row i into
            # output row j. The cross-shard combine has to ride an add
            # collective, so: expand local words -> per-bit partial counts
            # -> saturate to bits -> nibble-pack -> psum_scatter -> saturate.
            fl = xw_l.shape[1] * bitmap.WORD_BITS
            bits = bitmap.unpack(xw_l, fl)             # (rows_l, fl)
            term = jnp.where(msk_l[:, :, None], bits[:, None, :], 0.0)
            ids = jnp.where(msk_l, idx_l, out_rows).reshape(-1)
            part = jax.ops.segment_sum(term.reshape(-1, fl), ids,
                                       num_segments=out_rows + 1)[:out_rows]
            if nibble_ok:
                nib = bitmap.pack_nibbles(part > 0)    # (out_rows, fl/8)
                tot = jax.lax.psum_scatter(nib, "data", scatter_dimension=0,
                                           tiled=True)
                own = bitmap.unpack_nibbles(tot, fl)   # (out_rows/dsz, fl)
            else:
                # unpacked psum_scatter fallback: float partial counts on
                # the wire (no lane limit), saturate to bits after
                own = jax.lax.psum_scatter(part, "data",
                                           scatter_dimension=0, tiled=True)
                own = (own > 0).astype(jnp.float32)
            return bitmap.pack(own)
    else:
        if out_rows <= 0 or out_rows % dsz:
            raise ValueError(f"transposed mxm_2d needs out_rows padded to "
                             f"the data axis ({dsz}); got {out_rows}")

        def body(idx_l, msk_l, val_l, x_l):
            # edge (i -> j) stored at local row i contributes mul(w_ij, x_i)
            # to output row j; segment-accumulate locally over all out_rows,
            # then combine across shards.
            w = val_l[:, :, None]
            m = msk_l[:, :, None]
            xg = x_l[:, None, :]                       # (rows_l, 1, F_l)
            ident = np.float32(sr.identity)
            if sr.mode == "dot":
                term = jnp.where(m, w * xg, 0.0)
            elif sr.mode in ("dot_indicator", "dot_pair"):
                term = jnp.where(m & (xg != 0), 1.0, 0.0)
            elif sr.mode == "dot_first":
                term = jnp.where(m & (xg != 0), w, 0.0)
            elif sr.mode == "bcast":
                term = jnp.where(m, sr.mul(w, xg), ident)
            else:
                raise NotImplementedError(sr.mode)
            flat = term.reshape(-1, term.shape[-1])
            ids = jnp.where(msk_l, idx_l, out_rows).reshape(-1)
            if sr.mode == "bcast":                     # min/max add monoid
                seg = (jax.ops.segment_min if sr.add.name == "min"
                       else jax.ops.segment_max)
                part = seg(flat, ids, num_segments=out_rows + 1)[:out_rows]
                full = (jax.lax.pmin if sr.add.name == "min"
                        else jax.lax.pmax)(part, "data")
                k = jax.lax.axis_index("data")
                return jax.lax.dynamic_slice_in_dim(
                    full, k * (out_rows // dsz), out_rows // dsz)
            part = jax.ops.segment_sum(flat, ids,
                                       num_segments=out_rows + 1)[:out_rows]
            y = jax.lax.psum_scatter(part, "data", scatter_dimension=0,
                                     tiled=True)
            if sr.mode == "dot_indicator":
                y = (y > 0).astype(jnp.float32)
            return y

    return jax.jit(_smap(
        body, mesh,
        in_specs=(P("data", None),) * 3 + (P("data", fr),),
        out_specs=P("data", fr)))


@functools.lru_cache(maxsize=None)
def bit_mxm_2d(mesh: Mesh, slots: int, k: int):
    """or_and matmul on ShardedBitELL panels: (tiles, cols, xw) -> yw.

    The fully bit-level row form — both the *adjacency* (core.bitadj
    32x32-edge uint32 tiles, panels "data"-sharded) and the *frontier*
    (core.bitmap words, rows over "data", words over pod x model) are
    packed, so the per-hop all-gather over "data" carries uint32 frontier
    words (32x less wire than the float route — the >= 8x all-gather
    payload cut tests/test_bitadj.py pins off the HLO) and the local
    gather-reduce is `core.bitadj.panels_mxm_words`: word-AND + OR, zero
    float intermediates. `k` is A's logical column count (frontier rows;
    gathered padding rows beyond the column-tile grid are zero and
    sliced off by the query-tile squaring). Output is (p_pad*32, W) words,
    rows "data"-sharded; `core.shard`-side padding rows are all-sentinel
    panels and render zero. lru-cached per (mesh, slot width, k) like
    every lowering factory here.
    """
    from repro.core import bitadj
    fr = _fr_spec(mesh)

    def body(tiles_l, cols_l, xw_l):
        xw = jax.lax.all_gather(xw_l, "data", axis=0, tiled=True)
        return bitadj.panels_mxm_words(tiles_l, cols_l, xw, k)

    del slots      # cache key only: slot width changes the traced shapes
    return jax.jit(_smap(
        body, mesh,
        in_specs=(P("data", None, None), P("data", None), P("data", fr)),
        out_specs=P("data", fr)))


@functools.lru_cache(maxsize=None)
def reduce_2d(mesh: Mesh, monoid_name: str, axis, ncols: int):
    """Stored-entry plus/or reduction over the mesh: (idx, msk, val) -> out.

    axis=1 (per row) is collective-free — rows live whole on one shard; the
    full (axis=None) and per-column (axis=0) reductions psum partials over
    "data" and return a replicated result. "or" reduces indicator counts and
    renders any-stored (> 0), matching grb.reduce's sparse contract.
    """
    if monoid_name not in ("plus", "or"):
        raise NotImplementedError(monoid_name)

    def body(idx_l, msk_l, val_l):
        w = val_l * msk_l.astype(jnp.float32)
        if monoid_name == "or":
            w = (w != 0).astype(jnp.float32)
        if axis == 1:
            out = jnp.sum(w, axis=1)
        elif axis is None:
            out = jax.lax.psum(jnp.sum(w), "data")
        else:                                          # axis == 0
            ids = jnp.where(msk_l, idx_l, ncols).reshape(-1)
            part = jax.ops.segment_sum(w.reshape(-1), ids,
                                       num_segments=ncols + 1)[:ncols]
            out = jax.lax.psum(part, "data")
        if monoid_name == "or":
            out = (out > 0).astype(jnp.float32)
        return out

    return jax.jit(_smap(body, mesh, in_specs=(P("data", None),) * 3,
                         out_specs=P("data") if axis == 1 else P()))


# ---------------------------------------------------------------------------
# shard-local element-wise lowerings — the slot-aligned COO set algebra grb's
# sharded ewise/assign/extract dispatch to (no collectives: rows live whole
# on one shard, so union/intersect/mask surgery is embarrassingly row-local)
# ---------------------------------------------------------------------------
# sentinel sort key for invalid slots; real keys are col*2 + source, so this
# is unreachable for any column count below ~2^30 (document, don't check:
# the int32 ELL index arrays cap columns well before that).
_MERGE_SENT = np.int32(np.iinfo(np.int32).max)


def _ewise_merge(ia, ma, va, ib, mb, vb, mode, op):
    """Row-local merge of two ELL row blocks into one (idx, mask, val) block.

    The *slot-alignment pass*: concatenate the two slot layouts (static width
    wa+wb), sort each row by (column, source) — source breaks ties so an A
    entry always immediately precedes its B partner at the same column — and
    pair adjacent equal columns. Each side stores at most one entry per
    (row, col) (the ELL invariant), so runs of equal columns have length <= 2
    and one shifted compare finds every pair.

    mode: "union"     op(a,b) where both, pass-through singletons (eWiseAdd)
          "intersect" op(a,b) where both, singletons dropped     (eWiseMult)
          "mask"      A entries where B stored (mask restrict)
          "mask_c"    A entries where B absent (complemented restrict)

    Zero results are dropped (stored == nonzero, the repo-wide convention).
    Pure row-local jnp — callers run it under shard_map (ewise_2d) or on
    plain host arrays (the differential oracle in tests does exactly that).
    """
    rows, wa = ia.shape
    col = jnp.concatenate([ia, ib], axis=1).astype(jnp.int32)
    src = jnp.concatenate(
        [jnp.zeros((rows, wa), jnp.int32),
         jnp.ones((rows, ib.shape[1]), jnp.int32)], axis=1)
    valid_in = jnp.concatenate([ma, mb], axis=1)
    val = jnp.concatenate([va, vb], axis=1).astype(jnp.float32)
    key = jnp.where(valid_in, col * 2 + src, _MERGE_SENT)
    key, col, src, val = jax.lax.sort((key, col, src, val),
                                      dimension=1, num_keys=1)
    valid = key != _MERGE_SENT
    same = valid[:, :-1] & valid[:, 1:] & (col[:, :-1] == col[:, 1:])
    pair_first = jnp.pad(same, ((0, 0), (0, 1)))     # slot i pairs with i+1
    pair_second = jnp.pad(same, ((0, 0), (1, 0)))
    val_nxt = jnp.pad(val[:, 1:], ((0, 0), (0, 1)))
    if mode == "union":
        out_val = jnp.where(pair_first, op(val, val_nxt), val)
        out_ok = valid & ~pair_second
    elif mode == "intersect":
        out_val = op(val, val_nxt)
        out_ok = pair_first
    elif mode == "mask":
        out_val = val
        out_ok = pair_first                           # slot i is the A entry
    elif mode == "mask_c":
        out_val = val
        out_ok = valid & (src == 0) & ~pair_first
    else:
        raise ValueError(f"unknown merge mode {mode!r}")
    out_ok = out_ok & (out_val != 0)
    return (jnp.where(out_ok, col, 0),
            out_ok,
            jnp.where(out_ok, out_val, 0.0))


@functools.lru_cache(maxsize=None)
def ewise_2d(mesh: Mesh, mode: str, op):
    """Shard-local element-wise merge over the mesh:
    (ia, ma, va, ib, mb, vb) -> (idx, mask, val), all (n_pad, w) row blocks
    "data"-sharded. No collectives — the shard_map is here so the lowering
    is structurally mesh-resident (scan_host_transfers proves it empty).

    lru-cached per (mesh, mode, op); monoid ops are module-level singletons
    so algorithm loops hit the cache, ad-hoc lambdas retrace per identity.
    """
    def body(ia, ma, va, ib, mb, vb):
        return _ewise_merge(ia, ma, va, ib, mb, vb, mode, op)

    return jax.jit(_smap(body, mesh, in_specs=(P("data", None),) * 6,
                         out_specs=(P("data", None),) * 3))


@functools.lru_cache(maxsize=None)
def restrict_dense_2d(mesh: Mesh, complement: bool):
    """Keep stored entries where a *dense* (n_pad, m) mask row block is
    nonzero (or zero, complemented) — one shard-local take_along_axis, the
    dense-mask side of the descriptor blend."""
    def body(idx_l, msk_l, val_l, dm_l):
        keep = jnp.take_along_axis(dm_l != 0, idx_l, axis=1)
        if complement:
            keep = ~keep
        m = msk_l & keep
        return (jnp.where(m, idx_l, 0), m,
                jnp.where(m, val_l, 0.0))

    return jax.jit(_smap(body, mesh,
                         in_specs=(P("data", None),) * 4,
                         out_specs=(P("data", None),) * 3))


@functools.lru_cache(maxsize=None)
def extract_cols_2d(mesh: Mesh):
    """Column-subset extract: relabel stored columns through a replicated
    (m,) LUT (new column id, or -1 to drop). Row-local — extracting columns
    never crosses row shards; row subsets do, and stay on the counted
    gather fallback in grb."""
    def body(idx_l, msk_l, val_l, lut):
        nc = lut[idx_l]
        m = msk_l & (nc >= 0)
        return (jnp.where(m, nc, 0).astype(jnp.int32), m,
                jnp.where(m, val_l, 0.0))

    return jax.jit(_smap(body, mesh,
                         in_specs=(P("data", None),) * 3 + (P(None),),
                         out_specs=(P("data", None),) * 3))


@functools.lru_cache(maxsize=None)
def reduce_minmax_2d(mesh: Mesh, monoid_name: str, axis, nrows: int,
                     ncols: int):
    """min/max reduction with *dense* semantics on the mesh: absent entries
    render as 0 and participate (grb.reduce's contract for non-plus/or
    monoids). Stored entries reduce under a +/-inf identity; one stored-count
    compare folds the implicit zeros back in. axis=1 is collective-free;
    axis=0/None combine shards with pmin/pmax + a psum of stored counts.

    nrows/ncols are the *logical* shape — padded rows are all mask-false and
    only ever contribute the identity."""
    if monoid_name not in ("min", "max"):
        raise NotImplementedError(monoid_name)
    big = np.float32(np.inf if monoid_name == "min" else -np.inf)
    comb = jnp.minimum if monoid_name == "min" else jnp.maximum
    seg = (jax.ops.segment_min if monoid_name == "min"
           else jax.ops.segment_max)
    pcomb = jax.lax.pmin if monoid_name == "min" else jax.lax.pmax

    def body(idx_l, msk_l, val_l):
        w = jnp.where(msk_l, val_l, big)
        if axis == 1:
            stored = (jnp.min if monoid_name == "min" else jnp.max)(w, axis=1)
            absent = jnp.sum(msk_l, axis=1) < ncols
            return jnp.where(absent, comb(stored, 0.0), stored)
        if axis is None:
            stored = pcomb(
                (jnp.min if monoid_name == "min" else jnp.max)(w), "data")
            total = jax.lax.psum(jnp.sum(msk_l.astype(jnp.int32)), "data")
            return jnp.where(total < nrows * ncols, comb(stored, 0.0), stored)
        ids = jnp.where(msk_l, idx_l, ncols).reshape(-1)
        part = seg(w.reshape(-1), ids, num_segments=ncols + 1)[:ncols]
        stored = pcomb(part, "data")
        cnt = jax.lax.psum(
            jax.ops.segment_sum(msk_l.astype(jnp.int32).reshape(-1), ids,
                                num_segments=ncols + 1)[:ncols], "data")
        return jnp.where(cnt < nrows, comb(stored, 0.0), stored)

    return jax.jit(_smap(body, mesh, in_specs=(P("data", None),) * 3,
                         out_specs=P("data") if axis == 1 else P()))


# ---------------------------------------------------------------------------
# transfer-count inspection — the HLO side of the host_transfers() regression
# ---------------------------------------------------------------------------
# Lowered-text markers that indicate a device->host hop. Pure mesh-resident
# programs (every lowering above) contain none of them.
_TRANSFER_TOKENS = ("infeed", "outfeed", "is_host_transfer=true",
                    "cpu_callback", "host_callback",
                    "annotate_device_placement")


def scan_host_transfers(fn, *args, **kwargs):
    """Lower ``fn(*args, **kwargs)`` and return every StableHLO/HLO line that
    marks a device->host transfer (infeed/outfeed/host callbacks/placement
    annotations). An empty list certifies the traced program is
    device-resident end to end — the structural half of the
    ``grb.host_transfers()`` regression (the counter pins the Python-level
    gathers the tracer can't see)."""
    lowered = jax.jit(fn).lower(*args, **kwargs)
    texts = [lowered.as_text(), lowered.compile().as_text()]
    hits = []
    for txt in texts:
        for ln in txt.splitlines():
            low = ln.lower()
            if any(tok in low for tok in _TRANSFER_TOKENS):
                hits.append(ln.strip())
    return hits


# ---------------------------------------------------------------------------
# dry-run probes — fused whole-algorithm loops for lowering/roofline analysis
# ---------------------------------------------------------------------------
def khop_counts_2d(mesh: Mesh, n: int, k: int, packed: bool = False,
                   sentinel: bool = False):
    """Returns a function (indices, mask, frontier0) -> counts (F,).

    indices/mask: (N, max_deg) ELL rows (row-sharded over "data");
    frontier0:    (N, F) one-hot seeds (int8; F sharded over pod+model).

    Dry-run probe: `launch.dryrun` compiles this fused k-hop cell to read
    collective bytes / roofline terms off one HLO module. The engine runs
    k-hop through `grb.mxm` on a sharded handle instead (same collectives,
    one shard_map per hop).

    packed=True — GraphBLAS *bitmap format* on the query axis via the public
    packed-frontier route (`core.bitmap`, 32 queries per uint32 word): the
    or_and semiring over {0,1} is bitwise, so the per-hop frontier
    all-gather and the neighbor gathers move 32x fewer bytes (§Perf GE-1).
    This is the same word layout `grb.mxm` uses automatically for wide
    or_and frontiers; the probe only exists to keep the whole loop in one
    shard_map for HLO collective accounting.

    sentinel=True — padded slots point at a dedicated all-zero row (index n)
    instead of carrying a validity mask: the mask array and its `where` op
    disappear from the hop loop (§Perf GE-2). The mask input is ignored.
    """
    fr_axes = _frontier_axes(mesh)

    from repro.core import bitmap

    def body(idx_l, msk_l, seed_l):
        # seed_l: (N/data, F_l) this shard's rows of the one-hot frontier
        if packed:
            frontier = bitmap.pack(seed_l)    # (rows, ceil(F_l/32)) uint32
        else:
            frontier = seed_l
        visited = frontier

        for _ in range(k):
            x_full = jax.lax.all_gather(frontier, "data", axis=0, tiled=True)
            if sentinel:
                # padded slots index row n: append one zero row, skip masking
                x_full = jnp.concatenate(
                    [x_full, jnp.zeros((1,) + x_full.shape[1:], x_full.dtype)],
                    axis=0)
            gathered = x_full[idx_l]                      # (rows, deg, F')
            if packed:
                if not sentinel:
                    gathered = jnp.where(msk_l[..., None], gathered,
                                         jnp.uint32(0))
                nxt = jax.lax.reduce(
                    gathered, jnp.uint32(0), jax.lax.bitwise_or, (1,))
                nxt = bitmap.word_andnot(nxt, visited)
                visited = bitmap.word_or(visited, nxt)
            else:
                if not sentinel:
                    gathered = jnp.where(msk_l[..., None], gathered, 0)
                nxt = gathered.max(axis=1)
                nxt = jnp.where(visited > 0, 0, nxt).astype(jnp.int8)
                visited = jnp.maximum(visited, nxt)
            frontier = nxt

        if packed:
            # unpack once at the end: reached count per query column
            count = bitmap.reduce_or_columns(
                visited, seed_l.shape[1]).astype(jnp.int32)
        else:
            count = visited.astype(jnp.int32).sum(axis=0)
        # rows are sharded over "data": total count sums across row shards
        count = jax.lax.psum(count, "data") - 1           # exclude the seed
        return count

    fr_spec = P("data", fr_axes if len(fr_axes) > 1 else (fr_axes[0] if fr_axes else None))
    out_spec = P(fr_axes if len(fr_axes) > 1 else (fr_axes[0] if fr_axes else None))
    return _smap(body, mesh,
                 in_specs=(P("data", None), P("data", None), fr_spec),
                 out_specs=out_spec)


def pagerank_2d(mesh: Mesh, n: int, iters: int, alpha: float = 0.85,
                push_dtype=None):
    """Dry-run probe: fused distributed PageRank (plus_times) on the
    row-sharded layout — per iteration one frontier all-gather over "data" +
    local gather-reduce + dangling-mass psum. Returns fn(indices, mask,
    out_deg); input geometry comes from :func:`pagerank_specs_2d`.

    The engine runs PageRank through `grb.mxv` on a sharded handle instead;
    this probe keeps the whole loop in one shard_map so dryrun reads its
    collective bytes off one HLO module.

    indices/mask: (N, max_deg) rows of A^T (in-neighbors), "data"-sharded;
    out_deg: (N,) f32, "data"-sharded. Result: ranks (N,) "data"-sharded.

    push_dtype=bf16 (§Perf GE-4): the all-gathered push vector is the
    collective payload; ranks sum in f32 locally, so bf16 on the wire halves
    collective bytes at ~3 decimal digits of rank precision.
    """

    def body(idx_l, msk_l, deg_l):
        rows = idx_l.shape[0]
        r_l = jnp.full((rows,), 1.0 / n, jnp.float32)
        inv_deg_l = jnp.where(deg_l > 0, 1.0 / jnp.maximum(deg_l, 1e-30), 0.0)
        dangling_l = deg_l == 0

        for _ in range(iters):
            push_l = r_l * inv_deg_l
            if push_dtype is not None:
                push_l = push_l.astype(push_dtype)
            push = jax.lax.all_gather(push_l, "data", axis=0, tiled=True)
            # convert only inside the reduce (f32 accumulator): converting
            # the gathered values eagerly makes XLA hoist the f32 cast above
            # the all-gather, silently doubling the wire bytes (§Perf GE-4).
            gathered = jnp.where(msk_l, push[idx_l],
                                 jnp.zeros((), push.dtype))
            pulled_l = jnp.sum(gathered, axis=1, dtype=jnp.float32)
            dmass = jax.lax.psum(
                jnp.sum(jnp.where(dangling_l, r_l, 0.0)), "data") / n
            r_l = (1.0 - alpha) / n + alpha * (pulled_l + dmass)
        return r_l

    return _smap(body, mesh,
                 in_specs=(P("data", None), P("data", None), P("data")),
                 out_specs=P("data"))


def pagerank_specs_2d(mesh: Mesh, n: int, max_deg: int):
    """Transpose-aware input geometry for the pagerank probe: (specs,
    shardings). The ELL arrays are rows of **A^T** (the pull direction —
    in-neighbors at each output row), "data"-sharded like every row layout
    here; out-degree rides the same row shards."""
    specs = (jax.ShapeDtypeStruct((n, max_deg), jnp.int32),
             jax.ShapeDtypeStruct((n, max_deg), jnp.bool_),
             jax.ShapeDtypeStruct((n,), jnp.float32))
    shards = (NamedSharding(mesh, P("data", None)),
              NamedSharding(mesh, P("data", None)),
              NamedSharding(mesh, P("data")))
    return specs, shards


def input_specs_2d(n: int, max_deg: int, f: int):
    """ShapeDtypeStruct stand-ins for the distributed k-hop dry-run."""
    return (jax.ShapeDtypeStruct((n, max_deg), jnp.int32),
            jax.ShapeDtypeStruct((n, max_deg), jnp.bool_),
            jax.ShapeDtypeStruct((n, f), jnp.int8))


def shardings_2d(mesh: Mesh, n: int, max_deg: int, f: int):
    fr_axes = _frontier_axes(mesh)
    fr = fr_axes if len(fr_axes) > 1 else (fr_axes[0] if fr_axes else None)
    return (NamedSharding(mesh, P("data", None)),
            NamedSharding(mesh, P("data", None)),
            NamedSharding(mesh, P("data", fr)))
