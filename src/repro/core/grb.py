"""The unified GraphBLAS operation surface: ``C<M> accum= op(A, B, desc)``.

This module is the single API the rest of the engine programs against — the
TPU analog of the GraphBLAS C API subset RedisGraph builds on:

  GrB_Descriptor  -> :class:`Descriptor`  (mask, complement, accum, replace,
                     input-transpose), replacing the mask/complement/accum/
                     ``A_T``/``impl`` kwargs that used to be re-threaded
                     through every caller,
  GrB_Matrix      -> :class:`GBMatrix`    (one handle over dense / BSR / ELL
                     / ShardedELL / DeltaMatrix storage: format-agnostic
                     dispatch, lazy cached transpose, nvals/shape
                     introspection, execution policy resolved once at
                     construction),
  GrB_mxm family  -> module-level :func:`mxm` / :func:`mxv` / :func:`vxm` /
                     :func:`ewise_add` / :func:`ewise_mult` / :func:`reduce` /
                     :func:`apply` / :func:`select` / :func:`assign` /
                     :func:`extract`.

The mxm family takes dense frontiers or sparse GBMatrix operands (BSR x BSR
routes through SpGEMM); the element-wise family is *format-aware*: sparse
operands run block-aligned (BSR, core.bsr) or COO set-algebra (ELL,
core.coo) paths with GraphBLAS union/intersection entry semantics and stay
sparse end to end — no silent densification (docs/API.md §eWise).

The fourth storage kind is *sharded* (`core.shard.ShardedELL`): the same ELL
row layout laid out over a mesh ("data" axis rows, pod x model frontier
columns). :func:`distribute` re-homes an ELL handle onto a mesh; mxm/mxv/
reduce then lower to the explicit-collective shard_map bodies in
`repro.distr.graph2d` (all-gather frontier in row form, psum_scatter row
blocks in transposed form), and the element-wise family — eWiseAdd/Mult,
apply/select with full descriptor blending, column extract/assign, min/max
reduce — runs *shard-local* through the slot-aligned merge lowering
(`graph2d.ewise_2d`): rows live whole on one shard, so COO set algebra
never needs a collective, let alone a gather. The few genuinely
cross-shard requests (row-subset extract/assign, cross-mesh masks) gather
to host and bump :func:`host_transfers` (docs/API.md §Sharded).

The fifth storage kind is the *delta* form (`core.delta.DeltaMatrix`,
docs/API.md §Delta): a frozen base plus pending plus/minus COO deltas, the
live-mutation path of `engine.Database`. The matmul family and plus/or
reduce compose the deltas with zero rebuild (row-patch decomposition —
exact for every semiring); the element-wise family, SpGEMM, descriptor
masks, and min/max reduce fall back to a cached materialize of the
effective matrix in the base's own format. Compaction back into the base
is policy-driven (`AUTO_DELTA_COMPACT`, re-exported here; measured by
benchmarks/bench_mutations.py).

Boolean traversals additionally ride the *bitmap-packed frontier* form
(`core.bitmap`, docs/API.md §Bitmap): an or_and mxm/mxv/vxm whose dense
frontier is at least AUTO_PACK_MIN_WIDTH wide packs it into uint32 words
(32 queries/word) on dense, ELL, and ShardedELL operands, blends
pure-masked writes word-wise, and unpacks at the boundary — results are
bit-identical to the float route and callers never see a packed array.
The policy is trace-time static; `packed_frontiers("on"|"off"|"auto")`
overrides it.

Public contract (what raises, what moves data):

  * TypeError — mixed operand kinds, always naming the expected ones:
    sparse with dense in the eWise family; sharded with unsharded
    anywhere; sparse B against a sharded A; non-ELL storage handed to
    :func:`distribute`; sharded `out=` under unsharded operands.
  * ValueError — shape mismatches (operands, masks vs result, assign
    regions) and invalid/duplicate index vectors.
  * Gathers to host (documented, correct, *counted* by
    :func:`host_transfers`) — only genuinely cross-shard requests:
    row-subset assign/extract (rows re-partition the "data" axis) and a
    sparse mask sharded on a *different* mesh. Everything else on a
    sharded handle stays on the mesh: eWiseAdd/Mult, apply/select under
    any descriptor blend, column extract/assign, and min/max reduce all
    run shard-local through the slot-aligned merge in
    `distr.graph2d.ewise_2d` (docs/API.md §Sharded).

Algorithms (`repro.algorithms`), the query executor (`repro.query.executor`),
and the batched server (`repro.engine.server`) all dispatch through here —
single-device and on a mesh, with zero sharding-specific call-site
arguments; new storage formats or backends plug in behind this surface
without touching callers.

Blend (write) semantics, centralized in :func:`finalize`:

  z       = accum(C, result)      if accum given and C given, else result
  C<M>    = z   inside the mask   (all-true when desc.mask is None)
  C<!M>   = identity              when C is None or desc.replace
          = C (old value)         otherwise
"""
from __future__ import annotations

import contextlib
import dataclasses
import weakref
from typing import Callable, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitadj as _bitadj
from repro.core import bitmap as _bitmap
from repro.core import bsr as _bsr
from repro.core import coo as _coo
from repro.core import ops as _ops
from repro.core import semiring as S
from repro.core import shard as _shard
from repro.core import xfer as _xfer
from repro.core.bitadj import (AUTO_BITADJ_MAX_SLOTS,  # noqa: F401
                               AUTO_BITADJ_MIN_FILL, BitELL, ShardedBitELL)
from repro.core.bsr import BSR, SPGEMM_MODES as _SPGEMM_MODES
from repro.core.delta import AUTO_DELTA_COMPACT, DeltaMatrix  # noqa: F401
from repro.core.ell import ELL
from repro.core.shard import ShardedELL

Array = jnp.ndarray
Storage = Union[BSR, ELL, ShardedELL, DeltaMatrix, BitELL, ShardedBitELL,
                Array]


# ---------------------------------------------------------------------------
# Descriptor — GrB_Descriptor analog
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True, eq=False)
class Descriptor:
    """Operation modifiers for one GraphBLAS call.

    mask        write mask M (same shape as the output, or a (n,) vector for
                mxv/vxm); entries where M is zero are *not* written. May be
                a dense array or a sparse GBMatrix/BSR handle — the SpGEMM
                path applies sparse masks block-wise (docs/API.md §SpGEMM)
    complement  use !M instead of M (GrB_COMP)
    accum       accumulate monoid: C<M> accum= result instead of C<M> = result
    replace     clear C entries outside the mask (GrB_REPLACE)
    transpose_a op reads A^T instead of A (GrB_INP0 + GrB_TRAN); served from
                the GBMatrix handle's cached transpose, never a runtime flip
    """
    mask: Optional[Union[Array, "GBMatrix", BSR]] = None
    complement: bool = False
    accum: Optional[S.Monoid] = None
    replace: bool = False
    transpose_a: bool = False

    def with_(self, **kw) -> "Descriptor":
        return dataclasses.replace(self, **kw)

    @property
    def mask_only(self) -> bool:
        """True when the write is a pure masked overwrite (no accum, no
        replace) — together with out=None, the kernel-fusable case."""
        return self.accum is None and not self.replace


NULL = Descriptor()
TRANSPOSE_A = Descriptor(transpose_a=True)


def desc(mask: Optional[Array] = None, complement: bool = False,
         accum: Optional[S.Monoid] = None, replace: bool = False,
         transpose_a: bool = False) -> Descriptor:
    """Convenience constructor mirroring GrB_Descriptor_set."""
    return Descriptor(mask=mask, complement=complement, accum=accum,
                      replace=replace, transpose_a=transpose_a)


def finalize(d: Descriptor, result: Array, out: Optional[Array],
             identity: float) -> Array:
    """Blend ``result`` into ``out`` under the descriptor (see module doc)."""
    if d.accum is not None and out is not None:
        z = d.accum.op(out, result)
    else:
        z = result
    if d.mask is None:
        return z
    m = (d.mask == 0) if d.complement else (d.mask != 0)
    if out is None or d.replace:
        outside = jnp.full_like(z, np.float32(identity))
    else:
        outside = out
    return jnp.where(m, z, outside)


# ---------------------------------------------------------------------------
# GBMatrix — GrB_Matrix analog
# ---------------------------------------------------------------------------
def _fmt_of(store: Storage) -> str:
    if isinstance(store, BSR):
        return "bsr"
    if isinstance(store, ELL):
        return "ell"
    if isinstance(store, ShardedELL):
        return "sharded"
    if isinstance(store, DeltaMatrix):
        return "delta"
    if isinstance(store, BitELL):
        return "bitadj"
    if isinstance(store, ShardedBitELL):
        return "bitshard"
    return "dense"


# -- impl="auto" crossover policy --------------------------------------------
# Measured by benchmarks/bench_triangles.py (RMAT edge_factor 8, block 128,
# XLA-CPU reference host): the sparse-kernel formulation loses below RMAT
# scale 9 and wins from it — 1.1x at s9 (512 rows = 4 block-rows,
# stored-tile fill 0.022), 1.6x at s10 (8 block-rows, fill 0.012). Below
# AUTO_MIN_GRID block-rows, or with stored tiles mostly full, one batched
# XLA matmul amortizes better than per-tile kernel scheduling; a B operand
# narrower than AUTO_MIN_WIDTH columns cannot fill an MXU pass either way.
AUTO_MIN_GRID = 4     # block-rows/-cols below this: one dense matmul wins
AUTO_MAX_FILL = 0.25  # stored-tile fill above this: effectively dense
AUTO_MIN_WIDTH = 8    # B frontier narrower than this: XLA (auto handles only)

# -- bitmap-packed frontier policy -------------------------------------------
# or_and-semiring mxm/mxv/vxm on dense / ELL / ShardedELL operands pack the
# boolean frontier into uint32 words (core.bitmap) when it is at least this
# wide. Measured by benchmarks/bench_khop.run_packed (RMAT s10 k-hop,
# XLA-CPU reference host): the packed route wins at every swept width —
# 9.8x at F=8, 26x at F=32, 84x at F=128 — because the unpacked ELL gather
# materializes an (n, deg, F) float32 intermediate the words shrink 32x.
# The floor only exempts near-scalar frontiers (a width-1 or_and mxv),
# where a word is >= 97% padding and the pack/unpack boundary is pure
# overhead; it mirrors AUTO_MIN_WIDTH. BSR operands never pack — their
# or_and route is the MXU indicator matmul, which packing would abandon.
AUTO_PACK_MIN_WIDTH = 8

_PACK_MODE = "auto"   # "auto" (width threshold) | "on" | "off"


@contextlib.contextmanager
def packed_frontiers(mode: str):
    """Temporarily override the bitmap-packing policy: "on" packs every
    or_and-eligible call regardless of width, "off" disables packing,
    "auto" restores the AUTO_PACK_MIN_WIDTH crossover. Benchmarks and the
    differential tests use this; production code should leave "auto"."""
    global _PACK_MODE
    if mode not in ("auto", "on", "off"):
        raise ValueError(f"packed_frontiers mode {mode!r} not in "
                         f"('auto', 'on', 'off')")
    prev, _PACK_MODE = _PACK_MODE, mode
    try:
        yield
    finally:
        _PACK_MODE = prev


def _pack_wanted(f: int) -> bool:
    """Width side of the packed-frontier policy (static at trace time)."""
    if _PACK_MODE == "off":
        return False
    return _PACK_MODE == "on" or f >= AUTO_PACK_MIN_WIDTH


def _kernel_pays_off(store: BSR) -> bool:
    """Fill-ratio/grid-size side of the measured crossover (width is only
    known per call and is checked in _dispatch_mxm)."""
    return (min(store.nbrows, store.nbcols) >= AUTO_MIN_GRID
            and store.fill_ratio <= AUTO_MAX_FILL)


def _resolve_impl(requested: str, fmt: str, store: Optional[BSR] = None) -> str:
    """Execution policy, resolved once at handle construction.

    Only the BSR format has two paths (Pallas kernel vs the XLA-native
    batched-matmul); explicit "pallas"/"xla" force one. "auto" picks the
    kernel when a real TPU backend is present AND the measured
    dense-vs-sparse crossover says the per-tile schedule beats one batched
    matmul for this operand (see _kernel_pays_off). ELL and dense always
    lower through XLA.
    """
    if fmt != "bsr":
        return "xla"
    if requested == "pallas":
        return "pallas"
    if requested == "auto" and jax.default_backend() == "tpu":
        if store is None or _kernel_pays_off(store):
            return "pallas"
    return "xla"


class GBMatrix:
    """One matrix handle over dense / BSR / ELL / ShardedELL / DeltaMatrix /
    BitELL (+ its ShardedBitELL mesh twin) storage.

    The handle carries everything per-call kwargs used to: the storage format,
    the resolved execution policy (``impl``), and a lazily-built, cached
    stored transpose (``A.T``) so callers never hand-pass ``A_T``. Transposes
    built by the graph loader are linked in via :meth:`link_transpose`.

    Handles are host-side objects; the underlying storage (registered
    pytrees / jnp arrays) is what flows through jit. Inside traced code,
    close over the handle — do not pass it as a traced argument.
    """
    __slots__ = ("store", "fmt", "impl", "auto", "name", "_t", "_sharded",
                 "__weakref__")

    def __init__(self, store: Storage, impl: str = "auto", name: str = ""):
        if isinstance(store, GBMatrix):
            store = store.store
        if not isinstance(store, (BSR, ELL, ShardedELL, DeltaMatrix,
                                  BitELL, ShardedBitELL)):
            store = jnp.asarray(store)
        self.store = store
        self.fmt = _fmt_of(store)
        # auto marks a policy the crossover heuristics may refine per call
        # (operand width); an explicit "pallas"/"xla" request is never
        # second-guessed.
        self.auto = impl == "auto"
        self.impl = _resolve_impl(impl, self.fmt,
                                  store if isinstance(store, BSR) else None)
        self.name = name
        self._t = None      # the linked transpose (see link_transpose)
        # mesh -> distributed twin, filled by grb.distribute (like the _T
        # cache: serving contexts re-resolve per query and must not re-pad
        # + re-device_put the whole graph each time)
        self._sharded: Optional[dict] = None

    # -- construction --------------------------------------------------------
    @classmethod
    def wrap(cls, A, impl: Optional[str] = None) -> "GBMatrix":
        """Adopt an existing handle or wrap raw storage. impl=None keeps an
        existing handle's resolved policy; an explicit impl re-resolves it."""
        if isinstance(A, GBMatrix):
            return A if impl is None else A.with_impl(impl)
        return cls(A, impl=impl or "auto")

    @classmethod
    def from_coo(cls, rows, cols, vals, shape, fmt: str = "auto",
                 block: int = 128, impl: str = "auto",
                 name: str = "") -> "GBMatrix":
        if fmt == "bsr":
            store = BSR.from_coo(rows, cols, vals, shape, block=block)
        elif fmt == "ell":
            store = ELL.from_coo(rows, cols, vals, shape)
        elif fmt == "bitadj":
            store = BitELL.from_coo(rows, cols, vals, shape)
        elif fmt == "dense":
            d = np.zeros(shape, dtype=np.float32)
            d[np.asarray(rows), np.asarray(cols)] = (
                1.0 if vals is None else np.asarray(vals, dtype=np.float32))
            store = jnp.asarray(d)
        else:
            store = _ops.auto_format(rows, cols, vals, shape, block=block)
        return cls(store, impl=impl, name=name)

    @classmethod
    def from_dense(cls, A, fmt: str = "dense", block: int = 128,
                   impl: str = "auto", name: str = "") -> "GBMatrix":
        if fmt == "dense":
            return cls(jnp.asarray(A), impl=impl, name=name)
        A = np.asarray(A)
        r, c = np.nonzero(A)
        return cls.from_coo(r, c, A[r, c], A.shape, fmt=fmt, block=block,
                            impl=impl, name=name)

    # -- introspection -------------------------------------------------------
    @property
    def shape(self):
        return self.store.shape

    @property
    def nvals(self) -> int:
        """Stored-entry count (GrB_Matrix_nvals)."""
        if self.fmt == "dense":
            return int(np.count_nonzero(np.asarray(self.store)))
        return self.store.nnz

    # -- transpose -----------------------------------------------------------
    @property
    def _T(self) -> Optional["GBMatrix"]:
        t = self._t
        return t() if isinstance(t, weakref.ref) else t

    @property
    def T(self) -> "GBMatrix":
        """Stored transpose, built once and cached; ``A.T.T is A``."""
        if self._T is None:
            # the handle cache outlives any trace that triggers the build
            # (e.g. transpose_a inside a while_loop body), so the transpose
            # arrays must be concrete, never trace-bound tracers
            with jax.ensure_compile_time_eval():
                if self.fmt == "dense":
                    t: Storage = self.store.T
                else:
                    t = self.store.transpose()
            # an auto policy stays auto: re-resolve against the transposed
            # store and keep the per-call crossover heuristics active
            self.link_transpose(GBMatrix(t,
                                         impl="auto" if self.auto
                                         else self.impl,
                                         name=self.name + "^T"))
        return self._T

    def link_transpose(self, other: "GBMatrix") -> "GBMatrix":
        """Install an explicitly-built transpose (RedisGraph maintains these
        per relation) so ``.T`` never rebuilds it. ``self`` holds the twin
        and the twin refers back weakly: a strong pair would be a reference
        cycle, freed (with the device arrays of its storage, such as a
        frozen snapshot's delta patches) only when Python's cyclic
        collector runs, not when the last handle goes."""
        self._t = other
        other._t = weakref.ref(self)
        return self

    # -- policy --------------------------------------------------------------
    def with_impl(self, impl: str) -> "GBMatrix":
        """Re-resolve the execution policy, sharing storage and the transpose
        cache. Returns self when the resolved policy is unchanged."""
        store = self.store if self.fmt == "bsr" else None
        if (_resolve_impl(impl, self.fmt, store) == self.impl
                and (impl == "auto") == self.auto):
            return self
        m = GBMatrix(self.store, impl=impl, name=self.name)
        if self._T is not None:
            m.link_transpose(GBMatrix(self._T.store, impl=impl,
                                      name=self._T.name))
        return m

    # -- conversion ----------------------------------------------------------
    def to_dense(self) -> Array:
        if self.fmt == "dense":
            return self.store
        return self.store.to_dense()

    # -- ergonomics ----------------------------------------------------------
    def __getattr__(self, attr: str):
        # forward storage-specific introspection (indices / mask / blocks /
        # nnz / to_coo / ...) so the handle is a drop-in for raw storage
        if attr.startswith("_"):
            raise AttributeError(attr)
        return getattr(self.store, attr)

    def __repr__(self) -> str:
        n, m = self.shape
        tag = f" {self.name!r}" if self.name else ""
        return (f"GBMatrix{tag} {n}x{m} fmt={self.fmt} impl={self.impl} "
                f"nvals={self.nvals}")


def matrix(obj, rel: Optional[str] = None,
           impl: Optional[str] = None) -> GBMatrix:
    """Adjacency handle from a Graph, Relation, GBMatrix, or raw storage.

    Duck-typed so `repro.core` never imports `repro.graph`: a Graph exposes
    ``relation()``/``relations``, a Relation exposes ``A``/``name``.
    impl=None (the default) keeps the handle's construction-time policy;
    an explicit impl re-resolves it via ``with_impl``.
    """
    if hasattr(obj, "relation") and hasattr(obj, "relations"):   # Graph
        try:
            r = obj.relation(rel)
        except KeyError:
            r = None
        if r is None:
            raise ValueError(f"no relation {rel!r} in graph "
                             f"(have: {sorted(obj.relations)})")
        obj = r
    if hasattr(obj, "A") and hasattr(obj, "name"):               # Relation
        return GBMatrix.wrap(obj.A, impl=impl)
    return GBMatrix.wrap(obj, impl=impl)


def distribute(obj, mesh, rel: Optional[str] = None) -> GBMatrix:
    """Re-home an ELL or BitELL handle onto a mesh: the sharded-storage
    constructor.

    Takes anything :func:`matrix` takes (Graph + rel, Relation, GBMatrix,
    raw ELL/BitELL). Returns a GBMatrix whose storage is a row-sharded
    ``core.shard.ShardedELL`` (or ``core.bitadj.ShardedBitELL`` for
    bit-packed structural adjacency — its transpose twin is force-built and
    linked, since the bit route has no transposed scatter lowering); a
    linked transpose is sharded and linked too, so ``A.T`` / ``transpose_a``
    descriptors keep resolving to stored transposes on the mesh. Every
    later `grb` call on the handle lowers to the mesh collectives — call
    sites carry zero sharding arguments.

    Other storage raises a TypeError naming the expected kinds (the mesh
    layout row-shards ELL's padded neighbor lists / BitELL's word panels;
    BSR tiles and dense arrays have no row-block layout here).

    Distributed twins are cached on the source handle per mesh (like the
    transpose cache), so per-query contexts re-resolving the same relation
    never re-pad + re-device_put the graph.
    """
    h = matrix(obj, rel)
    if h.fmt == "sharded":
        if h.store.mesh == mesh:
            return h
        hh = GBMatrix(h.store.to_ell(), name=h.name)  # re-home across meshes
        if h._T is not None and h._T.fmt == "sharded":
            hh.link_transpose(GBMatrix(h._T.store.to_ell(), name=h._T.name))
        h = hh
    if h.fmt == "bitshard":
        if h.store.mesh == mesh:
            return h
        hh = GBMatrix(h.store.to_bitell(), name=h.name)
        if h._T is not None and h._T.fmt == "bitshard":
            hh.link_transpose(GBMatrix(h._T.store.to_bitell(),
                                       name=h._T.name))
        h = hh
    if h.fmt == "delta":
        # the mesh layout has no delta lowering: compact into the base
        # format first (engine.Database freezes mesh-served graphs with
        # compact=True so serving contexts never pay this per query)
        hh = GBMatrix(h.store.materialize(), name=h.name)
        if h._T is not None and h._T.fmt == "delta":
            hh.link_transpose(GBMatrix(h._T.store.materialize(),
                                       name=h._T.name))
        h = hh
    if h.fmt == "bitadj":
        # bit-packed panels shard like ELL rows do — but transpose_a on the
        # mesh is always served from a stored twin (there is no transposed
        # bit-scatter lowering), so force-build + link it here, once
        cache = h._sharded if h._sharded is not None else {}
        m = cache.get(mesh)
        if m is None:
            hT = h.T                      # host rebuild, cached on the handle
            m = GBMatrix(ShardedBitELL.from_bitell(h.store, mesh),
                         name=h.name)
            m.link_transpose(
                GBMatrix(ShardedBitELL.from_bitell(hT.store, mesh),
                         name=hT.name))
            cache[mesh] = m
            h._sharded = cache
        return m
    if h.fmt != "ell":
        raise TypeError(
            f"grb.distribute: sharded dispatch needs ELL or BitELL row "
            f"storage, got {h.fmt!r} — rebuild with fmt='ell' "
            f"(GBMatrix.from_dense(x, fmt='ell') / "
            f"GraphBuilder.build(fmt='ell')) before distributing onto a "
            f"mesh")
    cache = h._sharded if h._sharded is not None else {}
    m = cache.get(mesh)
    if m is None:
        m = GBMatrix(ShardedELL.from_ell(h.store, mesh), name=h.name)
        if h._T is not None and h._T.fmt == "ell":
            m.link_transpose(GBMatrix(ShardedELL.from_ell(h._T.store, mesh),
                                      name=h._T.name))
        cache[mesh] = m
        h._sharded = cache
    return m


# ---------------------------------------------------------------------------
# uniform op surface — GrB_mxm family
# ---------------------------------------------------------------------------
def _dispatch_mxm(A: GBMatrix, B: Array, sr: S.Semiring,
                  d: Descriptor, fuse_mask: bool):
    """Format + policy dispatch for one semiring matmul. Returns
    (raw_result, mask_already_applied)."""
    if A.fmt == "bsr":
        impl = A.impl
        if impl == "pallas" and A.auto and B.shape[1] < AUTO_MIN_WIDTH:
            impl = "xla"   # auto policy: narrow frontier can't fill the MXU
        if (impl == "pallas" and sr.mode == "bcast"
                and A.store.emask is not None):
            impl = "xla"   # explicit-zero structure: kernel has no emask lane
        if impl == "pallas":
            from repro.kernels import ops as kops   # lazy: kernels import core
            if fuse_mask:
                # the kernel folds <M>/<!M> into its epilogue on the last
                # tile of each block-row — no separate masking pass
                return kops.bsr_mxm(A.store, B, sr, mask=d.mask,
                                    complement=d.complement), True
            return kops.bsr_mxm(A.store, B, sr), False
        return _ops.bsr_mxm_jnp(A.store, B, sr), False
    if A.fmt == "ell":
        return _ops.ell_mxm(A.store, B, sr), False
    return S.dense_mxm(S.structural_dense(A.store, sr), B, sr), False


def _mask_storage(mask) -> Optional[Storage]:
    """Unwrap a descriptor mask that may be a GBMatrix handle. Sharded masks
    gather to a host ELL; delta masks compose into their base format (the
    documented materialize fallback, docs/API.md §Delta) — mask blending
    happens host/dense-side."""
    if isinstance(mask, GBMatrix):
        mask = mask.store
    if isinstance(mask, (ShardedELL, ShardedBitELL, BitELL)):
        mask = mask.to_ell()
    if isinstance(mask, DeltaMatrix):
        mask = mask.materialize()
    return mask


def _mask_as_bsr(mask, block: int) -> Optional[BSR]:
    """Structural BSR view of a descriptor mask for the SpGEMM and sparse
    element-wise paths. Sparse masks convert sparse-to-sparse (COO);
    only a mask that is *already dense* is tiled from its array."""
    mask = _mask_storage(mask)
    if mask is None:
        return None
    if isinstance(mask, (BSR, ELL)):
        return _bsr.as_bsr(mask, block)
    return BSR.from_dense(np.asarray(mask), block=block)


def _mxm_spgemm(A: GBMatrix, B: GBMatrix, sr: S.Semiring,
                d: Descriptor) -> GBMatrix:
    """Sparse-times-sparse dispatch: C<M> = A (x) B with C staying BSR.

    The structural mask is applied block-wise during accumulation planning
    (non-complemented masks prune whole output tiles symbolically) and
    element-wise in the kernel epilogue — never on a dense product.
    """
    from repro.core.bsr import spgemm
    mask = _mask_as_bsr(d.mask, A.store.block)
    C = spgemm(A.store, B.store, sr, mask=mask, complement=d.complement,
               impl=A.impl)
    name = f"({A.name}x{B.name})" if (A.name or B.name) else ""
    return GBMatrix(C, impl="auto" if A.auto else A.impl, name=name)


def _mxm_sharded(A: GBMatrix, B, sr: S.Semiring, d: Descriptor,
                 out: Optional[Array]) -> Array:
    """Mesh dispatch: C<M> accum= A (x) B with A's rows sharded over "data".

    B must be a dense (k, F) frontier (sharded x sparse has no mesh
    lowering — the TypeError names the expected kinds). transpose_a is
    served from a linked sharded transpose when one exists; otherwise the
    transposed (psum_scatter) lowering reads the forward row shards — no
    materialization either way. The blend (mask/accum/replace) runs on the
    global result under GSPMD, identical to the dense path.
    """
    if isinstance(B, GBMatrix) and B.fmt == "dense":
        B = B.store                      # dense handle == dense frontier
    if isinstance(B, (GBMatrix, BSR, ELL, ShardedELL)):
        kind = _operand_kind(B)[0]
        raise TypeError(
            f"grb.mxm: a sharded A multiplies a dense (k, F) frontier "
            f"array; got a sparse {kind} operand for B. Gather it "
            f"explicitly (B.to_dense()) or keep both sides unsharded for "
            f"the SpGEMM path.")
    transposed = False
    if d.transpose_a:
        if A._T is not None:
            A = A.T
        else:
            transposed = True
        d = d.with_(transpose_a=False)
    if isinstance(d.mask, (GBMatrix, BSR, ELL, ShardedELL)):
        m = _mask_storage(d.mask)
        d = d.with_(mask=m if isinstance(m, jnp.ndarray) else m.to_dense())
    B = jnp.asarray(B)
    # or_and frontiers ride the mesh as packed uint32 words — the per-hop
    # all-gather (row form) / psum_scatter (transposed form) payload cut
    packed = (sr.mode == "dot_indicator" and B.ndim == 2
              and _pack_wanted(B.shape[1]))
    y = _shard.mxm(A.store, B, sr, transposed=transposed, packed=packed)
    return finalize(d, y, out, sr.identity)


def _mxm_delta(A: GBMatrix, B: Array, sr: S.Semiring, d: Descriptor,
               out: Optional[Array]) -> Array:
    """Delta-composed semiring matmul, exact for every semiring with zero
    rebuild: result row i depends only on A row i, so rows no delta touches
    come from the frozen base's product and delta-touched rows from the
    product of a small ELL *patch* holding their exact effective content
    (docs/API.md §Delta). The patch covers only the touched rows, so the
    composition overhead is O(touched * deg), not a second full product;
    its rows scatter over the base product (out-of-bounds padding drops).
    Rows past the base's extent (live node growth) are the add identity
    unless patched. Both sub-products recurse through :func:`mxm`, so the
    base keeps its own route — BSR kernel/XLA policy, bitmap-packed or_and
    frontiers — untouched."""
    dm: DeltaMatrix = A.store
    impl = "auto" if A.auto else A.impl
    baseh = GBMatrix(dm.base, impl=impl, name=A.name)
    bn, bm = baseh.shape
    n = dm.shape[0]
    patch, rows = dm.patch()
    if patch is None and n == bn:
        return mxm(baseh, B, sr, d, out=out)       # empty delta: base verbatim
    yb = mxm(baseh, B[:bm], sr)
    if n > bn:
        pad = jnp.full((n - bn, yb.shape[1]), np.float32(sr.identity),
                       dtype=yb.dtype)
        yb = jnp.concatenate([yb, pad], axis=0)
    if patch is not None:
        yp = mxm(GBMatrix(patch, impl=impl), B, sr)
        yb = yb.at[rows].set(yp, mode="drop")
    return finalize(d, yb, out, sr.identity)


def _packed_route_ok(A: GBMatrix, B, sr: S.Semiring) -> bool:
    """Static (trace-time) gate for the bitmap-packed or_and route: boolean
    semiring, dense frontier B, dense/ELL/BitELL storage (BSR keeps the MXU
    indicator matmul), frontier wide enough per the measured crossover.
    BitELL is exempt from the width floor — its adjacency side is packed
    whatever the frontier width, so the word route never loses."""
    if sr.mode != "dot_indicator" or getattr(B, "ndim", 0) != 2:
        return False
    if A.fmt == "bitadj":
        return True                          # structural: words always win
    return A.fmt in ("dense", "ell") and _pack_wanted(B.shape[1])


def _mxm_packed(A: GBMatrix, B: Array, sr: S.Semiring, d: Descriptor,
                out: Optional[Array]) -> Array:
    """or_and mxm with the frontier in core.bitmap packed form: pack at the
    call boundary, OR words through the packed gather (Pallas kernel on TPU,
    XLA reference otherwise), blend the mask word-wise when the write is a
    pure masked overwrite, unpack at the other boundary. Bit-identical to
    the float indicator route (the unpack renders exactly {0.0, 1.0})."""
    f = B.shape[1]
    Bw = _bitmap.pack(B)
    if A.fmt == "bitadj":
        if jax.default_backend() == "tpu":
            from repro.kernels import ops as kops   # lazy: kernels import core
            Yw = kops.bitadj_mxv_packed(A.store, Bw)
        else:
            Yw = _bitadj.mxm_words(A.store, Bw)
    elif A.fmt == "ell":
        if jax.default_backend() == "tpu":
            from repro.kernels import ops as kops   # lazy: kernels import core
            Yw = kops.ell_mxv_packed(A.store, Bw)
        else:
            Yw = _ops.ell_mxm_packed(A.store, Bw)
    else:
        Yw = _ops.dense_mxm_packed(A.store, Bw)
    if d.mask is not None and d.mask_only and out is None:
        # the or_and identity is 0, so <M> / <!M> on a replace-into-empty
        # write is pure word algebra: keep = and, complement keep = andnot
        Mw = _bitmap.pack(jnp.asarray(d.mask))
        Yw = (_bitmap.word_andnot(Yw, Mw) if d.complement
              else _bitmap.word_and(Yw, Mw))
        return _bitmap.unpack(Yw, f)
    return finalize(d, _bitmap.unpack(Yw, f), out, sr.identity)


def _mxm_bitshard(A: GBMatrix, B, sr: S.Semiring, d: Descriptor,
                  out: Optional[Array]) -> Array:
    """Mesh dispatch for bit-packed adjacency: or_and/any_pair calls run
    fully bit-level (pack at the boundary, `bitadj.sharded_mxm_words` — one
    packed all-gather per call, word-AND + OR locally, zero float
    intermediates; the route is taken for *every* dot_indicator call, so
    results never depend on the packing policy). transpose_a always serves
    from the linked twin grb.distribute force-built. Other semirings take
    the cached ShardedELL materialization and the regular sharded route."""
    if isinstance(B, GBMatrix) and B.fmt == "dense":
        B = B.store
    if isinstance(B, (GBMatrix, BSR, ELL, ShardedELL, BitELL,
                      ShardedBitELL)):
        kind = _operand_kind(B)[0]
        raise TypeError(
            f"grb.mxm: a sharded A multiplies a dense (k, F) frontier "
            f"array; got a sparse {kind} operand for B. Gather it "
            f"explicitly (B.to_dense()) or keep both sides unsharded for "
            f"the SpGEMM path.")
    if d.transpose_a:
        if A._T is None or A._T.fmt != "bitshard":
            raise RuntimeError(
                "grb.mxm: transpose_a on bit-sharded storage needs the "
                "linked transpose twin grb.distribute builds — distribute "
                "the handle (not a hand-wrapped ShardedBitELL) first")
        A = A.T
        d = d.with_(transpose_a=False)
    if isinstance(d.mask, (GBMatrix, BSR, ELL, ShardedELL, BitELL,
                           ShardedBitELL, DeltaMatrix)):
        m = _mask_storage(d.mask)
        d = d.with_(mask=m if isinstance(m, jnp.ndarray) else m.to_dense())
    B = jnp.asarray(B)
    if sr.mode == "dot_indicator" and B.ndim == 2:
        f = B.shape[1]
        Yw = _bitadj.sharded_mxm_words(A.store, _bitmap.pack(B))
        if d.mask is not None and d.mask_only and out is None:
            Mw = _bitmap.pack(jnp.asarray(d.mask))
            Yw = (_bitmap.word_andnot(Yw, Mw) if d.complement
                  else _bitmap.word_and(Yw, Mw))
            return _bitmap.unpack(Yw, f)
        return finalize(d, _bitmap.unpack(Yw, f), out, sr.identity)
    Ae = GBMatrix(A.store.materialize_sharded(), name=A.name)
    if A._T is not None and A._T.fmt == "bitshard":
        Ae.link_transpose(GBMatrix(A._T.store.materialize_sharded(),
                                   name=A._T.name))
    return _mxm_sharded(Ae, B, sr, d, out)


def mxm(A, B, sr: S.Semiring, d: Descriptor = NULL,
        out: Optional[Array] = None):
    """C<M> accum= A (x) B over a semiring — the uniform GraphBLAS call.

    A: GBMatrix (or raw BSR/ELL/dense, wrapped on the fly). B: either a
    dense (m, f) frontier matrix (returns a dense C) or a *sparse* GBMatrix
    (BSR x BSR routes through the SpGEMM kernel and returns a BSR-backed
    GBMatrix — see docs/API.md §SpGEMM for the dispatch rule). ``out`` is
    the existing C for accum/blend; None means replace-into-empty.
    """
    A = GBMatrix.wrap(A)
    if A.fmt == "sharded":
        return _mxm_sharded(A, B, sr, d, out)
    if A.fmt == "bitshard":
        return _mxm_bitshard(A, B, sr, d, out)
    if isinstance(B, (ShardedELL, ShardedBitELL)) or (
            isinstance(B, GBMatrix) and B.fmt in ("sharded", "bitshard")):
        raise TypeError(
            "grb.mxm: B is sharded but A is not — operand kinds must match. "
            "Distribute A onto the same mesh (grb.distribute(A, mesh)) or "
            "gather B explicitly (B.to_dense()).")
    if d.transpose_a:
        A = A.T
        d = d.with_(transpose_a=False)
    # delta operands against a *sparse* partner (the SpGEMM route and its
    # BSR result type) compose via the cached materialize fallback; against
    # a dense frontier, A stays delta and takes the row-patch route below
    if isinstance(B, (GBMatrix, BSR, ELL)) and A.fmt == "delta":
        A = GBMatrix(A.store.materialize(), impl="auto" if A.auto else A.impl,
                     name=A.name)
    if isinstance(B, GBMatrix) and B.fmt == "delta":
        B = GBMatrix(B.store.materialize(), name=B.name)
    if (isinstance(B, GBMatrix) and A.fmt == "bsr" and B.fmt == "bsr"
            and out is None and sr.mode in _SPGEMM_MODES):
        return _mxm_spgemm(A, B, sr, d)
    if isinstance(B, GBMatrix):
        B = B.to_dense()
    if isinstance(d.mask, (GBMatrix, BSR, ELL, ShardedELL, BitELL,
                           ShardedBitELL, DeltaMatrix)):
        m = _mask_storage(d.mask)
        d = d.with_(mask=m if isinstance(m, jnp.ndarray) else m.to_dense())
    if A.fmt == "delta":
        return _mxm_delta(A, jnp.asarray(B), sr, d, out)
    if A.fmt == "bitadj" and not _packed_route_ok(A, B, sr):
        # weighted / non-indicator call on structural storage: the cached
        # materialize-to-ELL fallback (mirrors the DeltaMatrix contract)
        A = GBMatrix(A.store.to_ell(), impl="auto" if A.auto else A.impl,
                     name=A.name)
    if _packed_route_ok(A, B, sr):
        return _mxm_packed(A, jnp.asarray(B), sr, d, out)
    fuse = d.mask is not None and out is None and d.mask_only
    y, mask_done = _dispatch_mxm(A, B, sr, d, fuse)
    if mask_done:
        return y
    return finalize(d, y, out, sr.identity)


def host_transfers() -> int:
    """Device->host gathers inside op dispatch since process start — the
    transfer-accounting sibling of ``densify_calls()`` / ``pack_calls()``
    (core.xfer). Sharded gathers (``ShardedELL.to_ell`` and everything that
    routes through it) and BSR host materializations bump it; materializing
    a final algorithm *result* does not. "Zero host transfers in any
    sharded hot loop" is pinned as a delta of this counter plus the
    structural HLO scan in ``distr.graph2d.scan_host_transfers``."""
    return _xfer.host_transfers()


def mxm_words(A, Bw: Array, transpose_a: bool = False) -> Array:
    """or_and mxm with the frontier already bitmap-packed: (k, W) uint32
    words in, (rows, W) words out — the packed-in/packed-out entry that
    word-resident hop loops (BFS / k-hop / WCC / executor sweeps) thread
    through a ``while_loop`` carry, so nothing packs, unpacks, or gathers
    at the per-hop call boundary.

    No descriptor: the or_and identity is 0, so callers blend masks
    word-wise themselves (``bitmap.word_and`` / ``word_andnot`` — exactly
    what the visited-complement mask of a traversal is). Dense, ELL, and
    sharded operands lower natively packed; BSR/delta operands have no
    packed route (their or_and path is the MXU indicator matmul) and
    detour through the float mxm *on device*, re-packing the result —
    gate callers with :func:`words_route_ok` to avoid that.
    """
    A = GBMatrix.wrap(A)
    if A.fmt == "sharded":
        transposed = False
        if transpose_a:
            if A._T is not None:
                A = A.T
            else:
                transposed = True
        return _shard.mxm_words(A.store, Bw, transposed=transposed)
    if A.fmt == "bitshard":
        if transpose_a:
            if A._T is None or A._T.fmt != "bitshard":
                raise RuntimeError(
                    "grb.mxm_words: transpose_a on bit-sharded storage "
                    "needs the linked twin grb.distribute builds")
            A = A.T
        return _bitadj.sharded_mxm_words(A.store, Bw)
    if transpose_a:
        A = A.T
    if A.fmt == "bitadj":
        if jax.default_backend() == "tpu":
            from repro.kernels import ops as kops   # lazy: kernels import core
            return kops.bitadj_mxv_packed(A.store, Bw)
        return _bitadj.mxm_words(A.store, Bw)
    if A.fmt == "ell":
        if jax.default_backend() == "tpu":
            from repro.kernels import ops as kops   # lazy: kernels import core
            return kops.ell_mxv_packed(A.store, Bw)
        return _ops.ell_mxm_packed(A.store, Bw)
    if A.fmt == "dense":
        return _ops.dense_mxm_packed(A.store, Bw)
    f = Bw.shape[1] * _bitmap.WORD_BITS
    y = mxm(A, _bitmap.unpack(Bw, f), S.OR_AND)
    if isinstance(y, GBMatrix):
        y = y.to_dense()
    return _bitmap.pack(y)


def words_route_ok(A, f: int) -> bool:
    """Trace-time gate for word-resident hop loops: True when
    :func:`mxm_words` lowers natively packed for this operand (dense / ELL /
    sharded storage) and the packing policy wants a width-``f`` frontier
    packed (``packed_frontiers`` / AUTO_PACK_MIN_WIDTH). BitELL /
    ShardedBitELL pass unconditionally — the adjacency side is packed
    whatever the frontier width. BSR and delta operands keep the float
    hop loop."""
    A = GBMatrix.wrap(A)
    if A.fmt in ("bitadj", "bitshard"):
        return True      # adjacency itself is packed: words always win
    return A.fmt in ("dense", "ell", "sharded") and _pack_wanted(f)


def _columnize(v) -> Optional[Array]:
    # sparse GBMatrix/BSR masks have no ndim and pass through to mxm's
    # mask conversion untouched; (n,) vectors become width-1 columns
    if v is not None and getattr(v, "ndim", None) == 1:
        return v[:, None]
    return v


def mxv(A, x: Array, sr: S.Semiring, d: Descriptor = NULL,
        out: Optional[Array] = None) -> Array:
    """y<m> accum= A (x) x — a width-1 frontier."""
    dm = d.with_(mask=_columnize(d.mask))
    y = mxm(A, x[:, None], sr, dm, out=_columnize(out))
    return y[:, 0]


def vxm(x: Array, A, sr: S.Semiring, d: Descriptor = NULL,
        out: Optional[Array] = None) -> Array:
    """y = x (x) A == A^T (x) x, served from the handle's cached transpose."""
    return mxv(A, x, sr, d.with_(transpose_a=not d.transpose_a), out=out)


# ---------------------------------------------------------------------------
# element-wise family — GrB_eWiseAdd / eWiseMult / apply / select
# ---------------------------------------------------------------------------
# Structural convention (repo-wide): an entry is stored iff nonzero; an
# absent entry renders as 0 when a sparse result is densified. The whole
# family therefore uses GraphBLAS *entry* semantics uniformly across dense /
# BSR / ELL operands:
#
#   ewise_add   pattern = union;        op(a, b) where both stored, the
#               stored value where only one side is (absent never fed to op)
#   ewise_mult  pattern = intersection; op(a, b) on the intersection
#   apply       pattern = stored(x);    f applied to stored entries only
#   select      stored entries passing pred, zero-blocks pruned
#
# and the descriptor blend writes *empty* (renders 0) outside the mask —
# not the monoid identity — with accum merging by union. Sparse operands
# stay sparse end-to-end (block-aligned ops in core.bsr, COO set algebra in
# core.coo for ELL); mixing a sparse operand with a dense array raises a
# TypeError naming the expected kinds rather than densifying silently.

def _operand_kind(x):
    """('bsr'|'ell'|'sharded'|'dense', storage) of a handle / store / array.
    Delta operands compose into their base format here (cached materialize,
    docs/API.md §Delta) — the whole element-wise / assign / extract family
    sees exact post-mutation entries without per-op special cases."""
    if isinstance(x, GBMatrix):
        x = x.store
    if isinstance(x, DeltaMatrix):
        x = x.materialize()
    if isinstance(x, BitELL):
        x = x.to_ell()        # cached structural materialization (§BitAdj)
    if isinstance(x, ShardedBitELL):
        return "sharded", x.materialize_sharded()
    if isinstance(x, BSR):
        return "bsr", x
    if isinstance(x, ELL):
        return "ell", x
    if isinstance(x, ShardedELL):
        return "sharded", x
    return "dense", jnp.asarray(x)


def _unshard(x):
    """Gather-to-host view of a sharded operand (ELL, handle-ness kept);
    non-sharded operands pass through."""
    if x is None:
        return None
    kind, s = _operand_kind(x)
    if kind != "sharded":
        return x
    e = s.to_ell()
    return GBMatrix(e, name=x.name) if isinstance(x, GBMatrix) else e


def _sharded_pair_mesh(fn: str, a, b, out=None):
    """Pairing contract for ops with a gather-to-host mesh path: both main
    operands sharded on one mesh (out sharded or None) -> that mesh; no
    sharded operand -> None; anything mixed -> TypeError naming the kinds."""
    kinds = [_operand_kind(x) for x in (a, b) if x is not None]
    shd = [s for k, s in kinds if k == "sharded"]
    ko, so = _operand_kind(out) if out is not None else (None, None)
    if not shd:
        if ko == "sharded":
            raise TypeError(
                f"grb.{fn}: out= is sharded but the operands are not — "
                f"operand kinds must match; distribute the operands "
                f"(grb.distribute) or gather out (out.to_ell())")
        return None
    if len(shd) != len(kinds):
        got = " and ".join(k for k, _ in kinds)
        raise TypeError(
            f"grb.{fn}: operand kinds must match — a sharded matrix pairs "
            f"only with another sharded matrix on the same mesh; got {got}. "
            f"Distribute the unsharded side (grb.distribute(x, mesh)) or "
            f"gather the sharded one (x.to_ell() / x.to_dense()).")
    mesh = shd[0].mesh
    for s in shd[1:]:
        if s.mesh != mesh:
            raise TypeError(f"grb.{fn}: sharded operands live on different "
                            f"meshes — distribute both onto one mesh")
    if ko == "sharded" and so.mesh != mesh:
        raise TypeError(f"grb.{fn}: out= lives on a different mesh than the "
                        f"operands — distribute all three onto one mesh")
    return mesh


# stable-identity ops for the shard-local merge (graph2d.ewise_2d lru-caches
# its shard_map per (mesh, mode, op) — module-level callables keep it warm)
def _take_second(a, b):           # mask restricts never consult the op
    del a
    return b


def _disjoint_concat(a, b):       # unions of provably disjoint patterns
    return a + b


def _sharded_restrict(res: ShardedELL, mask, complement: bool) -> ShardedELL:
    """Mask restrict on a sharded result, shard-local whenever possible:
    a same-mesh sharded mask merges through the slot-aligned pass; any
    dense/host-sparse mask takes the per-slot dense gather. Only a mask
    sharded on a *different* mesh still gathers (counted via to_ell)."""
    m = mask.store if isinstance(mask, GBMatrix) else mask
    if isinstance(m, ShardedELL) and m.mesh == res.mesh:
        if m.shape != res.shape:
            raise ValueError(f"descriptor mask shape {tuple(m.shape)} != "
                             f"result {tuple(res.shape)}")
        return _shard.merge_stored(res, m, _take_second,
                                   "mask_c" if complement else "mask")
    md = _mask_storage(mask)
    dense = md if isinstance(md, (jnp.ndarray, np.ndarray)) else md.to_dense()
    if tuple(dense.shape) != tuple(res.shape):
        raise ValueError(f"descriptor mask shape {tuple(dense.shape)} != "
                         f"result {tuple(res.shape)}")
    return _shard.restrict_dense(res, dense, complement)


def _sharded_blend(d: Descriptor, res: ShardedELL,
                   out: Optional[ShardedELL]) -> ShardedELL:
    """The structural blend rule (union-accum, empty outside the mask) on
    ShardedELL storage — the mesh-resident sibling of
    _structural_finalize_bsr, composed entirely from shard-local merges."""
    if d.accum is not None and out is not None:
        res = _shard.merge_stored(out, res, d.accum.op, "union")
    if d.mask is None:
        return res
    z_in = _sharded_restrict(res, d.mask, d.complement)
    if out is None or d.replace:
        return z_in
    old = _sharded_restrict(out, d.mask, not d.complement)
    return _shard.merge_stored(z_in, old, _disjoint_concat, "union")


def _sharded_out(out, fn: str, mesh, shape) -> Optional[ShardedELL]:
    """Coerce an out= operand for the shard-local blend. A same-mesh sharded
    out passes through; host-sparse outs re-home onto the mesh (a host->
    device put, not a gather); dense outs raise the family's TypeError."""
    if out is None:
        return None
    kind, store = _operand_kind(out)
    if kind == "dense":
        raise TypeError(f"grb.{fn}: sparse operands need a sparse out= "
                        f"(GBMatrix/BSR/ELL) or None (got a dense array); "
                        f"wrap it with GBMatrix.from_dense(out, fmt='ell')")
    if tuple(store.shape) != tuple(shape):
        raise ValueError(f"grb.{fn}: out shape {store.shape} != result "
                         f"{shape}")
    if kind == "sharded":
        return store                      # same mesh: _sharded_pair_mesh ran
    if kind == "bsr":
        store = ELL.from_coo(*store.to_coo(), store.shape)
    return ShardedELL.from_ell(store, mesh)


def _ewise_pair(a, b, fn: str):
    """Classify an operand pair into one execution path, coercing only in
    sparse-to-sparse directions (ELL joins a BSR partner via COO, never
    through a dense intermediate)."""
    ka, sa = _operand_kind(a)
    kb, sb = _operand_kind(b)
    if (ka == "dense") != (kb == "dense"):
        raise TypeError(
            f"grb.{fn}: operand kinds must match — both dense arrays or both "
            f"sparse matrices (GBMatrix/BSR/ELL); got {ka} and {kb}. Convert "
            f"explicitly: GBMatrix.from_dense(x, fmt=...) for the dense side "
            f"or x.to_dense() for the sparse side.")
    if sa.shape != sb.shape:
        raise ValueError(f"grb.{fn} shapes: {sa.shape} vs {sb.shape}")
    if ka == "dense":
        return "dense", sa, sb
    if "bsr" in (ka, kb):
        if isinstance(sa, ELL):
            sa = _bsr.as_bsr(sa, sb.block)
        if isinstance(sb, ELL):
            sb = _bsr.as_bsr(sb, sa.block)
        return "bsr", sa, sb
    return "ell", sa, sb


def _dense_out(out, fn: str) -> Optional[Array]:
    if out is None:
        return None
    kind, store = _operand_kind(out)
    if kind != "dense":
        raise TypeError(f"grb.{fn}: dense operands need a dense out= array "
                        f"(got a sparse {kind} matrix); densify it "
                        f"explicitly with out.to_dense() if intended")
    return store


def _sparse_out_bsr(out, fn: str, block: int) -> Optional[BSR]:
    if out is None:
        return None
    kind, store = _operand_kind(out)
    if kind == "dense":
        raise TypeError(f"grb.{fn}: sparse operands need a sparse out= "
                        f"(GBMatrix/BSR/ELL) or None (got a dense array); "
                        f"wrap it with GBMatrix.from_dense(out, fmt='bsr')")
    return _bsr.as_bsr(store, block)


def _sparse_out_entries(out, fn: str, shape=None):
    """(keys, vals) of a sparse out= operand for the COO blend."""
    if out is None:
        return None, None
    kind, store = _operand_kind(out)
    if kind == "dense":
        raise TypeError(f"grb.{fn}: sparse operands need a sparse out= "
                        f"(GBMatrix/BSR/ELL) or None (got a dense array); "
                        f"wrap it with GBMatrix.from_dense(out, fmt='ell')")
    if shape is not None and store.shape != shape:
        raise ValueError(f"grb.{fn}: out shape {store.shape} != result "
                         f"{shape}")
    r, c, v = store.to_coo()
    return _coo.keys_of(r, c, max(store.shape[1], 1)), \
        np.asarray(v, np.float32)


def _wrap_sparse(store: Storage, *operands) -> "GBMatrix":
    """Wrap a sparse result, inheriting the first handle operand's policy.
    An auto policy stays auto so the crossover heuristics re-resolve against
    the *result's* store (a select can change the grid/fill drastically)."""
    for o in operands:
        if isinstance(o, GBMatrix):
            return GBMatrix(store, impl="auto" if o.auto else o.impl)
    return GBMatrix(store)


def _mask_entry_keys(mask, shape) -> np.ndarray:
    """Stored-entry key set of a descriptor mask (dense or sparse), checked
    against the result shape (a mis-shaped mask must error, not corrupt)."""
    m = _mask_storage(mask)
    if tuple(m.shape) != tuple(shape):
        raise ValueError(f"descriptor mask shape {tuple(m.shape)} != "
                         f"result {tuple(shape)}")
    ncols = max(shape[1], 1)
    if isinstance(m, (BSR, ELL)):
        r, c, _ = m.to_coo()
        return _coo.keys_of(r, c, ncols)
    r, c = np.nonzero(np.asarray(m))
    return _coo.keys_of(r, c, ncols)


def _dense_union(a: Array, b: Array, op) -> Array:
    both = (a != 0) & (b != 0)
    # a + b is exactly "the stored value" where only one side stores one
    return jnp.where(both, op(a, b), a + b)


def _structural_finalize_dense(d: Descriptor, result: Array,
                               out: Optional[Array]) -> Array:
    """The blend rule with entry semantics on dense storage: union-accum,
    and *empty* (0) — not a monoid identity — outside the mask."""
    if d.accum is not None and out is not None:
        z = _dense_union(out, result, d.accum.op)
    else:
        z = result
    mask = d.mask
    if mask is None:
        return z
    m = _mask_storage(mask)
    mask = m.to_dense() if isinstance(m, (BSR, ELL)) else jnp.asarray(m)
    keep = (mask == 0) if d.complement else (mask != 0)
    outside = jnp.zeros_like(z) if (out is None or d.replace) else out
    return jnp.where(keep, z, outside)


def _structural_finalize_bsr(d: Descriptor, res: BSR,
                             out: Optional[BSR]) -> BSR:
    """The same blend rule out of block-aligned sparse primitives — the
    result pattern never leaves tile-list land."""
    if d.accum is not None and out is not None:
        res = _bsr.ewise_add(out, res, d.accum.op)
    if d.mask is None:
        return res
    M = _mask_as_bsr(d.mask, res.block)
    z_in = _bsr.mask_keep(res, M, complement=d.complement)
    if out is None or d.replace:
        return z_in
    old = _bsr.mask_keep(out, M, complement=not d.complement)
    return _bsr.ewise_add(z_in, old, lambda x, y: x + y)   # disjoint patterns


def _structural_finalize_ell(d: Descriptor, keys, vals, out, fn: str,
                             shape) -> ELL:
    """The blend rule on COO entry sets, rebuilt into ELL at the end."""
    w = max(shape[1], 1)                 # zero-width region: no entries
    kc, vc = _sparse_out_entries(out, fn, shape)
    mk = None if d.mask is None else _mask_entry_keys(d.mask, shape)
    accum_op = None if d.accum is None else d.accum.op
    k, v = _coo.blend(keys, vals, kc, vc, mk, d.complement, accum_op,
                      d.replace)
    return ELL.from_entries(*_coo.nonzero(k, v), shape)


def _ell_entries(e) -> tuple:
    r, c, v = e.to_coo()
    return _coo.keys_of(r, c, e.shape[1]), np.asarray(v, np.float32)


def ewise_add(a, b, monoid: S.Monoid, d: Descriptor = NULL, out=None):
    """C<M> accum= A (+) B — GrB_eWiseAdd, union semantics (see above).

    Both operands dense arrays -> dense array; both sparse -> a sparse
    GBMatrix (BSR when either side is BSR, else ELL). Mixed kinds raise
    TypeError. ``monoid`` may be a Monoid or a raw binary callable.
    """
    mesh = _sharded_pair_mesh("ewise_add", a, b, out)
    if mesh is not None:                 # mesh-resident slot-aligned merge
        op = getattr(monoid, "op", monoid)
        A, B = _operand_kind(a)[1], _operand_kind(b)[1]
        if A.shape != B.shape:
            raise ValueError(f"grb.ewise_add shapes: {A.shape} vs {B.shape}")
        res = _shard.merge_stored(A, B, op, "union")
        C = _sharded_out(out, "ewise_add", mesh, A.shape)
        return _wrap_sparse(_sharded_blend(d, res, C), a, b, out)
    op = getattr(monoid, "op", monoid)
    kind, A, B = _ewise_pair(a, b, "ewise_add")
    if kind == "dense":
        return _structural_finalize_dense(
            d, _dense_union(A, B, op), _dense_out(out, "ewise_add"))
    if kind == "bsr":
        res = _bsr.ewise_add(A, B, op)
        C = _sparse_out_bsr(out, "ewise_add", A.block)
        return _wrap_sparse(_structural_finalize_bsr(d, res, C), a, b, out)
    k, v = _coo.nonzero(*_coo.union(*_ell_entries(A), *_ell_entries(B), op))
    return _wrap_sparse(
        _structural_finalize_ell(d, k, v, out, "ewise_add", A.shape),
        a, b, out)


def ewise_mult(a, b, op: Callable[[Array, Array], Array],
               d: Descriptor = NULL, out=None):
    """C<M> accum= A (.*) B — GrB_eWiseMult, intersection semantics.

    Same dispatch contract as :func:`ewise_add`; on BSR operands only tiles
    valid in both patterns are gathered (structural pruning before any
    element work). ``op`` may be a Monoid or a raw binary callable.
    """
    mesh = _sharded_pair_mesh("ewise_mult", a, b, out)
    if mesh is not None:                 # mesh-resident slot-aligned merge
        op2 = getattr(op, "op", op)
        A, B = _operand_kind(a)[1], _operand_kind(b)[1]
        if A.shape != B.shape:
            raise ValueError(f"grb.ewise_mult shapes: {A.shape} vs {B.shape}")
        res = _shard.merge_stored(A, B, op2, "intersect")
        C = _sharded_out(out, "ewise_mult", mesh, A.shape)
        return _wrap_sparse(_sharded_blend(d, res, C), a, b, out)
    op = getattr(op, "op", op)
    kind, A, B = _ewise_pair(a, b, "ewise_mult")
    if kind == "dense":
        both = (A != 0) & (B != 0)
        raw = jnp.where(both, op(A, B), jnp.zeros_like(A))
        return _structural_finalize_dense(d, raw, _dense_out(out, "ewise_mult"))
    if kind == "bsr":
        res = _bsr.ewise_mult(A, B, op)
        C = _sparse_out_bsr(out, "ewise_mult", A.block)
        return _wrap_sparse(_structural_finalize_bsr(d, res, C), a, b, out)
    k, v = _coo.nonzero(*_coo.intersect(*_ell_entries(A), *_ell_entries(B),
                                        op))
    return _wrap_sparse(
        _structural_finalize_ell(d, k, v, out, "ewise_mult", A.shape),
        a, b, out)


def apply(f: Callable[[Array], Array], x, d: Descriptor = NULL, out=None):
    """C<M> accum= f(A) — GrB_apply over *stored* entries only.

    Zero entries of a dense operand (and zero lanes inside stored BSR
    tiles) are absent and stay zero regardless of f(0). On a sharded
    operand every call is mesh-resident: the value map runs on each row
    shard in place, and descriptor blends compose shard-local merges
    (docs/API.md §Sharded).
    """
    _sharded_pair_mesh("apply", x, None, out)       # mixed-out contract
    kind, X = _operand_kind(x)
    if kind == "sharded":
        res = X.apply_stored(f)
        C = _sharded_out(out, "apply", X.mesh, X.shape)
        return _wrap_sparse(_sharded_blend(d, res, C), x, out)
    if kind == "dense":
        raw = jnp.where(X != 0, f(X), jnp.zeros_like(X))
        return _structural_finalize_dense(d, raw, _dense_out(out, "apply"))
    if kind == "bsr":
        res = _bsr.apply_stored(X, f)
        C = _sparse_out_bsr(out, "apply", X.block)
        return _wrap_sparse(_structural_finalize_bsr(d, res, C), x, out)
    k, v = _ell_entries(X)
    k, v = _coo.nonzero(k, np.asarray(f(v), dtype=np.float32))
    return _wrap_sparse(
        _structural_finalize_ell(d, k, v, out, "apply", X.shape), x, out)


def select(pred: Callable[[Array], Array], x, d: Descriptor = NULL,
           out=None):
    """C<M> accum= A where pred(A) — GxB_select over stored entries.

    Same signature and descriptor semantics as :func:`apply` (the mask /
    accum / out path goes through the same finalize); sparse results prune
    tiles the predicate emptied, so nvals/fill_ratio stay truthful. Sharded
    dispatch mirrors :func:`apply`: shard-local mask surgery, with
    descriptor blends composed from shard-local merges.
    """
    _sharded_pair_mesh("select", x, None, out)      # mixed-out contract
    kind, X = _operand_kind(x)
    if kind == "sharded":
        res = X.select_stored(pred)
        C = _sharded_out(out, "select", X.mesh, X.shape)
        return _wrap_sparse(_sharded_blend(d, res, C), x, out)
    if kind == "dense":
        raw = jnp.where((X != 0) & pred(X), X, jnp.zeros_like(X))
        return _structural_finalize_dense(d, raw, _dense_out(out, "select"))
    if kind == "bsr":
        res = _bsr.select_stored(X, pred)
        C = _sparse_out_bsr(out, "select", X.block)
        return _wrap_sparse(_structural_finalize_bsr(d, res, C), x, out)
    k, v = _ell_entries(X)
    keep = np.asarray(pred(v), dtype=bool)
    return _wrap_sparse(
        _structural_finalize_ell(d, k[keep], v[keep], out, "select",
                                 X.shape), x, out)


# ---------------------------------------------------------------------------
# reduce — GrB_reduce
# ---------------------------------------------------------------------------
def _reduce_bsr(s: BSR, monoid: S.Monoid, axis) -> Array:
    if monoid.name not in ("plus", "or") or axis not in (None, 0, 1):
        # min/max need the absent entries (dense zeros) to participate
        return monoid.reduce(s.to_dense(), axis=axis)
    v = s.blocks.astype(jnp.float32) * s.valid.astype(jnp.float32)[:, None,
                                                                   None]
    if monoid.name == "or":
        # boolean OR == "any stored entry", NOT max (wrong for negatives)
        v = (v != 0).astype(jnp.float32)
    if axis is None:
        tot = jnp.sum(v)
        return (tot > 0).astype(jnp.float32) if monoid.name == "or" else tot
    per = jnp.sum(v, axis=2 if axis == 1 else 1)          # (nnzb, block)
    seg = s.block_rows if axis == 1 else s.block_cols
    nseg = s.nbrows if axis == 1 else s.nbcols
    out = jax.ops.segment_sum(per, seg, num_segments=nseg).reshape(-1)
    out = out[:s.shape[0] if axis == 1 else s.shape[1]]
    return (out > 0).astype(jnp.float32) if monoid.name == "or" else out


def _reduce_ell(e: ELL, monoid: S.Monoid, axis) -> Array:
    if monoid.name not in ("plus", "or") or axis not in (None, 0, 1):
        return monoid.reduce(e.to_dense(), axis=axis)
    w = e.values * e.mask.astype(jnp.float32)
    if monoid.name == "or":
        w = (w != 0).astype(jnp.float32)
    if axis is None:
        tot = jnp.sum(w)
        return (tot > 0).astype(jnp.float32) if monoid.name == "or" else tot
    if axis == 1:
        out = jnp.sum(w, axis=1)
    else:
        m = e.shape[1]
        ids = jnp.where(e.mask, e.indices, m).reshape(-1)
        out = jax.ops.segment_sum(w.reshape(-1), ids,
                                  num_segments=m + 1)[:m]
    return (out > 0).astype(jnp.float32) if monoid.name == "or" else out


def _reduce_delta(h: "GBMatrix", monoid: S.Monoid, axis) -> Array:
    """Delta-composed reduce for the plus/or monoids, zero rebuild: per-row
    (axis=1) uses the same row decomposition as _mxm_delta — untouched rows
    from the base's reduce, delta-touched rows from the patch's; per-column
    (axis=0) is the per-row reduce of the *linked transpose twin* (the graph
    layer maintains twins incrementally); the full reduction folds the
    per-row vector. Anything else — min/max (absent entries participate),
    or axis=0 without a twin — takes the cached materialize fallback."""
    dm: DeltaMatrix = h.store
    if monoid.name in ("plus", "or"):
        if axis == 1:
            rb = reduce(dm.base, monoid, axis=1)
            if monoid.name == "or":
                # "any stored entry" uniformly (a dense base's raw max
                # would leak non-indicator values into the indicator path)
                rb = (rb != 0).astype(jnp.float32)
            n, bn = dm.shape[0], dm.base.shape[0]
            if n > bn:
                rb = jnp.concatenate(
                    [rb, jnp.zeros(n - bn, dtype=rb.dtype)])
            patch, rows = dm.patch()
            if patch is None:
                return rb
            rp = _reduce_ell(patch, monoid, axis=1)
            return rb.at[rows].set(rp, mode="drop")
        if axis == 0 and h._T is not None and h._T.fmt == "delta":
            return _reduce_delta(h._T, monoid, axis=1)
        if axis is None:
            tot = jnp.sum(_reduce_delta(h, monoid, axis=1))
            return (tot > 0).astype(jnp.float32) if monoid.name == "or" \
                else tot
    return reduce(dm.materialize(), monoid, axis=axis)


def reduce(x, monoid: S.Monoid, axis=None) -> Array:
    """Monoid reduction (GrB_reduce). Sparse operands (GBMatrix or raw
    BSR/ELL) reduce over *stored* entries without densifying for the plus
    and or monoids — full reduction, axis=0 (per column) and axis=1 (per
    row); "or" means "any stored entry", correct for negative values. Other
    monoids need the absent entries (dense zeros) and fall back through
    to_dense(). Sharded operands reduce on the mesh for plus/or (per-row
    sums shard-local, full/per-column sums psum partials over "data") *and*
    for min/max (stored-entry pmin/pmax + a stored-count compare folds the
    implicit zeros back in — graph2d.reduce_minmax_2d, no gather). Delta
    operands compose (plus/or) with zero rebuild — see _reduce_delta."""
    s = x.store if isinstance(x, GBMatrix) else x
    if isinstance(s, DeltaMatrix):
        h = x if isinstance(x, GBMatrix) else GBMatrix(s)
        return _reduce_delta(h, monoid, axis)
    if isinstance(s, (BitELL, ShardedBitELL)):
        # degree sums / any-stored straight off the bit-tiles (SWAR
        # popcounts, no materialization; sharded arrays reduce under GSPMD)
        if monoid.name in ("plus", "or") and axis in (None, 0, 1):
            return _bitadj.reduce_stored(s, monoid, axis)
        x = GBMatrix(s.to_ell()) if isinstance(s, BitELL) else x
    kind, X = _operand_kind(x)
    if kind == "bsr":
        return _reduce_bsr(X, monoid, axis)
    if kind == "ell":
        return _reduce_ell(X, monoid, axis)
    if kind == "sharded":
        if monoid.name in ("plus", "or") and axis in (None, 0, 1):
            return _shard.reduce_stored(X, monoid, axis)
        if monoid.name in ("min", "max") and axis in (None, 0, 1):
            return _shard.reduce_minmax(X, monoid, axis)
        return monoid.reduce(X.to_dense(), axis=axis)   # counted gather
    return monoid.reduce(X, axis=axis)


# ---------------------------------------------------------------------------
# assign / extract — GrB_assign / GrB_extract analogs
# ---------------------------------------------------------------------------
def _norm_index(idx, n: int, fn: str) -> np.ndarray:
    """Normalize a rows=/cols= argument to a unique int64 index vector."""
    if idx is None:
        return np.arange(n, dtype=np.int64)
    if isinstance(idx, slice):
        idx = range(*idx.indices(n))
    idx = np.asarray(idx, dtype=np.int64)
    if idx.ndim != 1:
        raise TypeError(f"grb.{fn}: indices must be 1-D (got ndim={idx.ndim})")
    if len(idx) and (idx.min() < 0 or idx.max() >= n):
        raise ValueError(f"grb.{fn}: index out of range for extent {n}")
    if len(np.unique(idx)) != len(idx):
        raise ValueError(f"grb.{fn}: duplicate indices are not supported")
    return idx


def _is_aligned_range(idx: np.ndarray, block: int) -> bool:
    return (len(idx) > 0 and idx[0] % block == 0
            and bool(np.all(np.diff(idx) == 1)))


def extract(A, rows=None, cols=None, d: Descriptor = NULL, out=None):
    """C<M> accum= A[rows, cols] — the GrB_extract analog.

    rows/cols: None (all), a slice/range, or a unique index vector. Dense
    operands return dense arrays; sparse operands stay sparse (BSR uses
    pure tile-list surgery when the ranges are contiguous and block-aligned,
    COO relabeling otherwise) and return a GBMatrix. The descriptor applies
    to the extracted (len(rows), len(cols)) result. Sharded operands stay
    mesh-resident for column subsets (rows=None — a shard-local LUT
    relabel); row subsets re-partition the "data" axis and take the counted
    gather fallback (docs/API.md §Sharded).
    """
    mesh = _sharded_pair_mesh("extract", A, None, out)
    if mesh is not None:
        SA = _operand_kind(A)[1]
        n, m = SA.shape
        I = _norm_index(rows, n, "extract")
        J = _norm_index(cols, m, "extract")
        if rows is None or (len(I) == n and np.array_equal(I, np.arange(n))):
            sub = _shard.extract_cols(SA, J)
            C = _sharded_out(out, "extract", mesh, sub.shape)
            return _wrap_sparse(_sharded_blend(d, sub, C), A, out)
        return distribute(extract(_unshard(A), rows, cols, d, _unshard(out)),
                          mesh)
    kind, SA = _operand_kind(A)
    n, m = SA.shape
    I = _norm_index(rows, n, "extract")
    J = _norm_index(cols, m, "extract")
    if kind == "dense":
        raw = SA[jnp.asarray(I)][:, jnp.asarray(J)]
        return _structural_finalize_dense(d, raw, _dense_out(out, "extract"))
    if kind == "bsr":
        if _is_aligned_range(I, SA.block) and _is_aligned_range(J, SA.block):
            sub = _bsr.extract_ranges(SA, int(I[0]), int(I[-1]) + 1,
                                      int(J[0]), int(J[-1]) + 1)
        else:
            r, c, v = SA.to_coo()
            rr, cc, vv = _coo.extract_entries(r, c, v, I, J, n, m)
            sub = BSR.from_coo(rr, cc, vv, (len(I), len(J)), block=SA.block)
        C = _sparse_out_bsr(out, "extract", sub.block)
        return _wrap_sparse(_structural_finalize_bsr(d, sub, C), A, out)
    r, c, v = SA.to_coo()
    rr, cc, vv = _coo.extract_entries(r, c, v, I, J, n, m)
    k = _coo.keys_of(rr, cc, max(len(J), 1))
    return _wrap_sparse(
        _structural_finalize_ell(d, k, vv, out, "extract",
                                 (len(I), len(J))), A, out)


def _assign_sharded_cols(C, sc: ShardedELL, A, J: np.ndarray,
                         d: Descriptor):
    """C(:, J)<M> accum= A with C sharded — fully mesh-resident: the region
    (all rows x J) splits from the rest of C by shard-local column LUTs, the
    blend runs on the (n, len(J)) region in local coordinates, and the
    result relabels back into global columns and unions with the untouched
    entries (disjoint patterns, so the merge never consults the op)."""
    n, m = sc.shape
    ka, sa = _operand_kind(A)
    if sa.shape != (n, len(J)):
        raise ValueError(f"grb.assign: A shape {sa.shape} != region "
                         f"{(n, len(J))}")
    if len(J) == 0:
        return C if isinstance(C, GBMatrix) else sc
    if ka == "sharded":
        if sa.mesh != sc.mesh:
            raise TypeError("grb.assign: sharded operands live on different "
                            "meshes — distribute both onto one mesh")
    else:
        # re-home the region operand onto C's mesh (host->device put)
        if ka == "dense":
            e = ELL.from_dense(np.asarray(sa))
        elif isinstance(sa, ELL):
            e = sa
        else:
            e = ELL.from_coo(*sa.to_coo(), sa.shape)
        sa = ShardedELL.from_ell(e, sc.mesh)
    lut_out = np.arange(m, dtype=np.int32)
    lut_out[J] = -1
    c_out = _shard.relabel_cols(sc, lut_out, m)     # entries outside region
    c_in = _shard.extract_cols(sc, J)               # region, local coords
    blended = _sharded_blend(d, sa, c_in)
    back = _shard.relabel_cols(blended, np.asarray(J, np.int32), m)
    res = _shard.merge_stored(c_out, back, _disjoint_concat, "union")
    return _wrap_sparse(res, C)


def assign(C, A, rows=None, cols=None, d: Descriptor = NULL):
    """C(rows, cols)<M> accum= A — the GrB_assign analog (functional: C is
    not mutated; a new handle/array of C's kind is returned).

    A must be (len(rows), len(cols)); the descriptor mask has that shape
    too (the mask-on-submatrix GrB_assign variant). Without accum/mask the
    region's pattern is *replaced* by A's (entries of C absent in A are
    deleted). Sparse C stays sparse: entries are re-split by region
    host-side and the blend runs on COO entry sets — no densification.
    Sharded C stays mesh-resident for column regions (rows=None): region
    split, blend, and reassembly are shard-local LUT relabels + merges;
    row subsets re-partition the "data" axis and take the counted gather
    fallback (docs/API.md §Sharded). A may be sharded alongside C (same
    mesh) or host-side (re-homed onto the mesh, a host->device put).
    """
    if "sharded" in (_operand_kind(C)[0], _operand_kind(A)[0]):
        kc, sc = _operand_kind(C)
        if kc != "sharded":
            raise TypeError(
                "grb.assign: A is sharded but C is not — operand kinds must "
                "match; distribute C (grb.distribute) or gather A "
                "(A.to_ell())")
        n, m = sc.shape
        I = _norm_index(rows, n, "assign")
        J = _norm_index(cols, m, "assign")
        if rows is None or (len(I) == n and np.array_equal(I, np.arange(n))):
            return _assign_sharded_cols(C, sc, A, J, d)
        return distribute(assign(_unshard(C), _unshard(A), rows, cols, d),
                          sc.mesh)
    kindC, SC = _operand_kind(C)
    n, m = SC.shape
    I = _norm_index(rows, n, "assign")
    J = _norm_index(cols, m, "assign")
    kindA, SA = _operand_kind(A)
    if SA.shape != (len(I), len(J)):
        raise ValueError(f"grb.assign: A shape {SA.shape} != region "
                         f"{(len(I), len(J))}")
    if len(I) == 0 or len(J) == 0:
        return C if isinstance(C, GBMatrix) else SC
    if kindC == "dense":
        subA = SA if kindA == "dense" else SA.to_dense()
        Ij, Jj = jnp.asarray(I), jnp.asarray(J)
        sub = SC[Ij][:, Jj]
        blended = _structural_finalize_dense(d, subA, sub)
        res = SC.at[Ij[:, None], Jj[None, :]].set(blended)
        return GBMatrix(res) if isinstance(C, GBMatrix) else res
    # sparse C: split stored entries by region membership, blend the local
    # entry set, and reassemble — COO set algebra end to end
    r, c, v = SC.to_coo()
    lutr = np.full(n, -1, dtype=np.int64)
    lutr[I] = np.arange(len(I))
    lutc = np.full(m, -1, dtype=np.int64)
    lutc[J] = np.arange(len(J))
    inreg = (lutr[r] >= 0) & (lutc[c] >= 0)
    w = len(J)
    kc = _coo.keys_of(lutr[r[inreg]], lutc[c[inreg]], w)
    vc = np.asarray(v[inreg], np.float32)
    if kindA == "dense":
        ar, ac = np.nonzero(np.asarray(SA))
        ka = _coo.keys_of(ar, ac, w)
        va = np.asarray(SA)[ar, ac].astype(np.float32)
    else:
        ar, ac, av = SA.to_coo()
        ka = _coo.keys_of(ar, ac, w)
        va = np.asarray(av, np.float32)
    mk = None if d.mask is None else _mask_entry_keys(d.mask,
                                                      (len(I), len(J)))
    accum_op = None if d.accum is None else d.accum.op
    k, val = _coo.blend(ka, va, kc, vc, mk, d.complement, accum_op,
                        d.replace)
    k, val = _coo.nonzero(k, val)
    gr = np.concatenate([r[~inreg], I[k // w]])
    gc = np.concatenate([c[~inreg], J[k % w]])
    gv = np.concatenate([np.asarray(v[~inreg], np.float32), val])
    if kindC == "bsr":
        store: Storage = BSR.from_coo(gr, gc, gv, (n, m), block=SC.block)
    else:
        store = ELL.from_coo(gr, gc, gv, (n, m))
    return _wrap_sparse(store, C)
