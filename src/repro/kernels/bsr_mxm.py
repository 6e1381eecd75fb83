"""Pallas TPU kernel: block-sparse semiring matmul  Y = A_bsr (x) X  [+ mask].

This is the traversal hot spot of the GraphBLAS engine — the TPU-native
replacement for SuiteSparse's sparse matmul at the heart of RedisGraph.

Layout / schedule
-----------------
  grid = (F_tiles, nnzb)            # nnzb minor => sequential over a row's tiles
  blocks[k]  : (bm, bk) dense tile, streamed HBM->VMEM by BlockSpec
  X[bcol[k]] : (bk, ft) tile of the dense frontier matrix
  Y[brow[k]] : (bm, ft) output tile — revisited while k walks one block-row,
               so the accumulator lives in VMEM (registers of the schedule);
               Pallas only writes it back to HBM when brow changes.

Scalar prefetch (pltpu.PrefetchScalarGridSpec) feeds the tile coordinate
arrays (block_rows / block_cols, and first / last / valid packed into one
flags word) to the index maps and the body —
the sparsity pattern steers DMA, the kernel body stays dense (MXU).

Semiring specialization
-----------------------
  dot            plus_times   : acc += A @ X                     (MXU)
  dot_indicator  or_and       : acc |= (A!=0) @ (X!=0) > 0       (MXU + clamp)
  dot_pair       plus_pair    : acc += (A!=0) @ (X!=0)           (MXU)
  dot_first      plus_first   : acc += A @ (X!=0)                (MXU)
  bcast          min/max_plus : chunked broadcast-reduce         (VPU)

The optional GraphBLAS mask (with complement) is fused into the epilogue on
the *last* tile of each block-row — tiles whose rows are fully masked still
stream (structural zeros), which the block-level `valid` flag short-circuits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import semiring as S
from repro.core.bsr import BSR

DEFAULT_F_TILE = 128

# per-tile schedule flags, packed into one scalar-prefetch word per tile so
# the prefetch holds 3 int32 per tile (SMEM is 1 MiB)
FIRST, LAST, VALID = 1, 2, 4


def _kernel(brow_ref, bcol_ref, flags_ref,                     # scalar prefetch
            blocks_ref, x_ref, mask_ref, y_ref, *,
            sr: S.Semiring, masked: bool, complement: bool, bcast_chunk: int):
    k = pl.program_id(1)
    ident = np.float32(sr.identity)
    flags = flags_ref[k]

    @pl.when((flags & FIRST) != 0)
    def _init():
        y_ref[...] = jnp.full_like(y_ref, ident)

    @pl.when((flags & VALID) != 0)
    def _accum():
        a = blocks_ref[0].astype(jnp.float32)          # (bm, bk)
        x = x_ref[...].astype(jnp.float32)             # (bk, ft)
        if sr.mode == "dot":
            part = jnp.dot(a, x, preferred_element_type=jnp.float32)
            y_ref[...] = y_ref[...] + part
        elif sr.mode in ("dot_indicator", "dot_pair"):
            part = jnp.dot((a != 0).astype(jnp.float32),
                           (x != 0).astype(jnp.float32),
                           preferred_element_type=jnp.float32)
            if sr.mode == "dot_indicator":
                y_ref[...] = jnp.maximum(y_ref[...], (part > 0).astype(jnp.float32))
            else:
                y_ref[...] = y_ref[...] + part
        elif sr.mode == "dot_first":
            part = jnp.dot(a, (x != 0).astype(jnp.float32),
                           preferred_element_type=jnp.float32)
            y_ref[...] = y_ref[...] + part
        elif sr.mode == "bcast":
            # tropical inner block: chunk rows of A to bound the (rows, bk, ft)
            # broadcast intermediate inside VMEM; the chunk is sliced from
            # the ref (Mosaic has no dynamic_slice on a loaded value)
            nchunk = blocks_ref.shape[1] // bcast_chunk

            def body(i, _):
                r0 = pl.multiple_of(i * bcast_chunk, bcast_chunk)
                rows = blocks_ref[0, pl.ds(r0, bcast_chunk), :].astype(
                    jnp.float32)
                rows = jnp.where(rows != 0, rows, ident)             # (ch, bk)
                prod = sr.mul(rows[:, :, None], x[None, :, :])       # (ch, bk, ft)
                part = sr.add.reduce(prod, axis=1)                   # (ch, ft)
                cur = y_ref[pl.dslice(i * bcast_chunk, bcast_chunk), :]
                y_ref[pl.dslice(i * bcast_chunk, bcast_chunk), :] = sr.add.op(cur, part)
                return 0

            jax.lax.fori_loop(0, nchunk, body, 0)
        else:
            raise NotImplementedError(sr.mode)

    if masked:
        @pl.when((flags & LAST) != 0)
        def _epilogue():
            m = mask_ref[...]
            keep = (m == 0) if complement else (m != 0)
            y_ref[...] = jnp.where(keep, y_ref[...], ident)


@functools.partial(
    jax.jit,
    static_argnames=("sr", "f_tile", "complement", "interpret", "bcast_chunk"))
def bsr_mxm(A: BSR, X: jnp.ndarray, sr: S.Semiring, *,
            mask: jnp.ndarray | None = None, complement: bool = False,
            f_tile: int = DEFAULT_F_TILE, bcast_chunk: int = 8,
            interpret: bool = False) -> jnp.ndarray:
    """Y[n,f] = add_j mul(A[n,j], X[j,f]), optionally masked (<mask> / <!mask>)."""
    n, m = A.shape
    b = A.block
    nbr, nbc = A.nbrows, A.nbcols
    f = X.shape[1]
    ft = min(f_tile, max(f, 1))
    f_pad = (-f) % ft

    Xp = jnp.pad(X.astype(jnp.float32), ((0, nbc * b - m), (0, f_pad)))
    fp = Xp.shape[1]
    if mask is not None:
        Mp = jnp.pad(mask.astype(jnp.float32), ((0, nbr * b - n), (0, f_pad)))
    else:
        Mp = jnp.zeros((nbr * b, fp), dtype=jnp.float32)  # unused

    grid = (fp // ft, A.nnzb)

    kernel = functools.partial(
        _kernel, sr=sr, masked=mask is not None, complement=complement,
        bcast_chunk=bcast_chunk)
    flags = (A.first * FIRST + A.last * LAST + A.valid * VALID).astype(
        jnp.int32)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, b, b),
                             lambda fi, k, br, bc, fl: (k, 0, 0)),
                pl.BlockSpec((b, ft),
                             lambda fi, k, br, bc, fl: (bc[k], fi)),
                pl.BlockSpec((b, ft),
                             lambda fi, k, br, bc, fl: (br[k], fi)),
            ],
            out_specs=pl.BlockSpec(
                (b, ft), lambda fi, k, br, bc, fl: (br[k], fi)),
        ),
        out_shape=jax.ShapeDtypeStruct((nbr * b, fp), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(A.block_rows, A.block_cols, flags, A.blocks, Xp, Mp)
    return out[:n, :f]
