"""Pallas TPU kernel: BSR x BSR semiring matmul (SpGEMM numeric phase).

The sparse-output counterpart of `kernels/bsr_mxm.py`: instead of walking a
block-row against a dense frontier, the grid walks the *task list* the
symbolic phase planned (`core.bsr.spgemm_symbolic`) — one task per matching
(A tile (i,l), B tile (l,j)) pair, tasks grouped contiguously by output tile.

Layout / schedule
-----------------
  grid = (ntasks,)                  # sequential; output tile revisited while
  A.blocks[a_sel[t]] : (b, b) tile  # consecutive tasks share c_sel, so the
  B.blocks[b_sel[t]] : (b, b) tile  # accumulator stays resident in VMEM and
  C.blocks[c_sel[t]] : (b, b) tile  # is written back once per output tile
  mask_blocks[c_sel[t]]             # mask tile aligned to the output tile

Scalar prefetch feeds (a_sel, b_sel, c_sel) and the first / last / valid
flags word (`bsr_mxm`'s packing: 4 int32 per task, as SMEM holds 1 MiB) to
the index maps and the body: the planned sparsity steers DMA, the body
stays a dense (b, b) MXU dot.
The GraphBLAS mask is applied in two places: the symbolic phase already
dropped output tiles outside a non-complemented mask's block pattern, and the
epilogue on the *last* task of each output tile applies the mask's element
pattern (or its complement) inside the surviving tiles — accumulation stays
mask-free, matching GrB_mxm's "mask applied to the result" timing.

Only MXU dot modes are supported (plus_times / plus_pair / or_and /
plus_first); tropical semirings take the dense fallback in `grb.mxm`.

`spgemm_blocks` is the jit'd entry: `impl="xla"` runs the gather +
segment-sum reference (the CPU path), `impl="pallas"` the kernel
(interpret-mode off-TPU).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import semiring as S
from repro.core.bsr import SPGEMM_MODES, SpGEMMPlan
from repro.kernels.bsr_mxm import FIRST, LAST, VALID


def _tile_product(a: jnp.ndarray, b: jnp.ndarray, sr: S.Semiring) -> jnp.ndarray:
    """One (b, b) x (b, b) semiring tile product on the MXU (f32)."""
    if sr.mode == "dot":
        return jnp.dot(a, b, preferred_element_type=jnp.float32)
    if sr.mode in ("dot_indicator", "dot_pair"):
        return jnp.dot((a != 0).astype(jnp.float32),
                       (b != 0).astype(jnp.float32),
                       preferred_element_type=jnp.float32)
    if sr.mode == "dot_first":
        return jnp.dot(a, (b != 0).astype(jnp.float32),
                       preferred_element_type=jnp.float32)
    raise NotImplementedError(sr.mode)


def _kernel(a_sel_ref, b_sel_ref, c_sel_ref, flags_ref,
            ablk_ref, bblk_ref, mblk_ref, y_ref, *,
            sr: S.Semiring, masked: bool, complement: bool):
    t = pl.program_id(0)
    ident = np.float32(sr.identity)
    flags = flags_ref[t]

    @pl.when((flags & FIRST) != 0)
    def _init():
        y_ref[...] = jnp.full_like(y_ref, ident)

    @pl.when((flags & VALID) != 0)
    def _accum():
        a = ablk_ref[0].astype(jnp.float32)
        b = bblk_ref[0].astype(jnp.float32)
        part = _tile_product(a, b, sr)
        if sr.mode == "dot_indicator":
            y_ref[0] = jnp.maximum(y_ref[0], (part > 0).astype(jnp.float32))
        else:
            y_ref[0] = y_ref[0] + part

    if masked:
        @pl.when((flags & LAST) != 0)
        def _epilogue():
            m = mblk_ref[0]
            keep = (m == 0) if complement else (m != 0)
            y_ref[0] = jnp.where(keep, y_ref[0], ident)


@functools.partial(
    jax.jit, static_argnames=("sr", "nc", "block", "masked", "complement",
                              "interpret"))
def _spgemm_pallas(Ab, Bb, Mb, a_sel, b_sel, c_sel, flags, *,
                   sr: S.Semiring, nc: int, block: int, masked: bool,
                   complement: bool, interpret: bool) -> jnp.ndarray:
    b = block
    grid = (a_sel.shape[0],)
    kernel = functools.partial(_kernel, sr=sr, masked=masked,
                               complement=complement)
    mask_map = ((lambda t, asel, bsel, csel, fl: (csel[t], 0, 0))
                if masked else
                (lambda t, asel, bsel, csel, fl: (0, 0, 0)))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, b, b),
                             lambda t, asel, bsel, csel, fl:
                             (asel[t], 0, 0)),
                pl.BlockSpec((1, b, b),
                             lambda t, asel, bsel, csel, fl:
                             (bsel[t], 0, 0)),
                pl.BlockSpec((1, b, b), mask_map),
            ],
            out_specs=pl.BlockSpec(
                (1, b, b),
                lambda t, asel, bsel, csel, fl: (csel[t], 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((nc, b, b), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(a_sel, b_sel, c_sel, flags, Ab, Bb, Mb)


@functools.partial(
    jax.jit, static_argnames=("sr", "nc", "masked", "complement"))
def _spgemm_jnp(Ab, Bb, Mb, a_sel, b_sel, c_sel, valid, *,
                sr: S.Semiring, nc: int, masked: bool,
                complement: bool) -> jnp.ndarray:
    """XLA reference numeric phase: gather task tiles, batched tile products,
    segment-sum into output tiles. The CPU/fallback path."""
    a = Ab.astype(jnp.float32)[a_sel]                  # (T, b, b)
    b = Bb.astype(jnp.float32)[b_sel]
    if sr.mode == "dot":
        contrib = jnp.einsum("tij,tjk->tik", a, b,
                             preferred_element_type=jnp.float32)
    elif sr.mode in ("dot_indicator", "dot_pair"):
        contrib = jnp.einsum("tij,tjk->tik", (a != 0).astype(jnp.float32),
                             (b != 0).astype(jnp.float32),
                             preferred_element_type=jnp.float32)
    elif sr.mode == "dot_first":
        contrib = jnp.einsum("tij,tjk->tik", a,
                             (b != 0).astype(jnp.float32),
                             preferred_element_type=jnp.float32)
    else:
        raise NotImplementedError(sr.mode)
    contrib = contrib * valid.astype(jnp.float32)[:, None, None]
    y = jax.ops.segment_sum(contrib, c_sel, num_segments=nc)
    if sr.mode == "dot_indicator":
        y = (y > 0).astype(jnp.float32)
    if masked:
        keep = (Mb == 0) if complement else (Mb != 0)
        y = jnp.where(keep, y, np.float32(sr.identity))
    return y


def spgemm_blocks(Ablocks: jnp.ndarray, Bblocks: jnp.ndarray,
                  plan: SpGEMMPlan, sr: S.Semiring, *,
                  mask_blocks: Optional[jnp.ndarray] = None,
                  complement: bool = False, impl: str = "xla",
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """Run a symbolic plan's numeric phase; returns (nc, b, b) output tiles."""
    assert sr.mode in SPGEMM_MODES, sr.mode
    block = int(Ablocks.shape[1])
    masked = mask_blocks is not None
    sel = dict(a_sel=jnp.asarray(plan.a_sel), b_sel=jnp.asarray(plan.b_sel),
               c_sel=jnp.asarray(plan.c_sel))
    if impl == "pallas":
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        Mb = (mask_blocks if masked
              else jnp.zeros((1, block, block), jnp.float32))
        flags = (plan.first * FIRST + plan.last * LAST
                 + plan.valid * VALID).astype(np.int32)
        return _spgemm_pallas(Ablocks, Bblocks, Mb, sel["a_sel"],
                              sel["b_sel"], sel["c_sel"], jnp.asarray(flags),
                              sr=sr, nc=plan.nc,
                              block=block, masked=masked,
                              complement=complement, interpret=interpret)
    # unmasked: the jitted fn never reads Mb (masked is static), so a
    # (1, b, b) dummy avoids materializing an (nc, b, b) zero array
    Mb = mask_blocks if masked else jnp.zeros((1, block, block), jnp.float32)
    return _spgemm_jnp(Ablocks, Bblocks, Mb, sel["a_sel"], sel["b_sel"],
                       sel["c_sel"], jnp.asarray(plan.valid), sr=sr,
                       nc=plan.nc, masked=masked, complement=complement)
