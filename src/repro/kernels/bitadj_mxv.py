"""Pallas TPU kernel: bit-tile or_and matmul  Yw = BitELL (&|) Xw.

The BitELL analog of `kernels/bitmap_mxv.py`: the adjacency *structure*
itself is packed (32x32 edge tiles in 32 uint32 words, `core.bitadj`), the
frontier is packed (PR 5), so the whole traversal inner loop is word loads
+ bitwise select + OR — no floats, no MXU, 32x less adjacency traffic than
the ELL gather on top of the 32x frontier cut.

Layout / schedule
-----------------
  grid = (P,)                       # one step per 32-row panel
  cols  (1, 1, S) int32, SMEM       # this panel's slot -> column-tile ids;
                                    #   sentinel C points at the appended
                                    #   all-zero query tile
  tiles (1, S, 32) VMEM             # this panel's bit-tiles; a slot's 32
                                    #   row words load as one lane row
  Xw   ((C+1)*32, W) uint32, VMEM   # packed frontier squared up to the
                                    #   column-tile grid + zero sentinel
                                    #   tile; whole-resident, one copy
  Yw   (32, W) per step             # the panel's 32 result rows

Per slot the kernel loads one 32-row query tile and the slot's 32 row
words with ref slices (Mosaic has no dynamic slice of a loaded value),
turns the words into a column, then spreads each of the 32 bit positions
as an all-ones/all-zeros mask (`0 - bit`) over the matching query row —
the word-AND + OR the XLA reference (`core.bitadj.panels_mxm_words`)
expresses as a bit-spread einsum. CPU runs interpret mode for conformance;
`grb` dispatch uses the XLA reference off-TPU (resolved by
`kernels.ops.bitadj_mxv_packed`, same pattern as the BSR kernels).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bitadj import TILE, BitELL, _pad_query_tiles
from repro.kernels.bitmap_mxv import resident_vmem_bytes


def _kernel(cols_ref, tiles_ref, x_ref, y_ref, *, slots: int):
    w = y_ref.shape[1]
    # one slot's 32 row words arrive as a lane row; the diagonal of its
    # broadcast turns them into a (32, 1) column, one word per result row
    eye = (jax.lax.broadcasted_iota(jnp.int32, (TILE, TILE), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (TILE, TILE), 1))

    def slot_body(s, acc):
        c = cols_ref[0, 0, s]                               # column-tile id
        xb = x_ref[pl.ds(pl.multiple_of(c * TILE, TILE), TILE), :]  # (32, W)
        row = tiles_ref[0, pl.ds(s, 1), :]                  # (1, 32) words
        tw = jnp.sum(jnp.where(eye, jnp.broadcast_to(row, (TILE, TILE)), 0),
                     axis=1, keepdims=True)                 # (32, 1)
        for b in range(TILE):
            # all-ones where bit b is set in each of the 32 row words
            sel = 0 - jnp.bitwise_and(jnp.right_shift(tw, b), 1)
            acc = jnp.bitwise_or(acc, sel & xb[b:b + 1, :])
        return acc

    y_ref[...] = jax.lax.fori_loop(
        0, slots, slot_body, jnp.zeros((TILE, w), dtype=jnp.int32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitadj_mxv_packed(A: BitELL, Xw: jnp.ndarray, *,
                      interpret: bool = False) -> jnp.ndarray:
    """Yw[i] = OR_{j in adj(i)} Xw[j] over uint32 words, adjacency served
    straight from the bit-tiles. Xw: (k, W) packed frontier. -> (n, W)."""
    n, k = A.shape
    Pn, Sn, _ = A.tiles.shape
    w = Xw.shape[1]
    # the body works on int32 (a same-width bitcast): the words' bits are
    # all it reads, and 32-bit signed lanes are Mosaic's native integer
    i32 = functools.partial(jax.lax.bitcast_convert_type, new_dtype=jnp.int32)
    Xt = i32(_pad_query_tiles(Xw.astype(jnp.uint32), k).reshape(-1, w))
    vmem = (resident_vmem_bytes(Xt.shape[0], w)
            + 2 * resident_vmem_bytes(Sn, TILE)
            + 2 * resident_vmem_bytes(TILE, w) + (4 << 20))

    out = pl.pallas_call(
        functools.partial(_kernel, slots=Sn),
        grid=(Pn,),
        in_specs=[
            pl.BlockSpec((1, 1, Sn), lambda p: (p, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, Sn, TILE), lambda p: (p, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((TILE, w), lambda p: (p, 0)),
        out_shape=jax.ShapeDtypeStruct((Pn * TILE, w), jnp.int32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=vmem),
    )(A.cols.reshape(Pn, 1, Sn).astype(jnp.int32),
      i32(A.tiles), Xt)
    return jax.lax.bitcast_convert_type(out[:n], jnp.uint32)
