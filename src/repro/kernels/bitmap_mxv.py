"""Pallas TPU kernel: packed or_and gather-reduce  Yw = A_ell (|) Xw.

The inner loop of every structural traversal once the frontier is in
`core.bitmap` packed form: for each vertex row, OR together the uint32
frontier words of its neighbors. One word column carries 32 concurrent
queries, so this is 32 fused boolean mxv's per word — all VPU bitwise ops,
no MXU, and 32x less VMEM traffic than the float indicator route.

Layout / schedule
-----------------
  grid = (n_pad / rows_tile, deg_pad / slot_chunk)
  last (scalar prefetch, SMEM)      # (T,) last slot chunk row tile t needs;
                                    #   the idx index map clamps to it, so
                                    #   chunks past a tile's longest row are
                                    #   neither fetched nor walked
  idx  (rows_tile, slot_chunk) SMEM # neighbor ids streamed per grid step
                                    #   (never the whole (n, deg) array —
                                    #   SMEM holds 1 MiB); invalid slots
                                    #   point at the all-zero sentinel row k
  cnt  (1, 1, rows_tile) SMEM       # per-row slot count: last valid slot + 1
  Xw   (k+1, W) uint32, VMEM        # packed frontier + the zero sentinel
                                    #   row; whole-resident, one copy
  Yw   (rows_tile, W) per row tile  # OR-accumulated across slot chunks

The fori over a row's slots does one dynamic row slice of Xw per edge — the
gather the XLA reference (`core.ops.ell_mxm_packed`) expresses as a fancy
index. Rows loop only over their own slots, so the work is the edge count,
not n x max_deg. On CPU the kernel runs in interpret mode for conformance
only; the `grb` dispatch uses the XLA reference off-TPU
(`kernels.ops.ell_mxv_packed` resolves this the same way the BSR kernels
do).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.ell import ELL

DEFAULT_ROWS_TILE = 8
DEFAULT_SLOT_CHUNK = 512

# VMEM is (8, 128)-tiled: a (k+1, W) uint32 block takes whole 128-lane rows
_SUBLANES, _LANES = 8, 128


def resident_vmem_bytes(rows: int, w: int) -> int:
    """VMEM bytes of a whole-resident (rows, w) 32-bit operand."""
    return (-(-rows // _SUBLANES) * _SUBLANES) * (-(-w // _LANES) * _LANES) * 4


def _kernel(last_ref, idx_ref, cnt_ref, x_ref, y_ref, *, rows_tile: int,
            chunk: int):
    t = pl.program_id(0)
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(c <= last_ref[t])
    def _accum():
        base = c * chunk
        for r in range(rows_tile):               # static unroll, rows_tile small
            hi = jnp.clip(cnt_ref[0, 0, r] - base, 0, chunk)

            def body(s, acc, r=r):
                j = idx_ref[r, s]                # sentinel -> the zero row
                return jnp.bitwise_or(acc, x_ref[pl.ds(j, 1), :])

            y_ref[pl.ds(r, 1), :] = jax.lax.fori_loop(
                0, hi, body, y_ref[pl.ds(r, 1), :])


@functools.partial(jax.jit,
                   static_argnames=("rows_tile", "slot_chunk", "interpret"))
def ell_mxv_packed(A: ELL, Xw: jnp.ndarray, *,
                   rows_tile: int = DEFAULT_ROWS_TILE,
                   slot_chunk: int = DEFAULT_SLOT_CHUNK,
                   interpret: bool = False) -> jnp.ndarray:
    """Yw[i] = OR_{j in adj(i)} Xw[j] over uint32 frontier words.

    A: ELL adjacency (only indices/mask used — or_and is structural).
    Xw: (k, W) packed frontier, k = A.shape[1]. Returns (n, W) uint32.
    """
    n, k = A.shape
    deg = A.max_deg
    w = Xw.shape[1]
    chunk = min(slot_chunk, deg)
    n_pad = n + (-n) % rows_tile
    deg_pad = deg + (-deg) % chunk
    tiles = n_pad // rows_tile

    # sentinel spelling: invalid / padded slots index the appended zero row
    idx = jnp.where(A.mask, A.indices, jnp.int32(k)).astype(jnp.int32)
    idx = jnp.pad(idx, ((0, n_pad - n), (0, deg_pad - deg)),
                  constant_values=k)
    slot = jnp.arange(1, deg + 1, dtype=jnp.int32)
    cnt = jnp.max(jnp.where(A.mask, slot, 0), axis=1).astype(jnp.int32)
    cnt = jnp.pad(cnt, (0, n_pad - n)).reshape(tiles, 1, rows_tile)
    last = (jnp.max(cnt, axis=(1, 2)) - 1) // chunk    # empty tile -> -1
    Xe = jnp.concatenate(
        [Xw.astype(jnp.uint32), jnp.zeros((1, w), dtype=jnp.uint32)], axis=0)

    vmem = (resident_vmem_bytes(k + 1, w)
            + 2 * resident_vmem_bytes(rows_tile, w) + (4 << 20))
    out = pl.pallas_call(
        functools.partial(_kernel, rows_tile=rows_tile, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(tiles, deg_pad // chunk),
            in_specs=[
                pl.BlockSpec((rows_tile, chunk),
                             lambda t, c, last: (
                                 t, jnp.minimum(c, jnp.maximum(last[t], 0))),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((1, 1, rows_tile), lambda t, c, last: (t, 0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((rows_tile, w),
                                   lambda t, c, last: (t, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n_pad, w), jnp.uint32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem),
    )(last, idx, cnt, Xe)
    return out[:n]
