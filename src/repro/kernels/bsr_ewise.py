"""Pallas TPU kernel: BSR element-wise numeric phase (gathered tile map).

The element-wise counterpart of `kernels/bsr_spgemm.py`: the host-side
coordinate plan in `core.bsr` (union / intersection / mask alignment of the
valid-tile key lists — the element-wise symbolic phase) produces, per output
tile, a *gather selector* into each operand's tile payload array; this module
runs the numeric phase on device, so the tile values never round-trip through
host numpy the way the pre-kernel implementation did.

Layout / schedule
-----------------
  grid = (T,)                        # one program per output tile; programs
  A.blocks[sel_a[t]] : (b, b) tile   # are independent (no revisit schedule —
  B.blocks[sel_b[t]] : (b, b) tile   # unlike SpGEMM there is no accumulation
  C.blocks[t]        : (b, b) tile   # across tasks)

Scalar prefetch feeds (sel_a, pa, sel_b, pb) to the index maps. A selector
of -1 means "no stored tile on this side" — the host plan clips it to 0 and
zeroes the presence flag (pa/pb), and the kernel multiplies the DMA'd tile
by the flag, so an absent operand tile reads as the all-zero tile the
structural convention demands (stored == nonzero).

Modes (the closure applied per tile pair; zeros stay zeros, so tiles the op
empties are pruned later by ``BSR.from_blocks_device``):
  union      where(both stored, op(a, b), a + b)   — GrB_eWiseAdd
  intersect  where(both stored, op(a, b), 0)       — GrB_eWiseMult
  apply      where(a stored, op(a), 0)             — GrB_apply (unary)
  select     where(a stored and op(a), a, 0)       — GxB_select (unary)
  mask       where(b stored, a, 0)                 — <M> restrict
  mask_c     where(b absent, a, 0)                 — <!M> restrict

`map_tiles` is the jit'd entry: `impl="xla"` runs the batched gather
reference (the CPU path), `impl="pallas"` the kernel (interpret mode
off-TPU). Both produce identical (T, b, b) float32 payloads.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

EWISE_MODES = ("union", "intersect", "apply", "select", "mask", "mask_c")

# modes whose second operand is never read (the B gather is a dummy)
UNARY_MODES = ("apply", "select")


def _tile_fn(mode: str, op):
    """The per-tile-pair closure; inputs are (b, b) f32, absent == 0."""
    if mode == "union":
        def fn(a, b):
            both = (a != 0) & (b != 0)
            # where only one side stores, the other tile holds 0, so a + b
            # is exactly the stored value there (0 where neither stores)
            return jnp.where(both, op(a, b).astype(jnp.float32), a + b)
    elif mode == "intersect":
        def fn(a, b):
            both = (a != 0) & (b != 0)
            return jnp.where(both, op(a, b).astype(jnp.float32),
                             jnp.float32(0.0))
    elif mode == "apply":
        def fn(a, b):
            del b
            return jnp.where(a != 0, op(a).astype(jnp.float32),
                             jnp.float32(0.0))
    elif mode == "select":
        def fn(a, b):
            del b
            return jnp.where((a != 0) & op(a), a, jnp.float32(0.0))
    elif mode == "mask":
        def fn(a, b):
            return jnp.where(b != 0, a, jnp.float32(0.0))
    elif mode == "mask_c":
        def fn(a, b):
            return jnp.where(b == 0, a, jnp.float32(0.0))
    else:
        raise NotImplementedError(f"bsr_ewise mode {mode!r}")
    return fn


def _kernel(sel_a_ref, pa_ref, sel_b_ref, pb_ref, ablk_ref, bblk_ref,
            y_ref, *, fn, unary: bool):
    t = pl.program_id(0)
    a = ablk_ref[0].astype(jnp.float32) * pa_ref[t].astype(jnp.float32)
    if unary:
        b = a                      # never read by fn; keeps the arity uniform
    else:
        b = bblk_ref[0].astype(jnp.float32) * pb_ref[t].astype(jnp.float32)
    y_ref[0] = fn(a, b)


@functools.partial(
    jax.jit, static_argnames=("mode", "op", "block", "interpret"))
def _ewise_pallas(Ab, Bb, sel_a, pa, sel_b, pb, *, mode: str, op,
                  block: int, interpret: bool) -> jnp.ndarray:
    b = block
    nt = sel_a.shape[0]
    kernel = functools.partial(_kernel, fn=_tile_fn(mode, op),
                               unary=mode in UNARY_MODES)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(nt,),
            in_specs=[
                pl.BlockSpec((1, b, b),
                             lambda t, sa, pa_, sb, pb_: (sa[t], 0, 0)),
                pl.BlockSpec((1, b, b),
                             lambda t, sa, pa_, sb, pb_: (sb[t], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, b, b),
                                   lambda t, sa, pa_, sb, pb_: (t, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((nt, b, b), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(sel_a, pa, sel_b, pb, Ab, Bb)


@functools.partial(jax.jit, static_argnames=("mode", "op"))
def _ewise_jnp(Ab, Bb, sel_a, pa, sel_b, pb, *, mode: str, op) -> jnp.ndarray:
    """XLA reference numeric phase: batched gathers + the tile closure."""
    a = Ab.astype(jnp.float32)[sel_a] * pa.astype(jnp.float32)[:, None, None]
    if mode in UNARY_MODES:
        b = a
    else:
        b = (Bb.astype(jnp.float32)[sel_b]
             * pb.astype(jnp.float32)[:, None, None])
    return _tile_fn(mode, op)(a, b)


def map_tiles(Ablocks, sel_a, Bblocks, sel_b, mode: str, op=None, *,
              impl: str = "xla", interpret: bool | None = None):
    """Numeric phase of a BSR element-wise op: (T, b, b) output payloads.

    ``sel_a``/``sel_b`` are host int arrays of length T indexing the operand
    payload arrays; -1 selects the all-zero tile. For unary modes pass
    ``Bblocks=None`` / ``sel_b=None``. Returns device-resident float32 tiles
    aligned with the caller's output coordinate list.
    """
    assert mode in EWISE_MODES, mode
    block = int(Ablocks.shape[1])
    sel_a = np.asarray(sel_a, dtype=np.int32)
    nt = len(sel_a)
    if nt == 0:
        return jnp.zeros((0, block, block), jnp.float32)
    pa = (sel_a >= 0).astype(np.int32)
    sel_a = np.clip(sel_a, 0, None)
    if mode in UNARY_MODES or sel_b is None:
        sel_b = np.zeros(nt, dtype=np.int32)
        pb = np.zeros(nt, dtype=np.int32)
        Bblocks = jnp.zeros((1, block, block), jnp.float32)
    else:
        sel_b = np.asarray(sel_b, dtype=np.int32)
        pb = (sel_b >= 0).astype(np.int32)
        sel_b = np.clip(sel_b, 0, None)
        if Bblocks.shape[0] == 0:
            Bblocks = jnp.zeros((1, block, block), jnp.float32)
    if Ablocks.shape[0] == 0:
        Ablocks = jnp.zeros((1, block, block), jnp.float32)
    args = (jnp.asarray(Ablocks), jnp.asarray(Bblocks),
            jnp.asarray(sel_a), jnp.asarray(pa),
            jnp.asarray(sel_b), jnp.asarray(pb))
    if impl == "pallas":
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        return _ewise_pallas(*args, mode=mode, op=op, block=block,
                             interpret=interpret)
    return _ewise_jnp(*args, mode=mode, op=op)
