"""Ahead-of-time TPU v5e compiles of the main-path Pallas kernels at the
widths of the Graph500 scale-15 deployment (n = 32,768, edge factor 16).

Nothing runs: each kernel is lowered with `ShapeDtypeStruct` arguments on a
*described* v5e chip and compiled by the TPU compiler installed with JAX.
That refuses what the Pallas interpreter accepts — blocks off the (8, 128)
tiling, dynamic slices of loaded values, scalar prefetch past SMEM, more
VMEM than the chip has — so these tests guard the chip path on a CPU host.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import semiring as S
from repro.core.bitadj import TILE, BitELL
from repro.core.bsr import BSR
from repro.core.ell import ELL
from repro.kernels import bitadj_mxv, bitmap_mxv, bsr_ewise, bsr_mxm, \
    bsr_spgemm

# Graph500 R-MAT, scale 15, edge factor 16, seed 0: the largest Graph500
# graph the ELL store holds on one 16 GB chip
N = 32768
ELL_DEG = 3928            # hub degree 3,926 padded to the ELL's 8-slot grain
BIT_SLOTS = 64            # AUTO_BITADJ_MAX_SLOTS column tiles per panel
BLOCK = 128
NNZB = 32512              # stored 128x128 tiles of the scale-15 BSR build
V5E_HBM = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _compile(one_chip, fn, *args, **kw):
    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    args = jax.tree_util.tree_map(spec, args)
    compiled = fn.lower(*args, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()   # the kernel, not XLA
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < V5E_HBM
    return compiled


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _ell(n, deg):
    return ELL((n, n), _sds((n, deg), jnp.int32), _sds((n, deg), jnp.bool_),
               _sds((n, deg), jnp.float32), nnz=1)


def _bsr(n, nnzb):
    i32 = _sds((nnzb,), jnp.int32)
    return BSR((n, n), BLOCK, _sds((nnzb, BLOCK, BLOCK), jnp.float32),
               i32, i32, i32, i32, i32, _sds((n // BLOCK + 1,), jnp.int32),
               nnz=1)


@pytest.mark.parametrize("words", [1, 16])
def test_ell_mxv_packed_compiles(one_chip, words):
    """The served k-hop sweep: a single-seed query (one word) and a full
    512-lane batch (16 words) over the scale-15 hub-padded ELL."""
    _compile(one_chip, bitmap_mxv.ell_mxv_packed, _ell(N, ELL_DEG),
             _sds((N, words), jnp.uint32))


@pytest.mark.parametrize("words", [1, 16])
def test_bitadj_mxv_packed_compiles(one_chip, words):
    P = N // TILE
    A = BitELL((N, N), _sds((P, BIT_SLOTS, TILE), jnp.uint32),
               _sds((P, BIT_SLOTS), jnp.int32), nnz=1)
    _compile(one_chip, bitadj_mxv.bitadj_mxv_packed, A,
             _sds((N, words), jnp.uint32))


@pytest.mark.parametrize("srname,f", [("or_and", 512), ("plus_times", 128),
                                      ("min_plus", 128)])
def test_bsr_mxm_compiles(one_chip, srname, f):
    _compile(one_chip, bsr_mxm.bsr_mxm, _bsr(N, NNZB),
             _sds((N, f), jnp.float32), sr=S.get(srname))


@pytest.mark.parametrize("masked", [False, True])
def test_bsr_spgemm_compiles(one_chip, masked):
    tiles = _sds((NNZB, BLOCK, BLOCK), jnp.float32)
    mask = tiles if masked else _sds((1, BLOCK, BLOCK), jnp.float32)
    sel = _sds((NNZB,), jnp.int32)
    _compile(one_chip, bsr_spgemm._spgemm_pallas, tiles, tiles, mask,
             sel, sel, sel, sel, sr=S.PLUS_PAIR, nc=NNZB, block=BLOCK,
             masked=masked, complement=False, interpret=False)


@pytest.mark.parametrize("mode", ["union", "apply"])
def test_bsr_ewise_compiles(one_chip, mode):
    op = jnp.add if mode == "union" else jnp.negative
    tiles = _sds((NNZB, BLOCK, BLOCK), jnp.float32)
    sel = _sds((NNZB,), jnp.int32)
    _compile(one_chip, bsr_ewise._ewise_pallas, tiles, tiles, sel, sel, sel,
             sel, mode=mode, op=op, block=BLOCK, interpret=False)
