"""The jit key of the sparse storage pytrees (`ELL`, `BitELL`).

A compiled program reads a store's arrays and its shape, never its
stored-entry count, so two stores with equal array shapes and different
`nnz` share one executable; `nnz` stays an exact host int through
`tree_map` and `device_put`; and no traced code sees the count of the call
that first traced it. Compiles are counted through `tracing.totals()`
(`compile.count.<fun_name>`). Each test builds shapes of its own, so the
process-wide jit caches hold nothing for it beforehand."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.core.bitadj import BitELL
from repro.core.delta import DeltaMatrix
from repro.core.ell import ELL
from repro.kernels import bitadj_mxv, bitmap_mxv

KINDS = ["ell", "bitell"]


def delta(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def _pair(kind: str, n: int):
    """Two stores of one kind with equal array shapes and counts 3 and 4."""
    rows, cols = np.array([0, 1, 2, 3]), np.array([1, 2, 3, 4])
    build = ELL.from_coo if kind == "ell" else BitELL.from_coo
    a = build(rows[:3], cols[:3], None, (n, n))
    b = build(rows, cols, None, (n, n))
    assert (a.nnz, b.nnz) == (3, 4)
    return a, b


def _kernel(kind: str):
    if kind == "ell":
        return bitmap_mxv.ell_mxv_packed, "jit(ell_mxv_packed)"
    return bitadj_mxv.bitadj_mxv_packed, "jit(bitadj_mxv_packed)"


def probe_store_sum(store):
    return sum(jnp.sum(x.astype(jnp.float32))
               for x in jax.tree_util.tree_leaves(store))


def probe_store_count(store):
    """The count the trace sees, or -1 where it has none."""
    try:
        return jnp.int32(store.nnz)
    except ValueError:
        return jnp.int32(-1)


@pytest.mark.parametrize("kind", KINDS)
def test_equal_shapes_give_equal_treedefs(kind):
    a, b = _pair(kind, 48)
    assert jax.tree_util.tree_structure(a) == jax.tree_util.tree_structure(b)
    assert hash(jax.tree_util.tree_structure(a)) == \
        hash(jax.tree_util.tree_structure(b))
    c, _ = _pair(kind, 80)                      # another shape: another key
    assert jax.tree_util.tree_structure(a) != jax.tree_util.tree_structure(c)


@pytest.mark.parametrize("kind,n", [("ell", 40), ("bitell", 96)])
def test_kernel_compiles_once_for_equal_shapes(kind, n):
    a, b = _pair(kind, n)
    fn, key = _kernel(kind)
    xw = jnp.asarray(np.arange(n, dtype=np.uint32)[:, None] | 1)
    before = tracing.totals()
    ya = np.asarray(fn(a, xw, interpret=True))
    yb = np.asarray(fn(b, xw, interpret=True))
    assert delta(before, tracing.totals()).get(f"compile.count.{key}") == 1
    # one executable, and each store's own answer: the added edge (3, 4)
    # only changes row 3
    assert not np.array_equal(ya[3], yb[3])
    assert np.array_equal(np.delete(ya, 3, axis=0), np.delete(yb, 3, axis=0))


@pytest.mark.parametrize("kind,n", [("ell", 56), ("bitell", 160)])
def test_plain_jit_compiles_once_for_equal_shapes(kind, n):
    a, b = _pair(kind, n)
    f = jax.jit(probe_store_sum)
    before = tracing.totals()
    f(a).block_until_ready()
    f(b).block_until_ready()
    d = delta(before, tracing.totals())
    assert d.get("compile.count.jit(probe_store_sum)") == 1


@pytest.mark.parametrize("kind", KINDS)
def test_nnz_exact_after_tree_map_and_device_put(kind):
    _, b = _pair(kind, 64)
    for e in (jax.tree_util.tree_map(lambda x: x, b), jax.device_put(b),
              jax.device_put(b, jax.devices()[0])):
        assert type(e) is type(b)
        assert type(e.nnz) is int and e.nnz == 4
        assert e.shape == b.shape


@pytest.mark.parametrize("kind,n", [("ell", 88), ("bitell", 192)])
def test_no_stale_count_inside_a_trace(kind, n):
    """The second call hits the first one's executable: neither the trace
    nor what it returns may carry the first call's count."""
    a, b = _pair(kind, n)
    f = jax.jit(probe_store_count)
    assert int(f(a)) == -1 and int(f(b)) == -1
    same = jax.jit(lambda e: e)
    assert same(a).shape == a.shape
    out = same(b)
    assert np.array_equal(np.asarray(jax.tree_util.tree_leaves(out)[0]),
                          np.asarray(jax.tree_util.tree_leaves(b)[0]))
    with pytest.raises(ValueError, match="unknown inside a trace"):
        out.nnz


def test_delta_patches_in_one_bucket_share_one_executable():
    """A write stream that keeps the patch in one (rows, width) bucket:
    every patch has the same treedef and shapes and its own exact count,
    the hop kernel builds once for all of them, and a patch read back
    inside a trace shows no count."""
    n = 72
    r = np.repeat(np.arange(n), 2)          # every row two edges: the stream
    c = (r + np.tile([1, 2], n)) % n        # leaves touched rows under 8
    base = ELL.from_coo(r, c, None, (n, n))
    d = DeltaMatrix.wrap(base)
    stream = [("add", 0, 5, 1.0), ("add", 1, 6, 1.0), ("add", 2, 7, 1.0),
              ("add", 0, 9, 1.0), ("del", 0, 5, 0.0), ("add", 3, 11, 1.0),
              ("del", 1, 6, 0.0), ("add", 1, 13, 1.0)]
    patches = []
    for op in stream:
        d = d.apply_ops([op])
        p, _ = d.patch()
        patches.append(p)
    shapes = {tuple(x.shape for x in jax.tree_util.tree_leaves(p))
              for p in patches}
    assert len(shapes) == 1
    assert len({jax.tree_util.tree_structure(p) for p in patches}) == 1
    for p in patches:
        assert type(p.nnz) is int
        assert p.nnz == int(np.count_nonzero(np.asarray(p.mask)))
    assert len({p.nnz for p in patches}) > 1
    xw = jnp.ones((n, 1), jnp.uint32)
    before = tracing.totals()
    for p in patches:
        bitmap_mxv.ell_mxv_packed(p, xw, interpret=True).block_until_ready()
    d_ = delta(before, tracing.totals())
    assert d_.get("compile.count.jit(ell_mxv_packed)") == 1
    f = jax.jit(probe_store_count)
    assert [int(f(p)) for p in patches] == [-1] * len(patches)
