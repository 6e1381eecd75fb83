"""Descriptor semantics vs a dense NumPy oracle, for all three formats.

Exercises the centralized blend rule (grb.finalize) end-to-end through
grb.mxm over every mask-mode x accum x replace x existing-C combination,
plus the GBMatrix handle contract: cached lazy transpose, linked transposes
from the graph builder, introspection, and policy resolution.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import BSR, ELL, grb, ops, semiring as S
from repro.core.grb import Descriptor

N, M, F = 96, 80, 6


def _case(seed=0):
    rng = np.random.default_rng(seed)
    r = rng.integers(0, N, size=700)
    c = rng.integers(0, M, size=700)
    key = r * M + c
    _, i = np.unique(key, return_index=True)
    r, c = r[i], c[i]
    v = rng.uniform(0.5, 2.0, size=len(r)).astype(np.float32)
    D = np.zeros((N, M), np.float32)
    D[r, c] = v
    X = np.where(rng.uniform(size=(M, F)) < 0.4,
                 rng.uniform(0.5, 2.0, size=(M, F)), 0.0).astype(np.float32)
    mask = (rng.uniform(size=(N, F)) < 0.5).astype(np.int8)
    C = rng.uniform(0.5, 1.5, size=(N, F)).astype(np.float32)
    return r, c, v, D, X, mask, C


def _handle(fmt, r, c, v, D):
    if fmt == "bsr":
        return grb.GBMatrix(BSR.from_coo(r, c, v, (N, M), block=32))
    if fmt == "ell":
        return grb.GBMatrix(ELL.from_coo(r, c, v, (N, M)))
    return grb.GBMatrix(jnp.asarray(D))


_ACCUM = {"none": None, "plus": S.PLUS, "min": S.MIN}
_ACCUM_NP = {"none": None, "plus": np.add, "min": np.minimum}


def _oracle(raw, C, mask, complement, accum_np, replace, identity):
    """The documented blend rule, independently in NumPy."""
    z = accum_np(C, raw) if (accum_np is not None and C is not None) else raw
    if mask is None:
        return z
    m = (mask == 0) if complement else (mask != 0)
    outside = np.float32(identity) if (C is None or replace) else C
    return np.where(m, z, outside)


@pytest.mark.parametrize("fmt", ["dense", "bsr", "ell"])
@pytest.mark.parametrize("srname", ["plus_times", "min_plus"])
@pytest.mark.parametrize("mask_mode", ["none", "mask", "comp"])
@pytest.mark.parametrize("accum", ["none", "plus"])
@pytest.mark.parametrize("replace", [False, True])
@pytest.mark.parametrize("with_c", [False, True])
def test_descriptor_blend_combinations(fmt, srname, mask_mode, accum,
                                       replace, with_c):
    sr = S.get(srname)
    r, c, v, D, X, mask, C = _case(seed=3)
    A = _handle(fmt, r, c, v, D)
    raw = np.asarray(S.dense_mxm(S.structural_dense(jnp.asarray(D), sr),
                                 jnp.asarray(X), sr))
    m = None if mask_mode == "none" else mask
    d = Descriptor(mask=None if m is None else jnp.asarray(m),
                   complement=mask_mode == "comp",
                   accum=_ACCUM[accum], replace=replace)
    out = jnp.asarray(C) if with_c else None
    got = np.asarray(grb.mxm(A, jnp.asarray(X), sr, d, out=out))
    want = _oracle(raw, C if with_c else None, m, mask_mode == "comp",
                   _ACCUM_NP[accum], replace, sr.identity)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5,
                               err_msg=f"{fmt}/{srname}/{mask_mode}/"
                                       f"accum={accum}/replace={replace}/"
                                       f"C={with_c}")


@pytest.mark.parametrize("fmt", ["dense", "bsr", "ell"])
def test_transpose_descriptor_and_cache(fmt):
    r, c, v, D, X, _, _ = _case(seed=5)
    A = _handle(fmt, r, c, v, D)
    assert A._T is None                      # lazy: nothing built yet
    got = np.asarray(grb.mxm(A, jnp.asarray(np.resize(X, (N, F))),
                             S.PLUS_TIMES, grb.TRANSPOSE_A))
    want = D.T @ np.resize(X, (N, F))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    assert A._T is not None and A.T is A.T   # built once, cached
    assert A.T.T is A                        # round-trip identity
    np.testing.assert_allclose(np.asarray(A.T.to_dense()), D.T, rtol=1e-6)


def test_builder_links_explicit_transpose():
    from repro.graph.graph import GraphBuilder
    r, c, v, D, _, _, _ = _case(seed=7)
    keep = (r < 64) & (c < 64)
    g = GraphBuilder(64).add_edges("R", r[keep], c[keep],
                                   v[keep]).build(fmt="bsr", block=32)
    A = g.relations["R"].A
    assert A._T is not None                  # loader installed the transpose
    assert g.relations["R"].A_T is A.T
    np.testing.assert_allclose(np.asarray(A.T.to_dense()),
                               np.asarray(A.to_dense()).T, rtol=1e-6)


@pytest.mark.parametrize("fmt", ["ell", "delta"])
def test_linked_transpose_pair_freed_by_refcount(fmt):
    """A linked pair is no reference cycle: dropping the handle that linked
    it frees both, storage included, with the cyclic collector off. A twin
    that outlives its handle builds a transpose of its own."""
    import gc
    import weakref
    from repro.core.delta import DeltaMatrix
    r, c, v, D, _, _, _ = _case(seed=11)
    A = _handle("ell", r, c, v, D)
    At = grb.GBMatrix(ELL.from_coo(c, r, v, (M, N)))
    if fmt == "delta":
        ops_ = [("add", 0, 1, 2.0), ("del", int(r[0]), int(c[0]), 0.0)]
        A = grb.GBMatrix(DeltaMatrix.wrap(A.store).apply_ops(ops_))
        At = grb.GBMatrix(DeltaMatrix.wrap(At.store).apply_ops(
            [(k, j, i, w) for k, i, j, w in ops_]))
        assert A.store.patch()[0] is not None
    A.link_transpose(At)
    assert A.T is At and At.T is A
    twin, store = weakref.ref(At), weakref.ref(At.store)
    del At
    gc.disable()
    try:
        del A
        assert twin() is None and store() is None
    finally:
        gc.enable()
    B = _handle("ell", r, c, v, D)
    Bt = B.T
    want = np.asarray(B.to_dense())
    del B
    np.testing.assert_allclose(np.asarray(Bt.T.to_dense()), want, rtol=1e-6)


def test_handle_introspection_and_policy():
    r, c, v, D, _, _, _ = _case(seed=9)
    for fmt, expect_nvals in (("bsr", len(r)), ("ell", len(r)),
                              ("dense", int((D != 0).sum()))):
        A = _handle(fmt, r, c, v, D)
        assert A.shape == (N, M)
        assert A.fmt == fmt
        assert A.nvals == expect_nvals
        assert A.impl in ("xla", "pallas")
    A = _handle("bsr", r, c, v, D)
    assert A.with_impl("auto") is A          # same resolved policy -> same handle
    B = A.with_impl("pallas")
    assert B.impl == "pallas" and B.store is A.store


def test_mxv_vxm_vector_masks():
    r, c, v, D, _, _, _ = _case(seed=11)
    A = _handle("bsr", r, c, v, D)
    x = np.random.default_rng(0).uniform(size=M).astype(np.float32)
    xn = np.random.default_rng(1).uniform(size=N).astype(np.float32)
    mask = (np.arange(N) % 2).astype(np.float32)
    got = np.asarray(grb.mxv(A, jnp.asarray(x), S.PLUS_TIMES,
                             Descriptor(mask=jnp.asarray(mask))))
    np.testing.assert_allclose(got, (D @ x) * mask, rtol=1e-5, atol=1e-5)
    got_v = np.asarray(grb.vxm(jnp.asarray(xn), A, S.PLUS_TIMES))
    np.testing.assert_allclose(got_v, xn @ D, rtol=1e-4, atol=1e-4)


def test_legacy_ops_surface_delegates():
    """ops.mxm kwargs spelling == grb.mxm Descriptor spelling."""
    r, c, v, D, X, mask, C = _case(seed=13)
    A = BSR.from_coo(r, c, v, (N, M), block=32)
    legacy = np.asarray(ops.mxm(A, jnp.asarray(X), S.PLUS_TIMES,
                                mask=jnp.asarray(mask), accum=S.PLUS,
                                C=jnp.asarray(C)))
    uniform = np.asarray(grb.mxm(grb.GBMatrix(A), jnp.asarray(X),
                                 S.PLUS_TIMES,
                                 Descriptor(mask=jnp.asarray(mask),
                                            accum=S.PLUS),
                                 out=jnp.asarray(C)))
    np.testing.assert_allclose(legacy, uniform, rtol=1e-6)


def test_descriptor_with_():
    d = Descriptor(complement=True)
    d2 = d.with_(transpose_a=True)
    assert d2.complement and d2.transpose_a and not d.transpose_a
    assert grb.NULL.mask_only


# -- GBMatrix x GBMatrix (SpGEMM path) vs the same dense oracle ---------------
F2 = 48  # sparse B operand width


def _sparse_case(seed=17):
    rng = np.random.default_rng(seed)
    r, c, v, D, _, _, _ = _case(seed=seed)
    rb = rng.integers(0, M, size=500)
    cb = rng.integers(0, F2, size=500)
    key = rb * F2 + cb
    _, i = np.unique(key, return_index=True)
    rb, cb = rb[i], cb[i]
    vb = rng.uniform(0.5, 2.0, size=len(rb)).astype(np.float32)
    DB = np.zeros((M, F2), np.float32)
    DB[rb, cb] = vb
    mask = (rng.uniform(size=(N, F2)) < 0.5).astype(np.int8)
    C = rng.uniform(0.5, 1.5, size=(N, F2)).astype(np.float32)
    A = grb.GBMatrix(BSR.from_coo(r, c, v, (N, M), block=32))
    B = grb.GBMatrix(BSR.from_coo(rb, cb, vb, (M, F2), block=32))
    return A, B, D, DB, mask, C


@pytest.mark.spgemm
@pytest.mark.parametrize("srname", ["plus_times", "plus_pair"])
@pytest.mark.parametrize("mask_mode", ["none", "mask", "comp"])
@pytest.mark.parametrize("accum", ["none", "plus"])
@pytest.mark.parametrize("replace", [False, True])
@pytest.mark.parametrize("with_c", [False, True])
def test_sparse_sparse_blend_combinations(srname, mask_mode, accum, replace,
                                          with_c):
    """mask x complement x accum x replace x existing-C on GBMatrix x
    GBMatrix operands. out=None keeps C sparse (SpGEMM, mask folded
    block-wise); an existing C blends through the dense finalize — both must
    match the documented rule the dense oracle implements."""
    sr = S.get(srname)
    A, B, D, DB, mask, C = _sparse_case(seed=19)
    raw = np.asarray(S.dense_mxm(jnp.asarray(D), jnp.asarray(DB), sr))
    m = None if mask_mode == "none" else mask
    d = Descriptor(mask=None if m is None else jnp.asarray(m),
                   complement=mask_mode == "comp",
                   accum=_ACCUM[accum], replace=replace)
    out = jnp.asarray(C) if with_c else None
    got = grb.mxm(A, B, sr, d, out=out)
    if isinstance(got, grb.GBMatrix):
        assert not with_c                 # sparse result only when C absent
        assert got.fmt == "bsr"
        got = got.to_dense()
    want = _oracle(raw, C if with_c else None, m, mask_mode == "comp",
                   _ACCUM_NP[accum], replace, sr.identity)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5,
                               err_msg=f"{srname}/{mask_mode}/accum={accum}/"
                                       f"replace={replace}/C={with_c}")


@pytest.mark.spgemm
def test_sparse_sparse_gbmatrix_mask():
    """The mask itself may be a sparse GBMatrix handle (triangle counting's
    C<A> = A (x) A) on both the sparse and dense pipelines."""
    A, B, D, DB, _, _ = _sparse_case(seed=23)
    raw = np.asarray(S.dense_mxm(jnp.asarray(D), jnp.asarray(DB),
                                 S.PLUS_PAIR))
    mask_h = grb.GBMatrix(BSR.from_dense((raw > 1).astype(np.float32),
                                         block=32))
    got = grb.mxm(A, B, S.PLUS_PAIR, Descriptor(mask=mask_h))
    want = np.where(raw > 1, raw, 0.0)
    np.testing.assert_allclose(np.asarray(got.to_dense()), want, rtol=1e-5)
    # same handle-mask through the dense pipeline (dense A)
    Ad = grb.GBMatrix(jnp.asarray(D))
    got_d = grb.mxm(Ad, jnp.asarray(DB), S.PLUS_PAIR,
                    Descriptor(mask=mask_h))
    np.testing.assert_allclose(np.asarray(got_d), want, rtol=1e-5)
