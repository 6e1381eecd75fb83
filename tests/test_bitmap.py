"""Bitmap-packed frontier conformance: packed == unpacked, bit for bit.

The packed boolean route (core.bitmap behind grb, docs/API.md §Bitmap) is
an execution detail — so every test here is differential: force the policy
on and off (`grb.packed_frontiers`) and require *exact* equality on the
golden graph zoo (K4, C5, Petersen, RMAT s6-s8), across formats, mask /
complement / accum blends, transposes, algorithms (BFS / k-hop / WCC), and
both session meshes. The sharded payload claim is pinned two ways: the
words-per-frontier accounting (`bitmap.payload_bytes`) and the all-gather
result bytes read off the lowered HLO of the mesh mxm (>= 8x smaller).

Single-device tests run in tier-1; the mesh grid carries the `distributed`
marker (forced 8-device topology — `make test-dist`, or tier-1's
subprocess wrapper).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bitmap, grb, ops as cops, semiring as S
from repro.core.ell import ELL
from repro.core.grb import Descriptor
from repro.graph.datagen import rmat_graph

pytestmark = pytest.mark.bitmap


# -- graph zoo (the test_sharded_grb golden set) ------------------------------
def _undirected(n, edges):
    D = np.zeros((n, n), np.float32)
    for a, b in edges:
        D[a, b] = D[b, a] = 1.0
    return D


def _graph_dense(name: str) -> np.ndarray:
    if name == "k4":
        return 1.0 - np.eye(4, dtype=np.float32)
    if name == "c5":
        return _undirected(5, [(i, (i + 1) % 5) for i in range(5)])
    if name == "petersen":
        return _undirected(10, [(i, (i + 1) % 5) for i in range(5)]
                           + [(5 + i, 5 + (i + 2) % 5) for i in range(5)]
                           + [(i, 5 + i) for i in range(5)])
    scale = int(name.split("_s")[1])
    g = rmat_graph(scale=scale, edge_factor=8, seed=scale, fmt="ell")
    D = np.asarray(g.relations["KNOWS"].A.to_dense())
    return (D != 0).astype(np.float32)


GRAPHS = ("k4", "c5", "petersen", "rmat_s6", "rmat_s7", "rmat_s8")
_CACHE: dict = {}


def _dense_of(name):
    if name not in _CACHE:
        _CACHE[name] = _graph_dense(name)
    return _CACHE[name]


def _bool_frontier(n, f, seed=0, p=0.2):
    rng = np.random.default_rng(seed)
    return (rng.random((n, f)) < p).astype(np.float32)


F = 40   # deliberately not a multiple of 32: exercises word padding


def _descriptors(n, f, seed):
    M = jnp.asarray(_bool_frontier(n, f, seed=seed + 100, p=0.5))
    out = jnp.asarray(_bool_frontier(n, f, seed=seed + 200, p=0.3))
    return [
        ("null", grb.NULL, None),
        ("mask", Descriptor(mask=M), None),
        ("mask_comp", Descriptor(mask=M, complement=True), None),
        ("transpose", grb.TRANSPOSE_A, None),
        ("mask_T", Descriptor(mask=M, complement=True, transpose_a=True),
         None),
        ("accum_out", Descriptor(mask=M, accum=S.OR), out),
        ("replace", Descriptor(mask=M, replace=True), out),
    ]


# -- pack / unpack primitives -------------------------------------------------
@pytest.mark.parametrize("f", [1, 7, 31, 32, 33, 40, 64, 100])
def test_pack_unpack_roundtrip(f):
    rng = np.random.default_rng(f)
    X = (rng.random((23, f)) < 0.4).astype(np.float32)
    Xw = bitmap.pack(jnp.asarray(X))
    assert Xw.dtype == jnp.uint32
    assert Xw.shape == (23, bitmap.n_words(f))
    np.testing.assert_array_equal(np.asarray(bitmap.unpack(Xw, f)), X)
    # popcount: per-word set bits sum to the frontier's population
    assert int(np.asarray(bitmap.popcount(Xw)).sum()) == int(X.sum())
    np.testing.assert_array_equal(
        np.asarray(bitmap.reduce_or_columns(Xw, f)), X.sum(axis=0))


def test_pack_is_structural_not_boolean():
    # any nonzero packs as 1 — the or_and stored-iff-nonzero convention
    X = np.array([[0.0, 2.5, -3.0, 0.0, 1.0]], np.float32)
    got = np.asarray(bitmap.unpack(bitmap.pack(jnp.asarray(X)), 5))
    np.testing.assert_array_equal(got, (X != 0).astype(np.float32))


def test_word_algebra_matches_set_algebra():
    rng = np.random.default_rng(0)
    A = (rng.random((9, F)) < 0.4).astype(np.float32)
    B = (rng.random((9, F)) < 0.4).astype(np.float32)
    Aw, Bw = bitmap.pack(jnp.asarray(A)), bitmap.pack(jnp.asarray(B))
    for fn, op in [(bitmap.word_or, np.maximum),
                   (bitmap.word_and, lambda a, b: a * b),
                   (bitmap.word_andnot, lambda a, b: a * (1 - b))]:
        np.testing.assert_array_equal(
            np.asarray(bitmap.unpack(fn(Aw, Bw), F)), op(A, B))


def test_nibble_words_sum_carry_free():
    # simulate the transposed-form collective: per-shard 0/1 partials summed
    # across the maximum shard count must saturate back to the exact OR
    rng = np.random.default_rng(1)
    parts = (rng.random((bitmap.NIBBLE_MAX_SHARDS, 6, 24)) < 0.3)
    summed = sum(np.asarray(bitmap.pack_nibbles(jnp.asarray(p)))
                 for p in parts)
    want = parts.any(axis=0)
    got = np.asarray(bitmap.unpack_nibbles(jnp.asarray(summed), 24))
    np.testing.assert_array_equal(got, want)


def test_payload_accounting():
    # the words-per-frontier regression: a packed frontier row is ceil(F/32)
    # uint32 words vs F float32 lanes — >= 8x less wire from F = 8 on
    for f in (8, 32, 40, 64, 256):
        assert bitmap.payload_bytes(100, f, packed=True) == \
            100 * bitmap.n_words(f) * 4
        assert bitmap.payload_reduction(f) >= 8
    assert bitmap.payload_reduction(256) == 32
    assert bitmap.payload_reduction(4) < 8          # why the policy floor


# -- policy -------------------------------------------------------------------
# Counter pins below request `fresh_trace` (conftest): pack_calls has
# trace-time semantics, so without cache isolation a pin can pass vacuously
# against a compilation an earlier test left behind.
def test_trace_time_counters_need_cache_isolation(fresh_trace):
    # the mechanism itself: a jitted caller counts at trace, a jit-cache hit
    # re-runs the op without re-counting, and clearing the caches restores
    # counting — the reason every pin in this file takes `fresh_trace`.
    import jax

    f = jax.jit(lambda x: bitmap.pack(x))
    x = jnp.ones((4, 8), dtype=jnp.float32)
    c0 = bitmap.pack_calls()
    np.asarray(f(x))
    assert bitmap.pack_calls() == c0 + 1, "fresh trace must count"
    np.asarray(f(x))
    assert bitmap.pack_calls() == c0 + 1, \
        "cache hit re-runs without counting — the vacuous-pass mode"
    fresh_trace()
    np.asarray(f(x))
    assert bitmap.pack_calls() == c0 + 2, "isolation restores counting"


def test_policy_width_floor_and_overrides(fresh_trace):
    D = _dense_of("rmat_s6")
    h = grb.GBMatrix.from_dense(D, fmt="ell")
    wide = jnp.asarray(_bool_frontier(D.shape[0], grb.AUTO_PACK_MIN_WIDTH))
    narrow = wide[:, :grb.AUTO_PACK_MIN_WIDTH - 1]
    fresh_trace()
    c0 = bitmap.pack_calls()
    grb.mxm(h, narrow, S.OR_AND)
    assert bitmap.pack_calls() == c0, "below the floor must stay unpacked"
    grb.mxm(h, wide, S.OR_AND)
    assert bitmap.pack_calls() > c0, "at the floor must pack"
    c1 = bitmap.pack_calls()
    with grb.packed_frontiers("off"):
        grb.mxm(h, wide, S.OR_AND)
    assert bitmap.pack_calls() == c1
    with grb.packed_frontiers("on"):
        grb.mxv(h, wide[:, 0], S.OR_AND)        # width-1 forced on
    assert bitmap.pack_calls() > c1
    with pytest.raises(ValueError):
        with grb.packed_frontiers("sideways"):
            pass


def test_policy_skips_bsr_and_other_semirings(fresh_trace):
    D = _dense_of("rmat_s6")
    wide = jnp.asarray(_bool_frontier(D.shape[0], F))
    fresh_trace()
    c0 = bitmap.pack_calls()
    grb.mxm(grb.GBMatrix.from_dense(D, fmt="bsr", block=64), wide, S.OR_AND)
    grb.mxm(grb.GBMatrix.from_dense(D, fmt="ell"), wide, S.PLUS_TIMES)
    grb.mxm(grb.GBMatrix.from_dense(D, fmt="ell"), wide, S.MIN_PLUS)
    assert bitmap.pack_calls() == c0


# -- differential grid: packed vs unpacked, single device ---------------------
@pytest.mark.parametrize("fmt", ["dense", "ell"])
@pytest.mark.parametrize("name", GRAPHS)
def test_mxm_packed_matches_unpacked(name, fmt):
    D = _dense_of(name)
    n = D.shape[0]
    h = grb.GBMatrix.from_dense(D, fmt=fmt)
    X = jnp.asarray(_bool_frontier(n, F, seed=7))
    for dname, d, out in _descriptors(n, F, seed=3):
        with grb.packed_frontiers("off"):
            want = np.asarray(grb.mxm(h, X, S.OR_AND, d, out=out))
        with grb.packed_frontiers("on"):
            got = np.asarray(grb.mxm(h, X, S.OR_AND, d, out=out))
        np.testing.assert_array_equal(got, want,
                                      err_msg=f"{name} {fmt} {dname}")


@pytest.mark.parametrize("name", ["petersen", "rmat_s7"])
def test_mxv_vxm_packed_matches_unpacked(name):
    D = _dense_of(name)
    n = D.shape[0]
    h = grb.GBMatrix.from_dense(D, fmt="ell")
    x = jnp.asarray(_bool_frontier(n, 1, seed=5)[:, 0])
    m = jnp.asarray(_bool_frontier(n, 1, seed=6)[:, 0])
    d = Descriptor(mask=m, complement=True)
    for op in (grb.mxv, grb.vxm):
        args = (h, x) if op is grb.mxv else (x, h)
        with grb.packed_frontiers("off"):
            want = np.asarray(op(*args, S.OR_AND, d))
        with grb.packed_frontiers("on"):
            got = np.asarray(op(*args, S.OR_AND, d))
        np.testing.assert_array_equal(got, want, err_msg=f"{name} {op}")


def test_any_pair_packs_too(fresh_trace):
    D = _dense_of("c5")
    h = grb.GBMatrix.from_dense(D, fmt="ell")
    X = jnp.asarray(_bool_frontier(5, F, seed=9))
    fresh_trace()
    c0 = bitmap.pack_calls()
    with grb.packed_frontiers("off"):
        want = np.asarray(grb.mxm(h, X, S.ANY_PAIR))
    got = np.asarray(grb.mxm(h, X, S.ANY_PAIR))
    assert bitmap.pack_calls() > c0
    np.testing.assert_array_equal(got, want)


# -- the Pallas kernel vs the XLA reference -----------------------------------
@pytest.mark.parametrize("name", ["petersen", "rmat_s6", "rmat_s7"])
def test_bitmap_kernel_interpret_matches_reference(name):
    from repro.kernels import bitmap_mxv
    D = _dense_of(name)
    e = ELL.from_dense(D)
    Xw = bitmap.pack(jnp.asarray(_bool_frontier(D.shape[0], F, seed=2)))
    want = np.asarray(cops.ell_mxm_packed(e, Xw))
    got = np.asarray(bitmap_mxv.ell_mxv_packed(e, Xw, interpret=True))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("slot_chunk,rows_tile", [(8, 8), (16, 16), (512, 8)])
def test_bitmap_kernel_slot_chunks_match_reference(slot_chunk, rows_tile):
    """A hub row spans many slot chunks and whole row tiles hold no edge:
    the clamped chunk stream and the per-row slot counts must still give
    the reference's words."""
    from repro.kernels import bitmap_mxv
    rng = np.random.default_rng(4)
    n = 200
    src = np.concatenate([rng.integers(40, n, 600), np.zeros(150, int),
                          np.full(30, 7)])
    dst = rng.integers(0, n, src.shape[0])
    e = ELL.from_coo(src, dst, None, (n, n))     # rows 8..39 bar 7 are empty
    assert e.max_deg > 4 * slot_chunk or slot_chunk >= e.max_deg
    Xw = bitmap.pack(jnp.asarray(_bool_frontier(n, 70, seed=5, p=0.05)))
    want = np.asarray(cops.ell_mxm_packed(e, Xw))
    got = np.asarray(bitmap_mxv.ell_mxv_packed(
        e, Xw, slot_chunk=slot_chunk, rows_tile=rows_tile, interpret=True))
    np.testing.assert_array_equal(got, want)


# -- algorithms ride the packed path bit-identically --------------------------
def test_khop_bfs_wcc_packed_identical():
    from repro import algorithms as alg
    g = rmat_graph(scale=7, edge_factor=8, seed=0, fmt="ell")
    rel = g.relations["KNOWS"]
    seeds = np.random.default_rng(0).integers(0, g.n, size=64)
    runs = {}
    for mode in ("off", "on"):
        with grb.packed_frontiers(mode):
            runs[mode] = (
                np.asarray(alg.khop_counts(rel, seeds, k=3)),
                np.asarray(alg.bfs_levels(rel, seeds)),
                np.asarray(alg.wcc(rel)))
    for a, b, what in zip(runs["off"], runs["on"],
                          ("khop", "bfs_levels", "wcc")):
        np.testing.assert_array_equal(a, b, err_msg=what)


def test_wcc_labels_are_component_minima():
    # the min-seed closure formulation must reproduce min-label semantics
    D = _dense_of("petersen")                       # one component -> all 0
    h = grb.GBMatrix.from_dense(D, fmt="ell")
    from repro.algorithms.wcc import wcc
    assert np.asarray(wcc(h)).tolist() == [0] * 10
    # two components + an isolate, tiny batch forces multiple closures
    D2 = np.zeros((7, 7), np.float32)
    D2[0, 1] = D2[1, 0] = D2[3, 4] = D2[4, 3] = D2[4, 5] = D2[5, 4] = 1.0
    got = np.asarray(wcc(grb.GBMatrix.from_dense(D2, fmt="ell"),
                         batch=2)).tolist()
    assert got == [0, 0, 2, 3, 3, 3, 6]


# -- sharded: both meshes, packed vs unpacked vs oracle -----------------------
def _sharded_pair(name, mesh):
    D = _dense_of(name)
    h = grb.GBMatrix.from_dense(D, fmt="ell", name=name)
    return h, grb.distribute(h, mesh)


@pytest.mark.distributed
@pytest.mark.parametrize("meshname", ["mesh222", "mesh421"])
@pytest.mark.parametrize("name", GRAPHS)
def test_sharded_packed_matches_unpacked(name, meshname, request):
    mesh = request.getfixturevalue(meshname)
    D = _dense_of(name)
    n = D.shape[0]
    h, sh = _sharded_pair(name, mesh)
    X = jnp.asarray(_bool_frontier(n, F, seed=13))
    for dname, d, out in _descriptors(n, F, seed=17):
        with grb.packed_frontiers("off"):
            want = np.asarray(grb.mxm(sh, X, S.OR_AND, d, out=out))
        with grb.packed_frontiers("on"):
            got = np.asarray(grb.mxm(sh, X, S.OR_AND, d, out=out))
        np.testing.assert_array_equal(got, want,
                                      err_msg=f"{name} {meshname} {dname}")
        oracle = np.asarray(grb.mxm(h, X, S.OR_AND, d, out=out))
        np.testing.assert_array_equal(got, oracle,
                                      err_msg=f"oracle {name} {dname}")


@pytest.mark.distributed
def test_sharded_packed_transposed_scatter(mesh222):
    # no linked transpose -> the nibble-word psum_scatter lowering
    from repro.core.shard import ShardedELL
    D = _dense_of("rmat_s7")
    h = grb.GBMatrix.from_dense(D, fmt="ell")
    sh = grb.GBMatrix(ShardedELL.from_ell(h.store, mesh222))
    assert sh._T is None
    X = jnp.asarray(_bool_frontier(D.shape[0], F, seed=23))
    with grb.packed_frontiers("on"):
        got = np.asarray(grb.mxm(sh, X, S.OR_AND, grb.TRANSPOSE_A))
    want = np.asarray(grb.mxm(h, X, S.OR_AND, grb.TRANSPOSE_A))
    np.testing.assert_array_equal(got, want)


@pytest.mark.distributed
def test_sharded_khop_packed_identical(mesh222, mesh421):
    from repro import algorithms as alg
    g = rmat_graph(scale=7, edge_factor=8, seed=1, fmt="ell")
    rel = g.relations["KNOWS"]
    seeds = np.random.default_rng(3).integers(0, g.n, size=64)
    want = np.asarray(alg.khop_counts(rel, seeds, k=3))
    for mesh in (mesh222, mesh421):
        sh = grb.distribute(rel.A, mesh)
        for mode in ("off", "on"):
            with grb.packed_frontiers(mode):
                got = np.asarray(alg.khop_counts(sh, seeds, k=3))
            np.testing.assert_array_equal(got, want,
                                          err_msg=f"{mesh} {mode}")


@pytest.mark.distributed
@pytest.mark.parametrize("meshname", ["mesh222", "mesh421"])
def test_allgather_payload_reduction_in_hlo(meshname, request):
    """The 8x claim, read off the lowered HLO: the row-form all-gather of a
    packed 256-wide frontier must move >= 8x fewer bytes than the float
    one, and exactly the words-per-frontier accounting predicts."""
    from repro.launch.dryrun import collective_stats
    mesh = request.getfixturevalue(meshname)
    D = _dense_of("rmat_s8")
    n = D.shape[0]
    f = 256
    sh = grb.distribute(grb.GBMatrix.from_dense(D, fmt="ell"), mesh)
    X = jax.ShapeDtypeStruct((n, f), jnp.float32)

    def gather_bytes(mode):
        with grb.packed_frontiers(mode):
            compiled = jax.jit(
                lambda x: grb.mxm(sh, x, S.OR_AND)).lower(X).compile()
        _, kinds = collective_stats(compiled.as_text())
        return kinds["all-gather"]["bytes"]

    unpacked, packed = gather_bytes("off"), gather_bytes("on")
    assert unpacked >= 8 * packed, (unpacked, packed)
    # exact words-per-frontier accounting: same gathered rows, F float32
    # lanes vs ceil(F/32) uint32 words (f=256 divides both paddings evenly)
    assert unpacked == packed * f // bitmap.n_words(f)
