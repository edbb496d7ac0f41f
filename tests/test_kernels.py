"""Per-kernel validation: Pallas (interpret=True on CPU) vs pure-jnp oracles,
swept over shapes and dtypes, plus hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import hypothesis, st

from repro.kernels import ops, ref

INTERP = True  # CPU container: kernels execute in interpret mode


# ------------------------------------------------------------- distance ---

SHAPES = [
    (1, 8, 8, 4),       # tiny, heavy padding
    (2, 128, 128, 32),  # exact tiles
    (3, 100, 200, 17),  # ragged everything
    (1, 257, 129, 128), # off-by-one over tiles
]


@pytest.mark.parametrize("b,m,n,d", SHAPES)
@pytest.mark.parametrize("metric", ["l2", "mips", "cosine"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pairwise_distance_matches_ref(b, m, n, d, metric, dtype):
    rng = np.random.default_rng(hash((b, m, n, d, metric)) % 2**31)
    a = jnp.asarray(rng.standard_normal((b, m, d)), dtype=dtype)
    bb = jnp.asarray(rng.standard_normal((b, n, d)), dtype=dtype)
    got = ops.pairwise_distance(a, bb, metric=metric, interpret=INTERP)
    want = ref.pairwise_distance_ref(a, bb, metric=metric)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("b,m,n,d", [(1, 16, 16, 8), (2, 130, 70, 100)])
def test_pairwise_distance_int8_exact(b, m, n, d):
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.integers(-128, 128, (b, m, d)), dtype=jnp.int8)
    bb = jnp.asarray(rng.integers(-128, 128, (b, n, d)), dtype=jnp.int8)
    got = ops.pairwise_distance_int8(a, bb, interpret=INTERP)
    want = ref.pairwise_distance_int8_ref(a, bb)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_pairwise_distance_int8_rejects_float():
    a = jnp.zeros((1, 8, 8), jnp.float32)
    with pytest.raises(TypeError):
        ops.pairwise_distance_int8(a, a, interpret=INTERP)


# -------------------------------------------------------------- FlashKNN ---

@pytest.mark.parametrize("c,d,k", [(32, 8, 2), (128, 32, 4), (200, 64, 3),
                                   (260, 16, 8)])
@pytest.mark.parametrize("metric", ["l2", "mips"])
def test_leaf_topk_matches_ref(c, d, k, metric):
    rng = np.random.default_rng(hash((c, d, k)) % 2**31)
    pts = jnp.asarray(rng.standard_normal((2, c, d)), dtype=jnp.float32)
    valid = np.ones((2, c), dtype=bool)
    valid[0, c // 2 :] = False  # one heavily padded leaf
    valid[1, ::7] = False       # scattered invalids
    vj = jnp.asarray(valid)
    gi, gv = ops.leaf_topk(pts, vj, k=k, metric=metric, interpret=INTERP)
    wi, wv = ref.leaf_topk_ref(pts, vj, k=k, metric=metric)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(wv), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))


def test_leaf_topk_duplicate_points_tiebreak():
    """Duplicate points => zero distances; ties must break identically."""
    pts = jnp.zeros((1, 64, 8), dtype=jnp.float32)
    valid = jnp.ones((1, 64), dtype=bool)
    gi, gv = ops.leaf_topk(pts, valid, k=3, interpret=INTERP)
    wi, wv = ref.leaf_topk_ref(pts, valid, k=3)
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
    assert (np.asarray(gv) == 0).all()


@hypothesis.settings(max_examples=15, deadline=None)
@hypothesis.given(
    c=st.integers(4, 80),
    d=st.integers(2, 40),
    k=st.integers(1, 6),
    seed=st.integers(0, 2**31 - 1),
)
def test_leaf_topk_property(c, d, k, seed):
    rng = np.random.default_rng(seed)
    pts = jnp.asarray(rng.standard_normal((1, c, d)), dtype=jnp.float32)
    valid = jnp.asarray(rng.random((1, c)) > 0.2)
    gi, gv = ops.leaf_topk(pts, valid, k=k, interpret=INTERP)
    wi, wv = ref.leaf_topk_ref(pts, valid, k=k)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(wv), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))


# ----------------------------------------------------------- rowwise topk ---

@pytest.mark.parametrize("b,m,n,k", [(1, 8, 8, 2), (2, 128, 300, 4),
                                     (1, 100, 1000, 8)])
def test_rowwise_topk_matches_ref(b, m, n, k):
    rng = np.random.default_rng(hash((b, m, n, k)) % 2**31)
    d = rng.standard_normal((b, m, n)).astype(np.float32)
    d[rng.random((b, m, n)) < 0.1] = np.inf  # masked entries
    dj = jnp.asarray(d)
    gi, gv = ops.rowwise_topk(dj, k=k, interpret=INTERP)
    wi, wv = ref.rowwise_topk_ref(dj, k=k)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(wv), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))


def test_rowwise_topk_all_masked_row():
    d = jnp.full((1, 4, 16), jnp.inf)
    gi, gv = ops.rowwise_topk(d, k=3, interpret=INTERP)
    assert (np.asarray(gi) == -1).all()
    assert np.isinf(np.asarray(gv)).all()


# -------------------------------------------------------------- edge hash ---

@pytest.mark.parametrize("e,m", [(1, 12), (128, 12), (1000, 16), (257, 8)])
def test_edge_hashes_match_sketch_module(e, m):
    from repro.core import sketch as _sketch

    rng = np.random.default_rng(e * 31 + m)
    s = jnp.asarray(rng.standard_normal((e, m)), dtype=jnp.float32)
    t = jnp.asarray(rng.standard_normal((e, m)), dtype=jnp.float32)
    got = ops.edge_hashes(s, t, interpret=INTERP)
    want = _sketch.hash_from_sketches(t, s)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n,l", [(5, 8), (40, 64), (17, 128)])
def test_merge_sorted_reservoirs_matches_ref(n, l):
    """The rank-based row merge (one row at a time on [l, l] tiles) equals
    the sort-based oracle on two valid reservoirs, ids/hashes/dists."""
    from repro.core.hashprune import hashprune_batch
    from repro.kernels.segmented_merge import merge_sorted_reservoirs

    def reservoir(seed):
        r = np.random.default_rng(seed)
        ids = jnp.asarray(r.integers(0, 4 * l, (n, 2 * l)), jnp.int32)
        hs = jnp.asarray(r.integers(0, 64, (n, 2 * l)), jnp.int32)
        ds = jnp.asarray(r.random((n, 2 * l)), jnp.float32)
        return hashprune_batch(ids, hs, ds, l_max=l)

    a, b = reservoir(n), reservoir(n + l)
    got = merge_sorted_reservoirs(*a, *b, interpret=INTERP)
    want = ref.merge_sorted_reservoirs_ref(*a, *b)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_edge_hash_range():
    rng = np.random.default_rng(0)
    s = jnp.asarray(rng.standard_normal((500, 12)), dtype=jnp.float32)
    t = jnp.asarray(rng.standard_normal((500, 12)), dtype=jnp.float32)
    h = np.asarray(ops.edge_hashes(s, t, interpret=INTERP))
    assert (h >= 0).all() and (h < 2**12).all()


# ------------------------------------------------------- gather-distance ---

GD_SHAPES = [
    (60, 4, 3, 5),        # tiny, heavy padding everywhere
    (300, 32, 16, 128),   # serving-shaped: E*R = 128 lane-exact
    (257, 17, 9, 65),     # ragged everything
    (128, 128, 8, 256),   # exact tiles, wide candidate block
]


@pytest.mark.parametrize("n,d,q,c", GD_SHAPES)
@pytest.mark.parametrize("metric", ["l2", "mips", "cosine"])
def test_gather_distance_matches_ref(n, d, q, c, metric):
    from repro.core.metrics import point_norms
    from repro.kernels.gather_distance import gather_distance

    rng = np.random.default_rng(hash((n, d, q, c, metric)) % 2**31)
    x = jnp.asarray(rng.standard_normal((n, d)), dtype=jnp.float32)
    qs = jnp.asarray(rng.standard_normal((q, d)), dtype=jnp.float32)
    ids = jnp.asarray(rng.integers(-1, n, (q, c)), dtype=jnp.int32)
    norms = point_norms(x, metric)
    got = gather_distance(x, norms, qs, ids, metric=metric, interpret=INTERP)
    want = ref.gather_distance_ref(x, norms, qs, ids, metric=metric)
    g, w = np.asarray(got), np.asarray(want)
    mask = np.asarray(ids) >= 0
    assert (np.isinf(g) == ~mask).all(), "-1 ids must map to +inf"
    np.testing.assert_allclose(g[mask], w[mask], rtol=1e-5, atol=1e-5)


def test_gather_distance_downcast_points():
    """bf16 points: the norm half stays exact (precomputed f32), only the
    inner product is rounded — kernel and oracle agree within bf16 tol."""
    from repro.core.metrics import point_norms
    from repro.kernels.gather_distance import gather_distance

    rng = np.random.default_rng(0)
    x32 = jnp.asarray(rng.standard_normal((200, 24)), dtype=jnp.float32)
    norms = point_norms(x32, "l2")       # BEFORE the downcast
    x16 = x32.astype(jnp.bfloat16)
    qs = jnp.asarray(rng.standard_normal((7, 24)), dtype=jnp.float32)
    ids = jnp.asarray(rng.integers(0, 200, (7, 33)), dtype=jnp.int32)
    got = gather_distance(x16, norms, qs, ids, metric="l2", interpret=INTERP)
    want = ref.gather_distance_ref(x16, norms, qs, ids, metric="l2")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-2, atol=3e-2)
    exact = ref.gather_distance_ref(x32, norms, qs, ids, metric="l2")
    np.testing.assert_allclose(np.asarray(got), np.asarray(exact),
                               rtol=0.15, atol=0.3)


def test_gather_distance_fits_vmem_budget():
    from repro.kernels.gather_distance import fits_vmem

    assert fits_vmem(jnp.zeros((1000, 32), jnp.float32))
    assert not fits_vmem(jnp.zeros((1 << 20, 128), jnp.float32))


def test_fits_vmem_int8_headroom():
    """The budget check is itemsize-aware: an int8 packing (plus its f32
    scales) fits where the same-shape f32 block does not."""
    from repro.kernels.gather_distance import fits_vmem

    n, d = 40960, 128           # f32: 20 MB > budget; int8 + scales: ~5.2 MB
    assert not fits_vmem(jnp.zeros((n, d), jnp.float32))
    assert fits_vmem(jnp.zeros((n, d), jnp.int8), jnp.zeros((n,), jnp.float32))
    # the extras count against the budget too
    assert not fits_vmem(jnp.zeros((n, d), jnp.int8),
                         jnp.zeros((n, d), jnp.float32))


# ------------------------------------------- int8 gather-distance (serving) ---

def _quantized(rng, n, d):
    x32 = jnp.asarray(rng.standard_normal((n, d)), dtype=jnp.float32)
    x8, scl = ref.quantize_symmetric(x32)
    return x32, x8, scl


def test_quantize_symmetric_basics():
    rng = np.random.default_rng(3)
    x32, x8, scl = _quantized(rng, 50, 19)
    assert x8.dtype == jnp.int8 and scl.dtype == jnp.float32
    assert (np.abs(np.asarray(x8)) <= 127).all()
    assert (np.asarray(scl) > 0).all()
    # every row's max-|value| element hits +-127 exactly
    assert (np.max(np.abs(np.asarray(x8)), axis=-1) == 127).all()
    # dequantization error bounded by half a step per component
    err = np.abs(np.asarray(x8) * np.asarray(scl)[:, None] - np.asarray(x32))
    assert (err <= 0.5 * np.asarray(scl)[:, None] + 1e-7).all()


def test_quantize_symmetric_zero_rows():
    """Zero rows quantize to zeros with a tiny positive scale (no NaN/inf
    from the 0/0)."""
    x = jnp.zeros((4, 8), jnp.float32)
    q, s = ref.quantize_symmetric(x)
    assert (np.asarray(q) == 0).all()
    assert (np.asarray(s) > 0).all() and np.isfinite(np.asarray(s)).all()


@pytest.mark.parametrize("n,d,q,c", GD_SHAPES)
@pytest.mark.parametrize("metric", ["l2", "mips", "cosine"])
def test_gather_distance_int8_matches_ref_bitexact(n, d, q, c, metric):
    """The quantized Pallas kernel (interpret mode) must agree with the
    jnp oracle BIT-FOR-BIT: the int8 x int8 -> int32 inner product is
    exact, the quantization is the shared order-independent scheme, and
    every f32 op is written in matching order on both sides."""
    from repro.core.metrics import point_norms
    from repro.kernels.gather_distance import gather_distance_int8

    rng = np.random.default_rng(hash((n, d, q, c, metric, 8)) % 2**31)
    x32, x8, scl = _quantized(rng, n, d)
    qs = jnp.asarray(rng.standard_normal((q, d)), dtype=jnp.float32)
    ids = jnp.asarray(rng.integers(-1, n, (q, c)), dtype=jnp.int32)
    norms = point_norms(x32, metric)          # EXACT, pre-quantization
    qn = point_norms(qs, metric)   # query norm terms: same mapping
    got = gather_distance_int8(x8, scl, norms, qs, qn, ids, metric=metric,
                               interpret=INTERP)
    want = ref.gather_distance_int8_ref(x8, scl, norms, qs, qn, ids,
                                        metric=metric)
    g = np.asarray(got)
    assert (np.isinf(g) == (np.asarray(ids) < 0)).all()
    np.testing.assert_array_equal(g, np.asarray(want))


@pytest.mark.parametrize("metric", ["l2", "mips", "cosine"])
def test_gather_distance_int8_close_to_f32(metric):
    """Quantized distances approximate the exact f32 block: the norm
    halves are exact, so the error is the rescaled int8 inner-product
    rounding only."""
    from repro.core.metrics import point_norms
    from repro.kernels.gather_distance import gather_distance_int8

    rng = np.random.default_rng(11)
    x32, x8, scl = _quantized(rng, 300, 24)
    qs = jnp.asarray(rng.standard_normal((9, 24)), dtype=jnp.float32)
    ids = jnp.asarray(rng.integers(0, 300, (9, 40)), dtype=jnp.int32)
    norms = point_norms(x32, metric)
    qn = point_norms(qs, metric)   # query norm terms: same mapping
    got = np.asarray(gather_distance_int8(x8, scl, norms, qs, qn, ids,
                                          metric=metric, interpret=INTERP))
    exact = np.asarray(ref.gather_distance_ref(x32, norms, qs, ids,
                                               metric=metric))
    # the quantization error is ABSOLUTE in the inner product (half a step
    # per component), so near-zero mips values need the atol term
    np.testing.assert_allclose(got, exact, rtol=0.05, atol=0.2)


def test_gather_distance_int8_degenerate_scales():
    """Zero vectors and constant datasets: tiny clamped scales must not
    produce NaN/inf in valid entries, and kernel == oracle still."""
    from repro.core.metrics import point_norms
    from repro.kernels.gather_distance import gather_distance_int8

    rng = np.random.default_rng(0)
    for x32 in (jnp.zeros((40, 8), jnp.float32),               # all zero
                jnp.full((40, 8), 2.25, jnp.float32),          # constant
                jnp.zeros((40, 8), jnp.float32).at[7:].set(-1.5)):
        x8, scl = ref.quantize_symmetric(x32)
        qs = jnp.asarray(rng.standard_normal((5, 8)), dtype=jnp.float32)
        ids = jnp.asarray(rng.integers(-1, 40, (5, 11)), dtype=jnp.int32)
        for metric in ("l2", "mips", "cosine"):
            norms = point_norms(x32, metric)
            qn = point_norms(qs, metric)   # query norm terms: same mapping
            got = np.asarray(gather_distance_int8(
                x8, scl, norms, qs, qn, ids, metric=metric, interpret=INTERP))
            want = np.asarray(ref.gather_distance_int8_ref(
                x8, scl, norms, qs, qn, ids, metric=metric))
            np.testing.assert_array_equal(got, want)
            valid = np.asarray(ids) >= 0
            assert np.isfinite(got[valid]).all()


def test_gather_distance_int8_rejects_float_points():
    from repro.kernels.gather_distance import gather_distance_int8

    x = jnp.zeros((16, 8), jnp.float32)
    aux = jnp.zeros((16,), jnp.float32)
    qs = jnp.zeros((2, 8), jnp.float32)
    ids = jnp.zeros((2, 4), jnp.int32)
    with pytest.raises(TypeError):
        gather_distance_int8(x, aux, aux, qs, jnp.zeros((2,), jnp.float32),
                             ids, interpret=INTERP)


# ------------------------------------- HBM-streaming gather-distance ---

@pytest.mark.parametrize("n,d,q,c", GD_SHAPES)
@pytest.mark.parametrize("metric", ["l2", "mips", "cosine"])
def test_gather_distance_hbm_matches_ref_bitexact(n, d, q, c, metric):
    """The HBM-streaming kernel (points stay in HBM, neighbor rows DMA'd
    into VMEM scratch) must agree with its shape-mirrored oracle
    BIT-FOR-BIT: both sides reduce the same lane-padded extent in the
    same elementwise order, and the norm halves are shared f32 data."""
    from repro.core.metrics import point_norms
    from repro.kernels.gather_distance import gather_distance_hbm

    rng = np.random.default_rng(hash((n, d, q, c, metric, 77)) % 2**31)
    x = jnp.asarray(rng.standard_normal((n, d)), dtype=jnp.float32)
    qs = jnp.asarray(rng.standard_normal((q, d)), dtype=jnp.float32)
    ids = jnp.asarray(rng.integers(-1, n, (q, c)), dtype=jnp.int32)
    norms = point_norms(x, metric)
    got = gather_distance_hbm(x, norms, qs, ids, metric=metric,
                              interpret=INTERP)
    want = ref.gather_distance_hbm_ref(x, norms, qs, ids, metric=metric)
    g = np.asarray(got)
    assert (np.isinf(g) == (np.asarray(ids) < 0)).all()
    np.testing.assert_array_equal(g, np.asarray(want))


@pytest.mark.parametrize("n,d,q,c", GD_SHAPES)
@pytest.mark.parametrize("metric", ["l2", "mips", "cosine"])
def test_gather_distance_hbm_close_to_vmem_kernel(n, d, q, c, metric):
    """Streaming vs VMEM-resident kernel on the same inputs: different
    reduction strategies, same distances to f32 tolerance — an oversized
    shard can switch paths without a recall cliff."""
    from repro.core.metrics import point_norms
    from repro.kernels.gather_distance import (gather_distance,
                                               gather_distance_hbm)

    rng = np.random.default_rng(hash((n, d, q, c, metric, 78)) % 2**31)
    x = jnp.asarray(rng.standard_normal((n, d)), dtype=jnp.float32)
    qs = jnp.asarray(rng.standard_normal((q, d)), dtype=jnp.float32)
    ids = jnp.asarray(rng.integers(-1, n, (q, c)), dtype=jnp.int32)
    norms = point_norms(x, metric)
    a = np.asarray(gather_distance_hbm(x, norms, qs, ids, metric=metric,
                                       interpret=INTERP))
    b = np.asarray(gather_distance(x, norms, qs, ids, metric=metric,
                                   interpret=INTERP))
    mask = np.asarray(ids) >= 0
    np.testing.assert_allclose(a[mask], b[mask], rtol=1e-5, atol=1e-5)


def test_gather_distance_hbm_downcast_points():
    """bf16 points stream bit-identically too: the scratch buffer keeps
    the points dtype and both sides upcast row-wise in the same order."""
    from repro.core.metrics import point_norms
    from repro.kernels.gather_distance import gather_distance_hbm

    rng = np.random.default_rng(21)
    x32 = jnp.asarray(rng.standard_normal((150, 24)), dtype=jnp.float32)
    norms = point_norms(x32, "l2")       # BEFORE the downcast
    x16 = x32.astype(jnp.bfloat16)
    qs = jnp.asarray(rng.standard_normal((6, 24)), dtype=jnp.float32)
    ids = jnp.asarray(rng.integers(-1, 150, (6, 18)), dtype=jnp.int32)
    got = gather_distance_hbm(x16, norms, qs, ids, metric="l2",
                              interpret=INTERP)
    want = ref.gather_distance_hbm_ref(x16, norms, qs, ids, metric="l2")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n,d,q,c", GD_SHAPES)
@pytest.mark.parametrize("metric", ["l2", "mips", "cosine"])
def test_gather_distance_int8_hbm_matches_ref_bitexact(n, d, q, c, metric):
    """The int8 streaming kernel shares the VMEM kernel's oracle: the
    int32 accumulation is order-free and every f32 op is elementwise in
    matching order, so ``gather_distance_int8_ref`` is bit-exact for
    BOTH kernels."""
    from repro.core.metrics import point_norms
    from repro.kernels.gather_distance import gather_distance_int8_hbm

    rng = np.random.default_rng(hash((n, d, q, c, metric, 79)) % 2**31)
    x32, x8, scl = _quantized(rng, n, d)
    qs = jnp.asarray(rng.standard_normal((q, d)), dtype=jnp.float32)
    ids = jnp.asarray(rng.integers(-1, n, (q, c)), dtype=jnp.int32)
    norms = point_norms(x32, metric)          # EXACT, pre-quantization
    qn = point_norms(qs, metric)
    got = gather_distance_int8_hbm(x8, scl, norms, qs, qn, ids,
                                   metric=metric, interpret=INTERP)
    want = ref.gather_distance_int8_ref(x8, scl, norms, qs, qn, ids,
                                        metric=metric)
    g = np.asarray(got)
    assert (np.isinf(g) == (np.asarray(ids) < 0)).all()
    np.testing.assert_array_equal(g, np.asarray(want))


def test_gather_distance_int8_hbm_rejects_float_points():
    from repro.kernels.gather_distance import gather_distance_int8_hbm

    x = jnp.zeros((16, 8), jnp.float32)
    aux = jnp.zeros((16,), jnp.float32)
    qs = jnp.zeros((2, 8), jnp.float32)
    ids = jnp.zeros((2, 4), jnp.int32)
    with pytest.raises(TypeError):
        gather_distance_int8_hbm(x, aux, aux, qs,
                                 jnp.zeros((2,), jnp.float32), ids,
                                 interpret=INTERP)


def test_gather_distance_hbm_beyond_vmem_budget():
    """The whole point of the streaming path: a points block the VMEM
    budget rejects still serves bit-exactly through the HBM kernel."""
    from repro.core.metrics import point_norms
    from repro.kernels.gather_distance import fits_vmem, gather_distance_hbm

    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((2048, 32)), dtype=jnp.float32)
    budget = 64 * 1024                        # 256 KB block >> 64 KB budget
    assert not fits_vmem(x, budget=budget)
    qs = jnp.asarray(rng.standard_normal((4, 32)), dtype=jnp.float32)
    ids = jnp.asarray(rng.integers(-1, 2048, (4, 24)), dtype=jnp.int32)
    norms = point_norms(x, "l2")
    got = gather_distance_hbm(x, norms, qs, ids, metric="l2",
                              interpret=INTERP)
    want = ref.gather_distance_hbm_ref(x, norms, qs, ids, metric="l2")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_vmem_points_budget_env_override(monkeypatch):
    """`PIPNN_VMEM_POINTS_BUDGET` reconfigures the budget every
    ``fits_vmem`` call reads; an explicit ``budget=`` beats the env."""
    from repro.kernels.gather_distance import fits_vmem, vmem_points_budget

    x = jnp.zeros((1000, 32), jnp.float32)    # 128 KB
    assert fits_vmem(x)                       # default 8 MiB
    monkeypatch.setenv("PIPNN_VMEM_POINTS_BUDGET", str(64 * 1024))
    assert vmem_points_budget() == 64 * 1024
    assert not fits_vmem(x)
    assert fits_vmem(x, budget=1 << 23)       # explicit beats env
    monkeypatch.delenv("PIPNN_VMEM_POINTS_BUDGET")
    assert fits_vmem(x)


# ----------------------------------------------- kernel-powered PiPNN build ---

def test_full_build_with_flashknn_matches_jax_path():
    """The fused kernel must produce the same index as the pure-JAX path."""
    from repro.core import pipnn
    from repro.core.leaf import LeafParams
    from repro.core.pipnn import PiPNNParams
    from repro.core.rbc import RBCParams

    rng = np.random.default_rng(5)
    x = rng.standard_normal((1500, 16)).astype(np.float32)
    params = PiPNNParams(
        rbc=RBCParams(c_max=128, c_min=16, fanout=(3,)),
        leaf=LeafParams(k=2, leaf_chunk=4),
        l_max=32, max_deg=16, seed=1,
    )
    i_jax = pipnn.build(x, params)
    i_krn = pipnn.build(x, params, knn_fn=ops.make_knn_fn(2, "l2", INTERP))
    np.testing.assert_array_equal(i_jax.graph, i_krn.graph)
