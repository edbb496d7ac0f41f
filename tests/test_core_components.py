"""Unit + property tests for RBC partitioning, RobustPrune, leaf building,
beam search, and metrics."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import hypothesis, st

from repro.core import metrics as _metrics
from repro.core.beam_search import (
    beam_search_batch,
    beam_search_np,
    brute_force_knn,
    medoid,
    recall_at_k,
)
from repro.core.leaf import (
    LeafParams,
    _smallest_k,
    build_leaf_edges,
    leaf_knn_jax,
)
from repro.core.rbc import (
    RBCParams,
    ball_carve,
    binary_partition,
    kmeans_carve,
    leaves_to_padded,
    partition,
    sorting_lsh_partition,
)
from repro.core.robust_prune import robust_prune_mask, robust_prune_np


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(7)
    return rng.standard_normal((2000, 16)).astype(np.float32)


# --------------------------------------------------------------- metrics ---

@pytest.mark.parametrize("metric", ["l2", "mips", "cosine"])
def test_pairwise_matches_naive(metric):
    rng = np.random.default_rng(0)
    a = rng.standard_normal((17, 9)).astype(np.float32)
    b = rng.standard_normal((13, 9)).astype(np.float32)
    got = np.asarray(_metrics.pairwise(jnp.asarray(a), jnp.asarray(b), metric))
    naive = np.zeros((17, 13), dtype=np.float32)
    for i in range(17):
        for j in range(13):
            if metric == "l2":
                naive[i, j] = np.sum((a[i] - b[j]) ** 2)
            elif metric == "mips":
                naive[i, j] = -np.dot(a[i], b[j])
            else:
                naive[i, j] = 1 - np.dot(a[i], b[j]) / (
                    np.linalg.norm(a[i]) * np.linalg.norm(b[j])
                )
    np.testing.assert_allclose(got, naive, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("gemm", ["pairwise", "leaf_knn", "leader_dists",
                                  "sketch"])
def test_distance_gemms_lower_at_full_precision(gemm):
    """The build's f32 distance GEMMs ask for HIGHEST precision: at the
    TPU's default (one bf16 pass) leaf k-NN and leader assignment reorder
    near neighbours.  CPU XLA computes f32 either way, so only the lowered
    program shows the request."""
    from repro.core.leader_assign import leader_dists
    from repro.core.sketch import sketch

    x = jnp.ones((2, 16, 8), jnp.float32)
    fn = {"pairwise": lambda x: _metrics.pairwise(x[0], x[1]),
          "leaf_knn": lambda x: leaf_knn_jax(x, x[..., 0] > 0, k=4),
          "leader_dists": lambda x: leader_dists(x[0], x[1]),
          "sketch": lambda x: sketch(x[0], x[1, :4])}[gemm]
    text = jax.jit(fn).lower(x).as_text()
    assert "dot_general" in text
    assert "precision = [HIGHEST, HIGHEST]" in text, text


# ------------------------------------------------------------------- RBC ---

def _check_cover(leaves, n, c_max):
    seen = np.zeros(n, dtype=bool)
    for b in leaves:
        assert len(b) <= c_max
        seen[b] = True
    assert seen.all(), "every point must land in at least one leaf"


@pytest.mark.parametrize("method", ["rbc", "binary", "kmeans", "sorting_lsh"])
def test_partitioners_cover_all_points(data, method):
    p = RBCParams(c_max=128, c_min=16, p_samp=0.02, fanout=(3, 2), seed=1)
    leaves = partition(data, p, method)
    _check_cover(leaves, data.shape[0], p.c_max)


def test_rbc_fanout_overlap(data):
    p = RBCParams(c_max=128, c_min=16, p_samp=0.02, fanout=(3,), seed=1)
    leaves = ball_carve(data, p)
    total = sum(len(b) for b in leaves)
    # fanout 3 at the top should yield roughly 3x point repeats
    assert total >= 2.0 * data.shape[0]


def test_rbc_deterministic(data):
    p = RBCParams(c_max=128, c_min=16, fanout=(3, 2), seed=42)
    a = ball_carve(data, p)
    b = ball_carve(data, p)
    assert len(a) == len(b)
    for la, lb in zip(a, b):
        np.testing.assert_array_equal(la, lb)


def test_leaves_to_padded_roundtrip():
    leaves = [np.array([0, 5, 3]), np.array([1])]
    padded = leaves_to_padded(leaves, 4)
    assert padded.shape == (2, 4)
    np.testing.assert_array_equal(padded[0], [0, 5, 3, -1])
    np.testing.assert_array_equal(padded[1], [1, -1, -1, -1])


def test_leaves_to_padded_rejects_oversized():
    with pytest.raises(ValueError):
        leaves_to_padded([np.arange(10)], 4)


# ----------------------------------------------------------- RobustPrune ---

@hypothesis.settings(max_examples=30, deadline=None)
@hypothesis.given(
    n_cand=st.integers(3, 24),
    alpha=st.sampled_from([1.0, 1.2, 1.5]),
    r=st.integers(1, 8),
    seed=st.integers(0, 10_000),
)
def test_robust_prune_mask_matches_sequential(n_cand, alpha, r, seed):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((40, 8)).astype(np.float32)
    p_idx = 0
    cand = rng.choice(np.arange(1, 40), size=n_cand, replace=False)
    expect = robust_prune_np(x[p_idx], cand, x, alpha=alpha, r=r, metric="l2")

    d_pc = np.sum((x[cand] - x[p_idx]) ** 2, axis=1).astype(np.float32)
    d_cc = np.sum(
        (x[cand][:, None, :] - x[cand][None, :, :]) ** 2, axis=-1
    ).astype(np.float32)
    keep = robust_prune_mask(
        jnp.asarray(d_pc)[None], jnp.asarray(d_cc)[None],
        jnp.asarray(cand.astype(np.int32))[None], alpha=alpha, max_deg=r,
    )
    got = sorted(cand[np.asarray(keep)[0]].tolist())
    assert got == sorted(expect.tolist())


def test_robust_prune_respects_degree_cap():
    rng = np.random.default_rng(0)
    d_pc = jnp.asarray(rng.uniform(1, 2, (4, 32)).astype(np.float32))
    d_cc = jnp.full((4, 32, 32), 100.0, dtype=jnp.float32)  # nothing dominates
    ids = jnp.broadcast_to(jnp.arange(32, dtype=jnp.int32), (4, 32))
    keep = robust_prune_mask(d_pc, d_cc, ids, alpha=1.2, max_deg=5)
    assert (np.asarray(keep).sum(axis=1) == 5).all()


# ------------------------------------------------------------------ leaf ---

def test_leaf_knn_matches_bruteforce():
    rng = np.random.default_rng(1)
    pts = rng.standard_normal((3, 32, 8)).astype(np.float32)
    valid = np.ones((3, 32), dtype=bool)
    valid[1, 20:] = False  # padded leaf
    ni, nd = leaf_knn_jax(jnp.asarray(pts), jnp.asarray(valid), k=3, metric="l2")
    ni, nd = np.asarray(ni), np.asarray(nd)
    for b in range(3):
        m = valid[b]
        d = np.sum((pts[b][:, None] - pts[b][None]) ** 2, axis=-1)
        d[~m] = np.inf
        d[:, ~m] = np.inf
        np.fill_diagonal(d, np.inf)
        for i in range(32):
            if not m[i]:
                assert (ni[b, i] == -1).all()
                continue
            expect = set(np.argsort(d[i], kind="stable")[:3].tolist())
            assert set(ni[b, i].tolist()) == expect


def _top_k_smallest(d, k):
    """The selection `leaf_knn_jax` made with `lax.top_k` before
    `_smallest_k`: the reference the reduction must equal bit for bit."""
    neg, idx = jax.lax.top_k(-d, k)
    return -neg, idx


def _top_k_leaf_knn(pts, valid, k):
    d = jax.vmap(lambda a: _metrics.pairwise(a, a, "l2"))(pts)
    c = pts.shape[1]
    mask = valid[:, None, :] & valid[:, :, None] & ~jnp.eye(c, dtype=bool)[None]
    nd, idx = _top_k_smallest(jnp.where(mask, d, jnp.inf), k)
    ok = jnp.isfinite(nd)
    return jnp.where(ok, idx, -1), jnp.where(ok, nd, jnp.inf)


@pytest.mark.parametrize("k", [1, 2, 3, 8, 9])
def test_smallest_k_equals_top_k(k):
    """Integer-valued points tie often; leaves 1-4 are padded, leaf 5 has
    one valid point and leaf 6 none, so their rows are all +inf."""
    rng = np.random.default_rng(k)
    pts = rng.integers(0, 3, (8, 40, 6)).astype(np.float32)
    valid = np.ones((8, 40), dtype=bool)
    for b, n_valid in enumerate([37, 20, 9, k, 1, 0], start=1):
        valid[b, n_valid:] = False
    pj, vj = jnp.asarray(pts), jnp.asarray(valid)

    d = jax.vmap(lambda a: _metrics.pairwise(a, a, "l2"))(pj)
    d = jnp.where(vj[:, None, :] & vj[:, :, None], d, jnp.inf)
    for got, want in zip(jax.jit(_smallest_k, static_argnums=1)(d, k),
                         _top_k_smallest(d, k)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    for got, want in zip(leaf_knn_jax(pj, vj, k=k),
                         _top_k_leaf_knn(pj, vj, k)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_streamed_build_equals_top_k_selection():
    """The streamed build through `leaf_knn_jax` gives the graph the
    `lax.top_k` selection gave, plugged in through the `knn_fn` hook."""
    from repro.core import pipnn

    rng = np.random.default_rng(15)
    x = rng.integers(-4, 5, (2048, 16)).astype(np.float32)
    params = pipnn.PiPNNParams(
        rbc=RBCParams(c_max=128, c_min=16, fanout=(3,)),
        leaf=LeafParams(k=2, leaf_chunk=4), l_max=32, max_deg=16, seed=3)
    got = pipnn.build(x, params)
    want = pipnn.build(x, params, knn_fn=functools.partial(_top_k_leaf_knn,
                                                           k=2))
    assert got.stats["streaming"] and want.stats["streaming"]
    np.testing.assert_array_equal(got.graph, want.graph)
    np.testing.assert_array_equal(got.dists, want.dists)
    assert got.start == want.start


def test_bidirected_contains_both_directions(data):
    p = RBCParams(c_max=128, c_min=16, fanout=(2,), seed=0)
    leaves = ball_carve(data, p)
    padded = leaves_to_padded(leaves, p.c_max)
    ed = build_leaf_edges(data, padded, LeafParams(method="bidirected", k=2))
    pairs = set(zip(ed.src[ed.valid()].tolist(), ed.dst[ed.valid()].tolist()))
    rev = {(b, a) for a, b in pairs}
    assert pairs == rev


@pytest.mark.parametrize("method", ["directed", "inverted", "mst", "robust_prune"])
def test_leaf_methods_produce_edges(data, method):
    p = RBCParams(c_max=128, c_min=16, fanout=(2,), seed=0)
    leaves = ball_carve(data, p)
    padded = leaves_to_padded(leaves, p.c_max)
    ed = build_leaf_edges(
        data, padded, LeafParams(method=method, k=2, max_deg=16)
    )
    v = ed.valid()
    assert v.sum() > data.shape[0], method
    assert (ed.dst[v] >= 0).all()
    assert np.isfinite(ed.dist[v]).all()
    assert (ed.src[v] != ed.dst[v]).all(), "no self loops"


# ----------------------------------------------------------- beam search ---

def test_beam_search_np_finds_exact_on_full_graph():
    rng = np.random.default_rng(2)
    x = rng.standard_normal((200, 8)).astype(np.float32)
    # complete-ish graph: 32-NN adjacency
    truth = brute_force_knn(x, x, 33)
    graph = truth[:, 1:33].astype(np.int32)
    q = rng.standard_normal((20, 8)).astype(np.float32)
    gt = brute_force_knn(x, q, 10)
    hits = 0
    for i in range(20):
        ids, _, _ = beam_search_np(graph, x, q[i], start=medoid(x), beam=40)
        hits += len(set(ids[:10].tolist()) & set(gt[i].tolist()))
    assert hits / 200 > 0.95


def test_beam_search_batch_agrees_with_np():
    rng = np.random.default_rng(4)
    x = rng.standard_normal((300, 8)).astype(np.float32)
    truth = brute_force_knn(x, x, 17)
    graph = truth[:, 1:17].astype(np.int32)
    q = rng.standard_normal((10, 8)).astype(np.float32)
    start = medoid(x)
    ids_b, _ = beam_search_batch(
        jnp.asarray(graph), jnp.asarray(x), jnp.asarray(q),
        start=start, beam=24, iters=28,
    )
    for i in range(10):
        ids_n, _, _ = beam_search_np(graph, x, q[i], start=start, beam=24)
        got = set(np.asarray(ids_b)[i, :10].tolist())
        expect = set(ids_n[:10].tolist())
        assert len(got & expect) >= 8, f"query {i}: {got} vs {expect}"


def test_recall_at_k():
    f = np.array([[1, 2, 3], [4, 5, 6]])
    t = np.array([[1, 2, 9], [4, 5, 6]])
    assert recall_at_k(f, t, 3) == pytest.approx(5 / 6)
