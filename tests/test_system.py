"""End-to-end behaviour tests for the PiPNN system (build -> query -> recall),
determinism (Appendix A.8), and the downstream k-NN-graph task."""
import numpy as np
import pytest

from repro.core import pipnn
from repro.core.beam_search import beam_search_np, brute_force_knn, recall_at_k
from repro.core.knn_graph import knn_graph_pipnn, knn_graph_recall
from repro.core.leaf import LeafParams
from repro.core.pipnn import PiPNNParams
from repro.core.rbc import RBCParams


@pytest.fixture(scope="module")
def dataset():
    rng = np.random.default_rng(11)
    return rng.standard_normal((4000, 24)).astype(np.float32)


@pytest.fixture(scope="module")
def params():
    return PiPNNParams(
        rbc=RBCParams(c_max=256, c_min=32, p_samp=0.02, fanout=(4, 2)),
        leaf=LeafParams(k=2, leaf_chunk=8),
        hash_bits=12,
        l_max=64,
        max_deg=32,
        seed=3,
    )


@pytest.fixture(scope="module")
def index(dataset, params):
    return pipnn.build(dataset, params)


def test_build_shapes_and_sanity(index, dataset, params):
    n = dataset.shape[0]
    assert index.graph.shape == (n, params.max_deg)
    assert index.dists.shape == (n, params.max_deg)
    v = index.graph >= 0
    assert v.any(axis=1).all(), "every point needs at least one neighbor"
    assert np.isfinite(index.dists[v]).all()
    # no self loops
    rows = np.broadcast_to(np.arange(n)[:, None], index.graph.shape)
    assert (index.graph[v] != rows[v]).all()
    assert 0 <= index.start < n


def test_recall_meets_bar(index, dataset):
    """10@10 recall (the paper's metric) on held-in queries, modest beam."""
    q = dataset[:200]
    truth = brute_force_knn(dataset, q, 11)
    t = np.array([row[row != i][:10] for i, row in enumerate(truth)])
    found = pipnn.search(index, dataset, q, k=11, beam=64)
    f = np.array([row[row != i][:10] for i, row in enumerate(found)])
    r = recall_at_k(f, t, 10)
    assert r > 0.9, f"recall {r}"


def test_deterministic_rebuild(dataset, params, index):
    """Appendix A.8: fixed seed => bit-identical index."""
    again = pipnn.build(dataset, params)
    np.testing.assert_array_equal(index.graph, again.graph)
    np.testing.assert_array_equal(index.dists, again.dists)
    assert index.start == again.start


def test_replicas_add_quality(dataset, params):
    """Extra replica (Sec. 5.2) must not hurt candidate coverage."""
    p1 = params.with_(rbc=params.rbc)
    import dataclasses
    p2 = params.with_(rbc=dataclasses.replace(params.rbc, replicas=2))
    i1 = pipnn.build(dataset, p1)
    i2 = pipnn.build(dataset, p2)
    assert i2.stats["n_candidate_edges"] > i1.stats["n_candidate_edges"]
    assert i2.average_degree() >= i1.average_degree() * 0.8


def test_no_final_prune_variant(dataset, params):
    idx = pipnn.build(dataset, params.with_(final_prune=False))
    assert (idx.graph >= 0).any(axis=1).all()


def test_mips_metric_build(dataset):
    p = PiPNNParams(
        rbc=RBCParams(c_max=256, c_min=32, fanout=(3,)),
        leaf=LeafParams(k=2),
        metric="mips", l_max=32, max_deg=16, seed=0,
    )
    idx = pipnn.build(dataset, p)
    q = dataset[:50]
    truth = brute_force_knn(dataset, q, 10, metric="mips")
    found = pipnn.search(idx, dataset, q, k=10, beam=48)
    r = recall_at_k(found, truth, 10)
    assert r > 0.6, f"MIPS recall {r}"


def test_knn_graph_task(dataset):
    p = PiPNNParams(
        rbc=RBCParams(c_max=256, c_min=32, fanout=(4, 2)),
        leaf=LeafParams(k=3), l_max=64, max_deg=32, seed=0,
    )
    knn, timings = knn_graph_pipnn(dataset, k=10, beam=48, params=p)
    assert knn.shape == (dataset.shape[0], 10)
    r = knn_graph_recall(dataset, knn, k=10, sample=400)
    assert r > 0.85, f"knn-graph recall {r}"
    assert timings["total"] > 0


def test_sequential_and_batch_search_agree(index, dataset):
    q = dataset[:20]
    f_batch = pipnn.search(index, dataset, q, k=10, beam=32, batch=True)
    f_np = pipnn.search(index, dataset, q, k=10, beam=32, batch=False)
    agree = np.mean([
        len(set(a.tolist()) & set(b.tolist())) / 10
        for a, b in zip(f_batch, f_np)
    ])
    assert agree > 0.8, f"batch/np agreement {agree}"


# ---------------------------------------------------- entry reachability ---

def _two_cycles():
    """Points 0-4 and 5-9 on two separate directed cycles; 4 slots/row."""
    g = np.full((10, 4), -1, np.int32)
    for i in range(5):
        g[i, 0] = (i + 1) % 5
        g[5 + i, 0] = 5 + (i + 1) % 5
    d = np.where(g >= 0, 1.0, np.inf).astype(np.float32)
    x = np.arange(10, dtype=np.float32)[:, None] * np.ones((1, 3), np.float32)
    return g, d, x


def test_connect_from_start_links_every_component():
    from repro.core.pipnn import _reachable, connect_from_start

    g, d, x = _two_cycles()
    assert _reachable(g, [0]).sum() == 5
    g2, d2, added = connect_from_start(g, d, x, 0, "l2")
    assert added == 1 and _reachable(g2, [0]).all()
    assert (g2[0] == 5).any(), "the entry point links the other component"
    slot = int(np.flatnonzero(g2[0] == 5)[0])
    assert d2[0, slot] == np.float32(3 * 25.0)      # squared l2, 0 -> 5
    assert (g == _two_cycles()[0]).all(), "inputs are not modified"
    g3, d3, again = connect_from_start(g2, d2, x, 0, "l2")
    assert again == 0 and (g3 == g2).all() and (d3 == d2).all()


def test_connect_from_start_full_entry_row_links_nearest_reached():
    """With no free slot at the entry point the link comes from the
    reached point nearest the component's root, into a free slot."""
    from repro.core.pipnn import _reachable, connect_from_start

    g, d, x = _two_cycles()
    g[0] = [1, 2, 3, 4]
    d[0] = 1.0
    g2, _, added = connect_from_start(g, d, x, 0, "l2")
    assert added == 1 and _reachable(g2, [0]).all()
    assert (g2[4] == 5).any(), "point 4 is the reached point nearest 5"


def _blob_labels(cfg, x):
    centers = np.random.default_rng(cfg.seed).standard_normal(
        (cfg.n_clusters, cfg.dim)) * cfg.cluster_scale
    return np.argmin(((x[:, None, :] - centers[None]) ** 2).sum(-1), axis=1)


def test_link_entry_hubs_reaches_every_cluster():
    """The entry point of a graph whose clusters share no edge gets a
    hub in each other cluster; it keeps at least half its own edges, and
    the inputs are not modified."""
    from repro.core.pipnn import link_entry_hubs
    from repro.data.pipeline import VectorPipelineConfig, make_vectors

    cfg = VectorPipelineConfig(n=800, dim=8, n_clusters=5,
                               cluster_scale=30.0, seed=3)
    x = make_vectors(cfg)
    lab = _blob_labels(cfg, x)
    width = 8
    g = np.full((len(x), width), -1, np.int32)
    for c in range(cfg.n_clusters):      # a full ring per cluster
        m = np.flatnonzero(lab == c)
        for j in range(width):
            g[m, j] = np.roll(m, j + 1)
    d = np.where(g >= 0, 1.0, np.inf).astype(np.float32)
    g0, d0 = g.copy(), d.copy()
    g2, d2 = link_entry_hubs(g, d, x, 0, "l2")
    assert (g == g0).all() and (d == d0).all()
    row = g2[0]
    assert set(lab[row]) == set(range(cfg.n_clusters))
    assert (row[: width // 2] == g0[0, : width // 2]).all()
    for slot in np.flatnonzero(lab[row] != lab[0]):
        assert d2[0, slot] == pytest.approx(
            ((x[0] - x[row[slot]]) ** 2).sum(), rel=1e-5)


def test_build_on_separated_clusters_reaches_every_point():
    """More leaders per cluster than the RBC fanout: no leaf spans two
    clusters, so the raw graph splits per cluster.  The built index must
    still reach every point from its entry point, whose row links every
    cluster, and search finds the true neighbors in every cluster."""
    from repro.core.pipnn import _reachable
    from repro.data.pipeline import (VectorPipelineConfig, make_queries,
                                     make_vectors)

    cfg = VectorPipelineConfig(n=3000, dim=16, n_clusters=4,
                               cluster_scale=20.0, seed=5)
    x, q = make_vectors(cfg), make_queries(cfg, 100)
    p = PiPNNParams(rbc=RBCParams(c_max=128, c_min=16, p_samp=0.05,
                                  fanout=(2, 1)),
                    leaf=LeafParams(k=2), max_deg=16, seed=0)
    idx = pipnn.build(x, p)
    lab = _blob_labels(cfg, x)
    row = idx.graph[idx.start]
    assert set(lab[row[row >= 0]]) == set(range(cfg.n_clusters))
    assert _reachable(idx.graph, [idx.start]).all()
    # without the links recall stays near the entry cluster's share (~0.25)
    found = pipnn.search(idx, x, q, k=10, beam=64)
    assert recall_at_k(found, brute_force_knn(x, q, 10), 10) > 0.9
