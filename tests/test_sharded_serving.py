"""Sharded SPMD serving tests: the cross-shard top-k merge vs a numpy
lexsort oracle (plus hypothesis property sweeps), 1-device-mesh parity
with the single-device ServingIndex, packing invariants, and (when the
host exposes >= 4 simulated devices via
``XLA_FLAGS=--xla_force_host_platform_device_count=N``) end-to-end
recall parity of the sharded search."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import hypothesis, st
from jax.sharding import Mesh

from repro.core import pipnn
from repro.core.beam_search import brute_force_knn, recall_at_k
from repro.core.serving import ServingIndex
from repro.distributed.serving import ShardedServingIndex, cross_shard_topk

NDEV = len(jax.devices())

multidevice = pytest.mark.skipif(
    NDEV < 4, reason="needs >= 4 devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")


def _mesh(s):
    return Mesh(np.array(jax.devices()[:s]), ("shards",))


# ------------------------------------------------------ cross-shard merge ---

def _topk_oracle(ids_s, ds_s, k):
    """numpy lexsort reference: per query, unique valid (dist, id) pairs
    across all shards, ascending by (dist, id), -1/inf padded to k."""
    s, nq, b = ids_s.shape
    out_i = np.full((nq, k), -1, np.int64)
    out_d = np.full((nq, k), np.inf, np.float32)
    for qi in range(nq):
        pairs = {}
        for si in range(s):
            for bi in range(b):
                i, dd = int(ids_s[si, qi, bi]), float(ds_s[si, qi, bi])
                if i >= 0 and np.isfinite(dd) and i not in pairs:
                    pairs[i] = dd
        items = sorted(pairs.items(), key=lambda t: (t[1], t[0]))[:k]
        for j, (i, dd) in enumerate(items):
            out_i[qi, j] = i
            out_d[qi, j] = dd
    return out_i, out_d


def _random_blocks(rng, s, nq, b, n_ids, *, tie_prob=0.0, drop_prob=0.2):
    """Disjoint per-shard id pools (the partition contract) with random
    -1 pads; optional exact-duplicate distances WITHIN a query to force
    (dist, id) tie-breaks."""
    ids = np.full((s, nq, b), -1, np.int64)
    ds = np.full((s, nq, b), np.inf, np.float32)
    pool = rng.permutation(n_ids)
    bounds = np.linspace(0, n_ids, s + 1).astype(int)
    for si in range(s):
        shard_pool = pool[bounds[si]: bounds[si + 1]]
        for qi in range(nq):
            take = min(b, len(shard_pool))
            chosen = rng.choice(shard_pool, size=take, replace=False)
            dd = rng.standard_normal(take).astype(np.float32)
            if tie_prob and take > 1:
                dup = rng.random(take) < tie_prob
                dd[dup] = dd[0]
            keep = rng.random(take) >= drop_prob
            ids[si, qi, :take][keep] = chosen[keep]
            ds[si, qi, :take][keep] = dd[keep]
    return ids, ds


@pytest.mark.parametrize("s,nq,b,k", [(2, 3, 4, 4), (4, 5, 8, 6),
                                      (8, 2, 4, 16), (3, 4, 6, 1)])
def test_cross_shard_topk_matches_lexsort_oracle(s, nq, b, k):
    rng = np.random.default_rng(hash((s, nq, b, k)) % 2**31)
    ids, ds = _random_blocks(rng, s, nq, b, n_ids=s * b * 2)
    gi, gd = cross_shard_topk(jnp.asarray(ids), jnp.asarray(ds), k=k)
    wi, wd = _topk_oracle(ids, ds, k)
    np.testing.assert_array_equal(np.asarray(gi), wi)
    np.testing.assert_allclose(np.asarray(gd), wd, rtol=0, atol=0)


def test_cross_shard_topk_tie_breaks_toward_smaller_id():
    """Exactly equal distances across shards must order by id — the same
    (dist, id) lex key the beam itself uses, so merges are deterministic
    regardless of shard order."""
    ids = np.array([[[7, 3]], [[5, 1]]], np.int64)        # [2, 1, 2]
    ds = np.zeros((2, 1, 2), np.float32)                  # all tied
    gi, gd = cross_shard_topk(jnp.asarray(ids), jnp.asarray(ds), k=4)
    np.testing.assert_array_equal(np.asarray(gi), [[1, 3, 5, 7]])
    assert (np.asarray(gd) == 0).all()


def test_cross_shard_topk_k_exceeds_union():
    """k past the union of valid entries pads with (-1, inf)."""
    ids = np.array([[[4, -1]], [[9, -1]]], np.int64)
    ds = np.array([[[0.5, np.inf]], [[0.25, np.inf]]], np.float32)
    gi, gd = cross_shard_topk(jnp.asarray(ids), jnp.asarray(ds), k=5)
    np.testing.assert_array_equal(np.asarray(gi), [[9, 4, -1, -1, -1]])
    assert np.isinf(np.asarray(gd)[0, 2:]).all()


def test_cross_shard_topk_k_exceeds_per_shard_beam():
    """k > B draws from MULTIPLE shards' beams — the merged depth is the
    union's, not one shard's."""
    rng = np.random.default_rng(9)
    s, nq, b, k = 4, 3, 4, 12
    ids, ds = _random_blocks(rng, s, nq, b, n_ids=64, drop_prob=0.0)
    gi, _ = cross_shard_topk(jnp.asarray(ids), jnp.asarray(ds), k=k)
    wi, _ = _topk_oracle(ids, ds, k)
    np.testing.assert_array_equal(np.asarray(gi), wi)
    assert (np.asarray(gi)[:, b:] >= 0).any(), "merge must reach past B"


def test_cross_shard_topk_halo_duplicates_identical_dists():
    """The halo contract: the SAME global id may appear in two shards'
    beams with bit-identical distances — the merge keeps one copy."""
    ids = np.array([[[2, 8]], [[2, 5]]], np.int64)        # id 2 replicated
    ds = np.array([[[0.125, 0.5]], [[0.125, 0.25]]], np.float32)
    gi, gd = cross_shard_topk(jnp.asarray(ids), jnp.asarray(ds), k=4)
    np.testing.assert_array_equal(np.asarray(gi), [[2, 5, 8, -1]])
    np.testing.assert_array_equal(np.asarray(gd)[0, :3],
                                  [0.125, 0.25, 0.5])


@hypothesis.settings(max_examples=25, deadline=None)
@hypothesis.given(
    s=st.integers(1, 6),
    nq=st.integers(1, 4),
    b=st.integers(1, 8),
    k=st.integers(1, 20),
    tie_prob=st.sampled_from([0.0, 0.5]),
    seed=st.integers(0, 2**31 - 1),
)
def test_cross_shard_topk_property(s, nq, b, k, tie_prob, seed):
    """Ragged per-shard counts, in-query ties, k above/below B/union —
    the merge must equal the lexsort oracle everywhere."""
    rng = np.random.default_rng(seed)
    ids, ds = _random_blocks(rng, s, nq, b, n_ids=max(s * b, 4),
                             tie_prob=tie_prob, drop_prob=0.35)
    gi, gd = cross_shard_topk(jnp.asarray(ids), jnp.asarray(ds), k=k)
    wi, wd = _topk_oracle(ids, ds, k)
    np.testing.assert_array_equal(np.asarray(gi), wi)
    np.testing.assert_allclose(np.asarray(gd), wd, rtol=0, atol=0)


# ----------------------------------------------------- packing invariants ---

@pytest.fixture(scope="module")
def built():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((900, 16)).astype(np.float32)
    return pipnn.build(x), x


def test_sharded_packing_partition_and_halo(built):
    """Owned rows partition the dataset exactly once; ghost rows replicate
    member-edge endpoints; every member edge survives the renumbering."""
    idx, x = built
    ssv = ShardedServingIndex.from_index(idx, x, mesh=_mesh(1))
    gids = np.asarray(ssv.gids)
    live = gids[gids >= 0]
    assert ssv.n == x.shape[0]
    # an S=1 mesh has no cross-shard edges, hence no halo
    assert len(live) == x.shape[0]
    assert sorted(live.tolist()) == list(range(x.shape[0]))
    # local graph ids resolve through gids to the original edges
    g = np.asarray(ssv.graph)[0]
    orig = np.asarray(idx.graph)
    for row in range(0, x.shape[0], 97):
        gid = gids[0, row]
        local = g[row][g[row] >= 0]
        np.testing.assert_array_equal(
            np.sort(gids[0, local]), np.sort(orig[gid][orig[gid] >= 0]))


def test_sharded_packing_rejects_bad_args(built):
    idx, x = built
    with pytest.raises(ValueError):
        ShardedServingIndex.from_index(idx, x, mesh=_mesh(1), router="rr")
    with pytest.raises(ValueError):        # fewer points than devices
        ShardedServingIndex.from_graph(idx.graph[:0], x[:0], 0,
                                       mesh=_mesh(1))
    # single-device ServingIndex rejects mesh-only kwargs without a mesh
    with pytest.raises(TypeError):
        ServingIndex.from_index(idx, x, router="all")


def test_sharded_one_device_mesh_matches_single(built):
    """An S=1 mesh is the single-device search wearing the shard_map
    plumbing: identical ids (one shard holds the whole graph, the merge
    is a no-op)."""
    idx, x = built
    q = x[:32]
    sv = ServingIndex.from_index(idx, x)
    ssv = ServingIndex.from_index(idx, x, mesh=_mesh(1))
    assert isinstance(ssv, ShardedServingIndex)
    a = sv.search(q, k=10, beam=24)
    b, stats = ssv.search(q, k=10, beam=24, with_stats=True)
    np.testing.assert_array_equal(a, b)
    assert stats["n_shards"] == 1 and stats["router"] == "all"
    assert stats["kernel_path"] == "xla"      # CPU auto-selection
    assert stats["hops"].shape == (32,)


def test_sharded_device_bytes_and_empty_batch(built):
    idx, x = built
    ssv = ServingIndex.from_index(idx, x, mesh=_mesh(1))
    assert ssv.device_bytes() > 0
    assert ssv.device_bytes(per_shard=True) == ssv.device_bytes()
    out = ssv.search(np.zeros((0, x.shape[1]), np.float32), k=7)
    assert out.shape == (0, 7) and out.dtype == np.int64


# --------------------------------------------- multi-device recall parity ---

@multidevice
def test_sharded_search_recall_parity(built):
    """>= 4 shards, replicate-to-all router: the halo packing keeps the
    full 1-hop neighborhood of every owned point, so merged recall stays
    within 0.01 of the single-device search."""
    idx, x = built
    rng = np.random.default_rng(7)
    q = rng.standard_normal((96, x.shape[1])).astype(np.float32)
    gt = brute_force_knn(x, q, k=10)
    r1 = recall_at_k(ServingIndex.from_index(idx, x).search(
        q, k=10, beam=32), gt)
    ssv = ServingIndex.from_index(idx, x, mesh=_mesh(4))
    rs = recall_at_k(ssv.search(q, k=10, beam=32), gt)
    assert rs >= r1 - 0.01, (r1, rs)


@multidevice
def test_sharded_int8_recall_parity(built):
    idx, x = built
    rng = np.random.default_rng(8)
    q = rng.standard_normal((64, x.shape[1])).astype(np.float32)
    gt = brute_force_knn(x, q, k=10)
    r1 = recall_at_k(ServingIndex.from_index(idx, x, dtype="int8").search(
        q, k=10, beam=32), gt)
    ssv = ServingIndex.from_index(idx, x, mesh=_mesh(4), dtype="int8")
    rs = recall_at_k(ssv.search(q, k=10, beam=32), gt)
    assert rs >= r1 - 0.01, (r1, rs)


@multidevice
def test_sharded_leaders_router_masks_shards(built):
    """The probing router serves each query from n_probes shards only:
    summed hops drop vs replicate-to-all, recall stays reasonable."""
    idx, x = built
    rng = np.random.default_rng(9)
    q = rng.standard_normal((48, x.shape[1])).astype(np.float32)
    gt = brute_force_knn(x, q, k=10)
    sall = ServingIndex.from_index(idx, x, mesh=_mesh(4))
    slead = ServingIndex.from_index(idx, x, mesh=_mesh(4),
                                    router="leaders", n_probes=2)
    a, st_all = sall.search(q, k=10, beam=32, with_stats=True)
    b, st_lead = slead.search(q, k=10, beam=32, with_stats=True)
    assert st_lead["router"] == "leaders"
    assert st_lead["hops"].sum() < st_all["hops"].sum()
    assert recall_at_k(b, gt) >= recall_at_k(a, gt) - 0.1


@multidevice
def test_pipnn_search_mesh_end_to_end(built):
    """mesh= threads through pipnn.search's serving cache; mesh and
    non-mesh packings coexist only one at a time (single cache slot)."""
    idx, x = built
    q = x[:16]
    mesh = _mesh(4)
    ids, stats = pipnn.search(idx, x, q, k=5, mesh=mesh, with_stats=True)
    assert stats["n_shards"] == 4
    assert isinstance(idx._serving, ShardedServingIndex)
    sv1 = idx._serving
    pipnn.search(idx, x, q, k=5, mesh=mesh)
    assert idx._serving is sv1                # cache hit on the same mesh
    with pytest.raises(ValueError):
        pipnn.search(idx, x, q, k=5, batch=False, mesh=mesh)


# ------------------------------------------- halo stats / query chunking ---

def test_halo_stats_one_device_mesh(built):
    """S=1: no cross-shard edges, so zero ghosts and zero halo fraction;
    members account for every point."""
    idx, x = built
    ssv = ServingIndex.from_index(idx, x, mesh=_mesh(1))
    hs = ssv.halo_stats()
    assert int(hs["members"].sum()) == x.shape[0]
    assert int(hs["ghosts"].sum()) == 0
    assert hs["halo_fraction"] == 0.0
    bd = ssv.device_bytes(breakdown=True)
    assert bd["ghost_bytes"] == 0
    assert bd["total"] == ssv.device_bytes()
    _, stats = ssv.search(x[:4], k=5, with_stats=True)
    assert stats["halo_fraction"] == 0.0


@multidevice
def test_halo_stats_accounting(built):
    """S=4: members still partition the dataset exactly; ghosts are the
    replicated neighborhood rows; member+ghost+pad == capacity per shard;
    the byte breakdown sums to device_bytes."""
    idx, x = built
    ssv = ServingIndex.from_index(idx, x, mesh=_mesh(4))
    hs = ssv.halo_stats()
    assert int(hs["members"].sum()) == x.shape[0]
    assert int(hs["ghosts"].sum()) > 0        # a real graph has halo
    cap = ssv.shard_capacity
    np.testing.assert_array_equal(
        hs["members"] + hs["ghosts"] + hs["pads"], np.full(4, cap))
    assert 0.0 < hs["halo_fraction"] < 1.0
    bd = ssv.device_bytes(breakdown=True)
    rows = bd["member_bytes"] + bd["ghost_bytes"] + bd["pad_bytes"]
    # the breakdown covers the row-indexed arrays; starts/leaders ride
    # on top of it in the total
    assert 0 < rows <= bd["total"] == ssv.device_bytes()
    assert bd["halo_fraction"] == hs["halo_fraction"]
    _, stats = ssv.search(x[:4], k=5, with_stats=True)
    assert stats["halo_fraction"] == hs["halo_fraction"]


def test_sharded_query_chunk_parity(built):
    """Chunked dispatch pads every batch to one shape: identical results,
    and the jit cache stops growing with batch size."""
    idx, x = built
    ssv = ServingIndex.from_index(idx, x, mesh=_mesh(1))
    q = x[:13]
    a = ssv.search(q, k=7, beam=16)
    b = ssv.search(q, k=7, beam=16, query_chunk=4)
    np.testing.assert_array_equal(a, b)
    # stats survive chunking (concatenated per chunk, trimmed to nq)
    c, stats = ssv.search(q, k=7, beam=16, query_chunk=5, with_stats=True)
    np.testing.assert_array_equal(a, c)
    assert stats["hops"].shape == (13,)
    with pytest.raises(ValueError):
        ssv.search(q, k=7, query_chunk=0)


@multidevice
def test_sharded_query_chunk_bounds_jit_cache(built):
    idx, x = built
    ssv = ServingIndex.from_index(idx, x, mesh=_mesh(4))
    for nq in (1, 3, 7, 12):
        ssv.search(x[:nq], k=5, beam=16, query_chunk=4)
    sizes = [fn._cache_size() for fn in ssv._search_cache.values()]
    assert sum(sizes) == 1, sizes


# ------------------------------------------------- n_probes validation ---

def test_leaders_router_rejects_nonpositive_probes(built):
    idx, x = built
    with pytest.raises(ValueError, match="n_probes"):
        ServingIndex.from_index(idx, x, mesh=_mesh(1), router="leaders",
                                n_probes=0)
    with pytest.raises(ValueError, match="n_probes"):
        ServingIndex.from_index(idx, x, mesh=_mesh(1), router="leaders",
                                n_probes=-3)


@multidevice
@pytest.mark.parametrize("n_probes", [1, 4, 9])
def test_leaders_router_probe_sweep(built, n_probes):
    """n_probes in {1, S, >S}: every query is served by at least one
    shard (no all-masked rows — the pre-PR-8 n_probes<=0 regression),
    and >S clamps to S (== replicate-to-all results)."""
    idx, x = built
    rng = np.random.default_rng(11)
    q = rng.standard_normal((24, x.shape[1])).astype(np.float32)
    ssv = ServingIndex.from_index(idx, x, mesh=_mesh(4), router="leaders",
                                  n_probes=n_probes)
    ids, stats = ssv.search(q, k=5, beam=24, with_stats=True)
    assert stats["n_probes"] == min(n_probes, 4)
    assert (ids[:, 0] >= 0).all(), "a query was masked from every shard"
    if n_probes >= 4:
        sall = ServingIndex.from_index(idx, x, mesh=_mesh(4))
        np.testing.assert_array_equal(ids, sall.search(q, k=5, beam=24))


# ------------------------------------------------- transfer discipline ---

def test_sharded_search_no_implicit_transfers(built, no_implicit_transfers):
    """The serving call under transfer_guard('disallow'): every host
    crossing must be routed through the declared to_device/to_host
    boundaries (the PIPS004 contract, enforced live)."""
    from repro.core import transfers

    idx, x = built
    ssv = ServingIndex.from_index(idx, x, mesh=_mesh(1))
    q = x[:6]
    expect = ssv.search(q, k=5, beam=16)          # compile outside guard
    ssv.search(q, k=5, beam=16, with_stats=True)
    with transfers.ledger() as counts, no_implicit_transfers():
        got = ssv.search(q, k=5, beam=16)
        np.testing.assert_array_equal(got, expect)
        assert counts == ShardedServingIndex.TRANSFER_BUDGET
        ssv.search(q, k=5, beam=16, with_stats=True)


@multidevice
def test_sharded_search_no_implicit_transfers_multidevice(
        built, no_implicit_transfers):
    """Same discipline on a real 4-shard mesh, int8 packing and chunked
    batches included (chunking pays one h2d/d2h per chunk)."""
    from repro.core import transfers

    idx, x = built
    for dtype in (None, "int8"):
        ssv = ServingIndex.from_index(idx, x, mesh=_mesh(4), dtype=dtype)
        q = x[:9]
        ssv.search(q, k=5, beam=16, query_chunk=4)    # compile first
        with transfers.ledger() as counts, no_implicit_transfers():
            ssv.search(q, k=5, beam=16, query_chunk=4)
        assert counts == {"h2d": 3, "d2h": 3}         # ceil(9/4) chunks


# ----------------------------------------------------- boundary hardening ---

def test_sharded_search_guards_k_beam_and_nan(built):
    """The sharded entry shares the single-device boundary validation:
    non-positive k/beam and NaN/Inf rows fail fast and structured, before
    anything is dispatched to the mesh."""
    from repro.core.validation import InvalidQueryError

    idx, x = built
    ssv = ServingIndex.from_index(idx, x, mesh=_mesh(1))
    with pytest.raises(ValueError, match="k must be >= 1"):
        ssv.search(x[:2], k=0)
    with pytest.raises(ValueError, match="beam must be >= 1"):
        ssv.search(x[:2], k=5, beam=-2)
    q = np.array(x[:3])
    q[2, 1] = np.inf
    with pytest.raises(InvalidQueryError) as ei:
        ssv.search(q, k=5)
    assert ei.value.reason == "nan_inf" and ei.value.rows == (2,)


def test_all_shards_down_raises(built):
    from repro.distributed.serving import AllShardsDown

    idx, x = built
    ssv = ServingIndex.from_index(idx, x, mesh=_mesh(1))
    ssv.mark_shard_down(0)
    with pytest.raises(AllShardsDown):
        ssv.search(x[:2], k=5)
    # probing re-admits immediately: no fault harness, so the default
    # probe (serve the shard's own leader) succeeds on the first try
    assert ssv.probe_shard(0)
    assert not ssv.down_shards
    assert (np.asarray(ssv.search(x[:2], k=5))[:, 0] >= 0).all()


def test_probe_shard_failure_keeps_tombstone(built):
    idx, x = built
    ssv = ServingIndex.from_index(idx, x, mesh=_mesh(1))
    ssv.mark_shard_down(0)
    assert not ssv.probe_shard(0, probe=lambda s: False)
    assert ssv.down_shards == (0,)
    calls = []

    def raising_probe(s):
        calls.append(s)
        raise RuntimeError("still dead")

    assert not ssv.probe_shard(0, probe=raising_probe)
    assert calls == [0] and ssv.down_shards == (0,)
    assert ssv.probe_shard(0, probe=lambda s: True)
    assert ssv.healthy_shards == 1


def test_sharded_converged_telemetry(built):
    idx, x = built
    ssv = ServingIndex.from_index(idx, x, mesh=_mesh(1))
    _, stats = ssv.search(x[:5], k=5, beam=16, with_stats=True)
    conv = stats["converged"]
    assert conv.shape == (5,) and conv.dtype == bool
    assert conv.all()
    _, stats1 = ssv.search(x[:5], k=5, beam=16, iters=1, with_stats=True)
    assert not stats1["converged"].any()
    assert stats["healthy_shards"] == 1


def test_sharded_packing_reaches_every_shard_row():
    """A shard's graph is searched from the shard's own entry point, so
    the packing links every component it holds to that entry — even
    when the global graph is split (two separate 5-cycles here)."""
    from repro.core.pipnn import _reachable

    g = np.full((10, 4), -1, np.int32)
    for i in range(5):
        g[i, 0] = (i + 1) % 5
        g[5 + i, 0] = 5 + (i + 1) % 5
    x = np.arange(10, dtype=np.float32)[:, None] * np.ones((1, 3),
                                                           np.float32)
    sv = ShardedServingIndex.from_graph(g, x, 0, mesh=_mesh(1))
    local = np.asarray(sv.graph)[0]
    start = int(np.asarray(sv.starts)[0])
    assert _reachable(local, [start]).all()
    found = sv.search(x, k=1, beam=4)
    np.testing.assert_array_equal(found[:, 0], np.arange(10))


@multidevice
def test_sharded_recall_parity_on_separated_clusters():
    """Clusters farther apart than any leaf: every shard must still reach
    all of its own clusters from its entry point."""
    from repro.core.leaf import LeafParams
    from repro.core.pipnn import PiPNNParams
    from repro.core.rbc import RBCParams
    from repro.data.pipeline import (VectorPipelineConfig, make_queries,
                                     make_vectors)

    cfg = VectorPipelineConfig(n=3000, dim=16, n_clusters=8,
                               cluster_scale=20.0, seed=5)
    x, q = make_vectors(cfg), make_queries(cfg, 100)
    idx = pipnn.build(x, PiPNNParams(
        rbc=RBCParams(c_max=128, c_min=16, p_samp=0.05, fanout=(2, 1)),
        leaf=LeafParams(k=2), max_deg=16, seed=0))
    truth = brute_force_knn(x, q, 10)
    single = recall_at_k(ServingIndex.from_index(idx, x).search(
        q, k=10, beam=64), truth, 10)
    sharded = recall_at_k(ServingIndex.from_index(idx, x, mesh=_mesh(4))
                          .search(q, k=10, beam=64), truth, 10)
    assert single > 0.9 and abs(sharded - single) <= 0.01
