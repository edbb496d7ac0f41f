"""PiPNN (Algorithm 4): partition -> pick -> HashPrune -> final prune.

This is the host-orchestrated reference/build path used by tests, examples
and benchmarks; the fully-static multi-pod SPMD build lives in
``repro/launch/build_index.py`` and reuses the same stage functions.

Stage 1 execution strategies, selected by ``RBCParams.execution``
(see ``core/rbc.py``): ``"host"`` is the numpy oracle recursion,
``"device"`` keeps only the variable-size worklist on the host while the
per-subproblem leader GEMM / top-f / bucket grouping run as fixed-shape
jitted steps (bit-identical leaves to the oracle for a fixed seed), and
``"static"`` runs the whole stage as ONE jitted two-level carve
(``ball_carve_device``, the ``build_index.py`` tile-step shape) so
``build(streaming=True)`` executes Stage 1-4 with zero host compute.
All three share the leader-assignment step in ``core/leader_assign.py``
with the SPMD build.  ``stats["partition_execution"]`` records the
resolved strategy; ``stats["partition_uncovered"]`` counts points in no
leaf — an invariant tripwire that should always be 0 (the static path
appends salvage leaves for replicas its capacity routing dropped).

Two Stage-2+3 execution strategies, selected by ``build(..., streaming=)``:

  * STREAMING (default, ``streaming=True``): a device-resident chunk
    pipeline.  For each chunk of leaves one fused jitted step runs the leaf
    kernel — the k-NN methods (``bidirected`` / ``directed`` /
    ``inverted``) or the all-to-all ``robust_prune`` leaf method — emits
    candidate edges as fixed-shape device arrays
    (``leaf.emit_knn_edges_jax`` / ``leaf.emit_robust_prune_edges_jax``),
    computes residual hashes from the precomputed sketches (the fused jnp
    ``hash_from_sketches``; the Pallas ``edge_hashes`` on request), and
    folds the chunk into the persistent [n, l_max] reservoir with buffer
    donation.  The fold is the SEGMENTED merge by default
    (``PiPNNParams.merge``): one global sort over the chunk's own edges
    plus a bounded per-row merge with the already-sorted reservoir
    (``hashprune.merge_segmented_edges``; Pallas row-merge kernel on TPU
    via ``use_pallas_merge``) — the persistent reservoir never enters a
    global sort.  ``merge="flat"`` selects the reservoir-as-edges re-sort
    fold (``hashprune_merge_flat``), kept as the oracle.  The merge chunk
    (``LeafParams.stream_chunk``) auto-sizes so one chunk's edge buffer is
    ~ the reservoir itself; the leaf GEMM still runs at the ``leaf_chunk``
    VMEM granularity inside the fused step.  Peak intermediate memory is
    O(stream_chunk_edges + n * l_max) = O(n * l_max) in auto mode, and
    there are no host round-trips inside the loop — candidate edges never
    materialize on the host.

  * FLAT (``streaming=False``, and the fallback for the ``mst`` leaf
    method only): materialize the whole candidate edge list on the host,
    then run one global ``hashprune_flat`` sort.  O(E) memory; kept as the
    oracle the streaming path is property-tested against (mergeability
    lemma, hashprune.py).

Stage 4 (``robust_prune.final_prune``) is device-resident too: a donated
[n, max_deg] output buffer pair is filled chunk-by-chunk via
``lax.dynamic_update_slice`` with a single device->host transfer at the
end, so with ``streaming=True`` the entire Stage 2-4 pipeline performs no
per-chunk host syncs.

Last, ``link_entry_hubs`` gives the entry point (the medoid) edges to
points spread over the data, and ``connect_from_start`` adds the few
edges that make every point reachable from it: on well-separated
clusters few or no leaves span two clusters, and without them beam
search would rarely leave the medoid's own cluster.
``stats["connect_edges"]`` counts the reachability edges.

All paths are bit-identical by HashPrune's mergeability (Theorem 3.1):
tests assert equal graphs on both metrics, for both the segmented and flat
folds, and streaming-vs-host final_prune.

The build is deterministic under a fixed seed (Appendix A.8): RBC is
deterministic given its RNG stream, and HashPrune is history-independent
(Theorem 3.1), so the produced graph is unique regardless of leaf processing
order — tests assert bit-identical rebuilds.

Alpha scale note: ``metrics`` returns *squared* L2.  RobustPrune's alpha is
specified on true distances in the paper (default 1.2); on squared
distances the equivalent multiplier is alpha**2, which ``PiPNNParams``
applies automatically for the l2 metric.  For MIPS (dissimilarity = -ip,
sign-indefinite) alpha scaling is not meaningful and we use alpha=1.0, the
standard DiskANN-MIPS practice.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core import sketch as _sketch
from repro.core.hashprune import (INVALID_ID, Reservoir, hashprune_flat,
                                  merge_flat_edges, merge_segmented_edges,
                                  reservoir_init)
from repro.core.leaf import (EdgeList, LeafParams, _leaf_robust_prune,
                             build_leaf_edges, emit_knn_edges_jax,
                             emit_robust_prune_edges_jax, iter_leaf_id_chunks,
                             leaf_knn_jax)
from repro.core.rbc import (RBCParams, _pairwise_np, leaves_to_padded,
                            padded_coverage, partition_padded,
                            resolve_execution)
from repro.core.robust_prune import final_prune

logger = logging.getLogger(__name__)

_KNN_METHODS = ("bidirected", "directed", "inverted")
_STREAM_METHODS = _KNN_METHODS + ("robust_prune",)
# Actual per-entry allocation of candidate-edge arrays, used for the
# apples-to-apples memory stats: a fully materialized edge carries
# src + dst + hash (int32) + dist (f32); the host EdgeList has no hash
# field, and a reservoir slot stores id + hash + dist (its row is implied).
_EDGE_BYTES = 16
_EDGE_BYTES_NOHASH = 12
_SLOT_BYTES = 12


@dataclasses.dataclass(frozen=True)
class PiPNNParams:
    rbc: RBCParams = dataclasses.field(default_factory=RBCParams)
    leaf: LeafParams = dataclasses.field(default_factory=LeafParams)
    partitioner: str = "rbc"
    hash_bits: int = 12        # m hyperplanes (paper default 12, Fig. 13)
    l_max: int = 64            # reservoir capacity (paper: 64..192)
    final_prune: bool = True   # Sec. 4.3 (enabled by default in the paper)
    alpha: float = 1.2         # on TRUE distance; squared for l2 internally
    max_deg: int = 64          # final graph degree cap (paper's comparison deg)
    metric: str = "l2"
    seed: int = 0
    use_pallas_hash: bool | None = None  # None: auto (the fused jnp hash;
    #                            see _resolve_pallas)
    merge: str = "segmented"   # streaming reservoir fold: "segmented" folds
    #                            each chunk via a chunk-only sort + bounded
    #                            per-row merge; "flat" is the global-re-sort
    #                            oracle (hashprune_merge_flat).  Bit-identical.
    use_pallas_merge: bool | None = None  # None: auto (Pallas on TPU only)

    def effective_alpha(self) -> float:
        if self.metric == "l2":
            return float(self.alpha) ** 2
        if self.metric == "mips":
            return 1.0
        return float(self.alpha)

    def with_(self, **kw) -> "PiPNNParams":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass
class PiPNNIndex:
    graph: np.ndarray          # [n, max_deg] int32, -1 padded
    dists: np.ndarray          # [n, max_deg] f32, +inf padded
    start: int                 # entry point (medoid)
    params: PiPNNParams
    timings: dict[str, float]
    stats: dict[str, Any]

    @property
    def n(self) -> int:
        return self.graph.shape[0]

    def average_degree(self) -> float:
        return float((self.graph >= 0).sum() / self.graph.shape[0])


def _resolve_pallas(params: PiPNNParams) -> tuple[bool, bool, bool]:
    """(use_pallas_hash, use_pallas_merge, interpret) for the Pallas kernels.

    Auto mode runs the Pallas reservoir merge on TPU, but never the Pallas
    edge hash: that kernel reads the two [E, m] sketch gathers from HBM,
    where XLA stores them lane-padded from m to 128 columns.  At an
    auto-sized SIFT1M stream chunk (E = 64M edges) each gather is 32.8 GB
    and the TPU compiler refuses the step (RESOURCE_EXHAUSTED); the jnp
    ``hash_from_sketches`` fuses gather and hash, so [E, m] never exists."""
    on_tpu = jax.default_backend() == "tpu"
    use_hash = bool(params.use_pallas_hash)
    use_merge = (on_tpu if params.use_pallas_merge is None
                 else bool(params.use_pallas_merge))
    logger.info("build kernels: edge hash=%s, reservoir merge=%s%s",
                "pallas" if use_hash else "jnp",
                "pallas" if use_merge else "jnp",
                "" if on_tpu else " (interpreted)")
    return use_hash, use_merge, not on_tpu


def _hash_edges(
    edges: EdgeList, sketches: np.ndarray, *,
    use_pallas: bool, interpret: bool,
) -> np.ndarray:
    """Residual hashes h_src(dst) for every candidate edge, via sketches."""
    h = _sketch.edge_hashes_from_ids(
        jnp.asarray(sketches), jnp.asarray(edges.src), jnp.asarray(edges.dst),
        use_pallas=use_pallas, interpret=interpret,
    )
    return np.asarray(h).astype(np.int32)


# ---------------------------------------------------------------------------
# Streaming Stage 2+3: fused leaf-kNN -> edge emit -> edge hash -> merge
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=32)
def _make_stream_step(
    knn_fn: Callable | None,
    k: int,
    metric: str,
    method: str,
    use_pallas: bool,
    interpret: bool,
    sub_chunk: int,
    alpha: float,
    max_deg: int,
    merge: str,
    use_pallas_merge: bool,
):
    """Compile the per-chunk fused step.

    stream_step(res_ids, res_hashes, res_dists, xj, sketches, ids_chunk)
      -> (res_ids', res_hashes', res_dists', n_valid_edges)

    Its stages are named scopes, so a trace's ops name their stage
    (``tracing.op_scopes``): ``leaf_knn`` (gather, GEMM, top-k, emit),
    ``edge_hash`` (hashes and padding masks), and the segmented fold's
    ``chunk_sort`` and ``reservoir_merge``.

    ``ids_chunk`` is [stream_chunk, c_max]; the leaf kernel (k-NN or, for
    the ``robust_prune`` method, the all-to-all leaf RobustPrune) runs over
    ``sub_chunk``-sized sub-batches (the VMEM-budget GEMM granularity)
    while edge emission, hashing and the reservoir fold happen once per
    chunk — so the merge cost is amortized over many leaves.  The fold is
    the segmented merge by default (chunk-only global sort + bounded
    per-row reservoir merge); ``merge="flat"`` selects the global-re-sort
    oracle.  The reservoir triplet is donated so the persistent state is
    updated in place across the whole stream.  Cached on (knn_fn identity,
    statics) so repeated builds reuse one executable.
    """
    knn = knn_fn or (lambda pts, valid: leaf_knn_jax(
        pts, valid, k=k, metric=metric))

    def stream_step(res_ids, res_hashes, res_dists, xj, sketches, ids_chunk):
        n = res_ids.shape[0]
        s, c = ids_chunk.shape

        def block(ids_sub):  # [sub_chunk, c_max] -> flat edge arrays
            pts = xj[jnp.maximum(ids_sub, 0)]
            if method == "robust_prune":
                keep, d = _leaf_robust_prune(
                    pts, ids_sub >= 0, metric=metric, alpha=alpha,
                    max_deg=max_deg)
                return emit_robust_prune_edges_jax(ids_sub, keep, d)
            ni, nd = knn(pts, ids_sub >= 0)
            return emit_knn_edges_jax(ids_sub, ni, nd, direction=method)

        # lax.map (not an unrolled python loop): program size stays constant
        # however large the auto-sized stream chunk grows, and the [C, C]
        # working set stays at the sub_chunk VMEM granularity
        with jax.named_scope("leaf_knn"):
            src, dst, dist = jax.lax.map(
                block, ids_chunk.reshape(s // sub_chunk, sub_chunk, c))
            src, dst, dist = (src.reshape(-1), dst.reshape(-1),
                              dist.reshape(-1))
        with jax.named_scope("edge_hash"):
            h = _sketch.edge_hashes_from_ids(
                sketches, src, dst, use_pallas=use_pallas,
                interpret=interpret)
            ok = src >= 0
            edges = (jnp.where(ok, src, jnp.int32(n)),
                     jnp.where(ok, dst, INVALID_ID),
                     jnp.where(ok, h, 0),
                     jnp.where(ok, dist, jnp.inf))
            n_valid = jnp.sum(ok, dtype=jnp.int32)
        fold = merge_flat_edges if merge == "flat" else functools.partial(
            merge_segmented_edges, use_pallas=use_pallas_merge,
            interpret=interpret)
        merged = fold(res_ids, res_hashes, res_dists, *edges)
        return merged.ids, merged.hashes, merged.dists, n_valid

    return jax.jit(stream_step, donate_argnums=(0, 1, 2))


def stream_step_workspace_bytes(
    n: int, l_max: int, s: int, c: int, k: int, *,
    method: str = "bidirected", merge: str = "segmented",
) -> int:
    """Modeled XLA temp bytes of one ``_make_stream_step`` chunk step:
    the emitted src/dst/hash/dist candidate buffers (one [s * epl] set
    plus the padding-masked copies handed to the fold) and the fold's
    own workspace (``hashprune.*_workspace_bytes``).  ``s`` leaves of
    ``c`` padded entries emit ``epl`` edges each — the model's only
    inputs are the CHUNK shape and the reservoir shape, never the total
    emitted edge count E: that is the paper's bounded-memory contract,
    and the memory auditor (``repro.analysis.memory_audit``) validates
    this model against the compiled byte ledger at every lattice point
    (PIPM004) and prices the BigANN-1B per-shard envelope with it
    (PIPM003)."""
    from repro.core.hashprune import (merge_flat_workspace_bytes,
                                      merge_segmented_workspace_bytes)

    if method == "robust_prune":
        epl = c * c
    else:
        epl = (2 if method == "bidirected" else 1) * c * k
    e = s * epl
    emit = 2 * e * _EDGE_BYTES
    fold = (merge_flat_workspace_bytes if merge == "flat"
            else merge_segmented_workspace_bytes)(n, l_max, e)
    return emit + fold


def _stream_edges_per_leaf(leaf: LeafParams, c_max: int) -> int:
    """Candidate-edge buffer entries one padded leaf contributes to the
    fused step (the emitters' fixed output shapes)."""
    if leaf.method == "robust_prune":
        return c_max * c_max      # emit_robust_prune_edges_jax: [C, C] mask
    fan = 2 if leaf.method == "bidirected" else 1
    return fan * c_max * leaf.k   # emit_knn_edges_jax


def _stream_chunk_leaves(
    leaf: LeafParams, n: int, l_max: int, nleaves: int, c_max: int
) -> int:
    """Leaves per streaming merge step (a multiple of ``leaf_chunk``).

    Auto mode sizes the chunk so one chunk's padded candidate-edge buffer
    is ~ the reservoir ([n, l_max] entries): the merge's re-sort work
    then amortizes to O(E / (n * l_max)) passes total while peak
    intermediate memory stays O(n * l_max) — the paper's "no extra
    intermediate memory" contract — instead of O(E).
    """
    lc = max(1, leaf.leaf_chunk)
    if leaf.stream_chunk is not None:
        s = max(lc, int(leaf.stream_chunk))
    else:
        edges_per_leaf = max(1, _stream_edges_per_leaf(leaf, c_max))
        s = max(lc, (n * l_max) // edges_per_leaf)
    s = min(s, max(lc, nleaves))          # never over-allocate past the data
    return -(-s // lc) * lc               # round up to a leaf_chunk multiple


# The stream step and its argument shapes as the last streamed build in
# this process ran it.  It exists only for trace readers, through
# ``stream_step_text``: a reader holds no index and reaches the program by
# import alone.  Both go once the trace reduction keeps each device op's
# own scope (its ``tf_op`` metadata, the same ``op_name`` path).
_last_stream_step: tuple[Callable, list] | None = None


def stream_step_text() -> str | None:
    """The compiled module text of the stream step at the shapes the last
    streamed build ran it at, or None before one ran: with
    ``tracing.op_scopes``, the stage each of its ops in a trace belongs
    to.  It lowers the step again and takes the executable the build left
    in the jit cache (it compiles only where that is gone), so call it
    outside a timed window."""
    if _last_stream_step is None:
        return None
    step, args = _last_stream_step
    return step.lower(*args).compile().as_text()


def _build_reservoir_streaming(
    x: np.ndarray,
    leaves_padded: np.ndarray,
    sketches: jax.Array,
    params: PiPNNParams,
    knn_fn: Callable | None,
) -> tuple[Reservoir, int, dict[str, int]]:
    """Stream leaf chunks through the fused step; returns
    (reservoir, n_candidate_edges, memory stats)."""
    leaf = params.leaf
    use_pallas, use_pallas_merge, interpret = _resolve_pallas(params)
    n = x.shape[0]
    nleaves, c_max = leaves_padded.shape
    chunk = _stream_chunk_leaves(leaf, n, params.l_max, nleaves, c_max)
    step = _make_stream_step(
        knn_fn if leaf.method in _KNN_METHODS else None,
        leaf.k, params.metric, leaf.method, use_pallas, interpret,
        max(1, leaf.leaf_chunk), leaf.alpha, leaf.max_deg, params.merge,
        use_pallas_merge)
    xj = jnp.asarray(x)
    res = reservoir_init(n, params.l_max)
    ids_r, hs_r, ds_r = res.ids, res.hashes, res.dists
    counts = []
    for ids in iter_leaf_id_chunks(leaves_padded, chunk):
        ids_j = jnp.asarray(ids)
        ids_r, hs_r, ds_r, cnt = step(ids_r, hs_r, ds_r, xj, sketches, ids_j)
        counts.append(cnt)  # device scalar: no per-chunk host sync
    if counts:
        global _last_stream_step
        _last_stream_step = (step, [
            jax.ShapeDtypeStruct(a.shape, a.dtype)
            for a in (ids_r, hs_r, ds_r, xj, sketches, ids_j)])
    # actual allocated candidate-edge bytes: the fused step materializes
    # src/dst/hash/dist for every (padded) chunk entry; `chunk` is already
    # capped at the padded leaf count, so this is the real buffer size
    chunk_entries = chunk * _stream_edges_per_leaf(leaf, c_max)
    if params.merge == "flat":
        # the fold re-expresses the reservoir as n*l_max padding-extended
        # edges and sorts them together with the chunk
        merge_ws = (n * params.l_max + chunk_entries) * _EDGE_BYTES
    else:
        # chunk-only global sort + [n, 2*l_max] per-row merge
        merge_ws = chunk_entries * _EDGE_BYTES + 2 * n * params.l_max * _SLOT_BYTES
    mem = {
        "stream_chunk_leaves": chunk,
        "peak_edge_bytes": chunk_entries * _EDGE_BYTES,
        "edge_bytes_build_leaves": chunk_entries * _EDGE_BYTES,
        "merge_workspace_bytes": merge_ws,
    }
    n_edges = int(np.sum([np.asarray(c) for c in counts])) if counts else 0
    return Reservoir(ids=ids_r, hashes=hs_r, dists=ds_r), n_edges, mem


# ---------------------------------------------------------------------------
# Reachability from the entry point
# ---------------------------------------------------------------------------

_CONNECT_ROUNDS = 16


def _reachable(graph: np.ndarray, roots, blocked: np.ndarray | None = None
               ) -> np.ndarray:
    """bool [n]: the points reachable from ``roots`` along graph edges
    without entering a ``blocked`` point."""
    n = graph.shape[0]
    seen = np.zeros(n, bool) if blocked is None else blocked.copy()
    new = np.zeros(n, bool)
    front = np.asarray(roots, np.int64)
    seen[front] = new[front] = True
    while front.size:
        nb = np.unique(graph[front].ravel())
        nb = nb[nb >= 0]
        nb = nb[~seen[nb]]
        seen[nb] = new[nb] = True
        front = nb
    return new


def link_entry_hubs(graph: np.ndarray, dists: np.ndarray | None,
                    x: np.ndarray, start: int, metric: str
                    ) -> tuple[np.ndarray, np.ndarray | None]:
    """Give the entry point edges to up to half a row of hubs spread over
    the data.

    Beam search starts from ``start`` alone.  On well-separated clusters
    only a few long edges join them, and a query in another cluster than
    the entry's spends its beam before it finds one: on a 32-cluster
    Gaussian mixture (n=262,144, beam 128) queries in the entry's cluster
    reached recall@10 0.98 and the rest 0.74.  The hubs come from a
    farthest-point traversal from ``start``, which visits every
    separated cluster before it revisits one.  They take the entry row's
    free slots, then its longest edges (rows are sorted by distance), so
    the entry keeps at least half its own edges; edges an eviction cuts
    off are restored by ``connect_from_start``.  Returns (graph, dists);
    the inputs are not modified.
    """
    graph = graph.copy()
    dists = None if dists is None else dists.copy()
    width = graph.shape[1]
    hubs = []
    near = _pairwise_np(x, x[start][None], metric)[:, 0]
    for _ in range(min(width // 2, len(x) - 1)):
        hubs.append(int(np.argmax(near)))
        near = np.minimum(near, _pairwise_np(x, x[hubs[-1]][None],
                                             metric)[:, 0])
    row = graph[start]
    hubs = [h for h in hubs if h != start and h not in row]
    slots = np.concatenate([np.flatnonzero(row < 0),
                            np.flatnonzero(row >= 0)[width // 2:][::-1]])
    for h, slot in zip(hubs, slots):
        graph[start, slot] = h
        if dists is not None:
            dists[start, slot] = _pairwise_np(x[start][None], x[h][None],
                                              metric)[0, 0]
    return graph, dists


def connect_from_start(graph: np.ndarray, dists: np.ndarray | None,
                       x: np.ndarray, start: int, metric: str
                       ) -> tuple[np.ndarray, np.ndarray | None, int]:
    """Add the edges that make every point reachable from ``start``.

    RBC sends each point to its ``fanout`` nearest leaders, so on data
    whose clusters are well separated (more leaders per cluster than the
    fanout) no leaf spans two clusters and the graph splits into one
    component per cluster: beam search from the medoid then reaches its
    own cluster only.  Each round takes the unreached points' source
    components (greedily: the smallest unreached id and everything it
    reaches) and adds one edge into each — from ``start`` while its row
    has free slots, largest components first, so the entry point's first
    expansion fans out to every cluster; otherwise from the nearest
    reached point, into a free slot when it has one.  Returns (graph,
    dists, edges added); the inputs are not modified, and ``dists=None``
    (a packing that keeps no edge lengths) stays None.
    """
    graph = graph.copy()
    dists = None if dists is None else dists.copy()
    reached = _reachable(graph, [start])
    added = 0
    for _ in range(_CONNECT_ROUNDS):
        if reached.all():
            return graph, dists, added
        seen, roots, sizes = reached.copy(), [], []
        while not seen.all():
            u = int(np.argmin(seen))
            comp = _reachable(graph, [u], seen)
            roots.append(u)
            sizes.append(int(comp.sum()))
            seen |= comp
        roots = np.asarray(roots)[np.argsort(-np.asarray(sizes),
                                             kind="stable")]
        n_hub = min(roots.size, int(np.sum(graph[start] < 0)))
        srcs = [start] * n_hub
        open_rows = np.flatnonzero(reached & (graph < 0).any(axis=1))
        pool = open_rows if open_rows.size else np.flatnonzero(reached)
        for u in roots[n_hub:]:
            d = _pairwise_np(x[u][None], x[pool], metric)[0]
            srcs.append(int(pool[np.argmin(d)]))
        for v, u in zip(srcs, roots):
            free = np.flatnonzero(graph[v] < 0)
            slot = free[0] if free.size else graph.shape[1] - 1
            graph[v, slot] = u
            if dists is not None:
                dists[v, slot] = _pairwise_np(x[v][None], x[u][None],
                                              metric)[0, 0]
        added += roots.size
        reached = _reachable(graph, [start])
    if not reached.all():
        raise RuntimeError(f"{int((~reached).sum())} points still unreachable "
                           f"from the entry point after {_CONNECT_ROUNDS} "
                           "linking rounds")
    return graph, dists, added


def build(
    x: np.ndarray,
    params: PiPNNParams | None = None,
    *,
    leaves: list[np.ndarray] | None = None,
    knn_fn: Callable | None = None,
    streaming: bool = True,
) -> PiPNNIndex:
    """Build a PiPNN index over ``x`` [n, d] float32.

    ``streaming=True`` (default) runs Stage 2+3 as the device-resident
    chunk pipeline (bounded memory, no host round-trips); ``False`` forces
    the O(E) flat oracle path.  Both produce bit-identical graphs.

    ``knn_fn``, if given, should be a STABLE callable (e.g. the cached
    ``kernels.ops.make_knn_fn``): the streaming fused step is compiled per
    knn_fn identity, so a fresh lambda per call recompiles every build.
    Under ``streaming=True`` it must also be jit-traceable (pure JAX —
    it runs inside the fused step); pass ``streaming=False`` for a
    host-side/numpy knn_fn.
    """
    from repro.core.beam_search import medoid  # local import, avoids cycle

    params = params or PiPNNParams()
    x = np.ascontiguousarray(x, dtype=np.float32)
    n, d = x.shape
    timings: dict[str, float] = {}
    stats: dict[str, Any] = {}

    # --- Stage 1: overlapping partitioning (Sec. 4.1) ---------------------
    # partition_padded produces the dense [L, c_max] device-facing matrix
    # directly; with rbc.execution="static" the whole stage is ONE jitted
    # two-level carve (ball_carve_device) with zero host recursion, with
    # "device" the host keeps only the worklist while the per-subproblem
    # math runs jitted, and with "host" it is the original numpy oracle.
    with tracing.span("pipnn.partition", into=timings,
                      key="partition") as counts:
        if leaves is None:
            rbc = dataclasses.replace(params.rbc, metric=params.metric,
                                      seed=params.seed)
            padded = partition_padded(x, rbc, params.partitioner)
            stats["partition_execution"] = (
                resolve_execution(rbc) if params.partitioner == "rbc"
                else "host")
        else:
            padded = leaves_to_padded(leaves, params.rbc.c_max)
            stats["partition_execution"] = "caller"
        placed = int((padded >= 0).sum())
        counts.update(n_leaves=int(padded.shape[0]),
                      point_repeat=placed / max(n, 1),
                      pad_ratio=padded.size / max(placed, 1))
    stats.update(counts)
    stats["partition_uncovered"] = n - padded_coverage(padded, n)

    import jax.random as jrandom

    key = jrandom.PRNGKey(params.seed)
    hyperplanes = _sketch.make_hyperplanes(key, params.hash_bits, d)
    leaf = dataclasses.replace(params.leaf, metric=params.metric)
    lparams = dataclasses.replace(params, leaf=leaf)

    stream_ok = streaming and leaf.method in _STREAM_METHODS
    stats["streaming"] = stream_ok

    if stream_ok:
        # --- Stage 2+3 fused: streaming device-resident pipeline ----------
        # one fused loop: the (tiny) sketch GEMM is charged to the
        # hashprune phase, everything else to build_leaves
        with tracing.span("pipnn.sketch", into=timings, key="hashprune"):
            sketches = jax.block_until_ready(
                _sketch.sketch_jit(jnp.asarray(x), hyperplanes))
        with tracing.span("pipnn.stream", into=timings, key="build_leaves"):
            res, n_edges, mem = _build_reservoir_streaming(
                x, padded, sketches, lparams, knn_fn)
            jax.block_until_ready(res.ids)
        stats["n_candidate_edges"] = n_edges
        stats.update(mem)
    else:
        # --- Stage 2: leaf building -> candidate edges (Sec. 4.2) ---------
        with tracing.span("pipnn.leaf_edges", into=timings,
                          key="build_leaves"):
            edges = build_leaf_edges(x, padded, leaf, knn_fn=knn_fn)
        stats["n_candidate_edges"] = int(edges.valid().sum())
        # the host EdgeList carries no hash field (12 B/edge); Stage 3 then
        # materializes src/dst/hash/dist device arrays for ALL edges at once
        # (16 B/edge) — that is the actual peak, reported apples-to-apples
        # with the streaming path's chunk buffers
        stats["edge_bytes_build_leaves"] = int(edges.src.size) * _EDGE_BYTES_NOHASH
        stats["merge_workspace_bytes"] = int(edges.src.size) * _EDGE_BYTES
        stats["peak_edge_bytes"] = int(edges.src.size) * _EDGE_BYTES

        # --- Stage 3: HashPrune (Sec. 3) ----------------------------------
        with tracing.span("pipnn.hashprune", into=timings, key="hashprune"):
            use_pallas, _, interpret = _resolve_pallas(params)
            sketches = np.asarray(
                _sketch.sketch_jit(jnp.asarray(x), hyperplanes))
            hashes = _hash_edges(edges, sketches, use_pallas=use_pallas,
                                 interpret=interpret)
            src = np.where(edges.src >= 0, edges.src, n).astype(np.int32)
            dst = np.where(edges.src >= 0, edges.dst,
                           INVALID_ID).astype(np.int32)
            dist = np.where(edges.src >= 0, edges.dist,
                            np.inf).astype(np.float32)
            res = hashprune_flat(
                jnp.asarray(src), jnp.asarray(dst), jnp.asarray(hashes),
                jnp.asarray(dist), n_points=n, l_max=params.l_max,
            )

    # --- Stage 4: final prune (Sec. 4.3) -----------------------------------
    with tracing.span("pipnn.final_prune", into=timings, key="final_prune"):
        if params.final_prune:
            graph, dists = final_prune(
                x, res, alpha=params.effective_alpha(),
                max_deg=params.max_deg, metric=params.metric,
            )
        else:
            ids = np.asarray(res.ids)[:, : params.max_deg]
            ds = np.asarray(res.dists)[:, : params.max_deg]
            if ids.shape[1] < params.max_deg:
                pad = params.max_deg - ids.shape[1]
                ids = np.pad(ids, ((0, 0), (0, pad)), constant_values=-1)
                ds = np.pad(ds, ((0, 0), (0, pad)), constant_values=np.inf)
            graph, dists = ids, ds

    # --- every point reachable from the entry point ------------------------
    # both spans add into timings["connect"]
    with tracing.span("pipnn.link_entry_hubs", into=timings, key="connect"):
        start = medoid(x, seed=params.seed)
        graph, dists = link_entry_hubs(np.asarray(graph), np.asarray(dists),
                                       x, start, params.metric)
    with tracing.span("pipnn.connect_from_start", into=timings,
                      key="connect") as counts:
        graph, dists, added = connect_from_start(
            graph, dists, x, start, params.metric)
        counts["connect_edges"] = added
    stats["connect_edges"] = added
    timings["total"] = sum(timings.values())

    return PiPNNIndex(
        graph=graph,
        dists=dists,
        start=start,
        params=params,
        timings=timings,
        stats=stats,
    )


def serving_index(index: PiPNNIndex, x: np.ndarray, *, dtype=None,
                  mesh=None):
    """The packed device-resident ``ServingIndex`` for ``(index, x)``,
    cached on the index: the first call uploads graph/points/norms (and
    the int8 scales when ``dtype="int8"``) to the device, every later
    call with the same dataset and graph objects reuses the same device
    buffers — zero host->device transfers besides the queries.  With
    ``mesh`` (a single-axis ``jax.sharding.Mesh``) the packing is the
    sharded ``distributed.serving.ShardedServingIndex`` — one
    partition-aligned shard per device; the cache keys on the mesh too,
    so single-device and sharded packings never alias.

    The cache holds strong references to ``x`` AND ``index.graph`` and
    keys on object identity (``is``), so a recycled address of a freed
    array can never alias into a stale hit — and replacing ``index.graph``
    (e.g. re-running a build pass or pruning into a fresh array) after
    the first search invalidates the cache instead of silently serving
    the stale device copy of the old graph.  (In-place element writes to
    the same array object are invisible to any identity key — copy-on-
    write the graph instead.)"""
    from repro.core.serving import ServingIndex

    key = (index.start, index.params.metric,
           None if dtype is None else str(dtype),
           None if mesh is None else id(mesh))
    cached = getattr(index, "_serving", None)
    if (cached is not None and getattr(index, "_serving_x", None) is x
            and getattr(index, "_serving_graph", None) is index.graph
            and getattr(index, "_serving_key", None) == key):
        return cached
    sv = ServingIndex.from_index(index, x, dtype=dtype, mesh=mesh)
    index._serving = sv
    index._serving_x = x
    index._serving_graph = index.graph
    index._serving_key = key
    return sv


def search(
    index: PiPNNIndex,
    x: np.ndarray,
    queries: np.ndarray,
    *,
    k: int = 10,
    beam: int = 32,
    batch: bool = True,
    expansions: int | None = None,
    iters: int | None = None,
    dtype=None,
    mesh=None,
    with_stats: bool = False,
) -> np.ndarray:
    """Query the index; returns [Q, k] neighbor ids, -1-padded when fewer
    than ``k`` neighbors are found (e.g. ``beam < k``).

    ``batch=True`` (the serving path) routes through a cached
    ``ServingIndex``: graph/points/norms live on the device after the
    first call, and queries run the multi-expansion beam search —
    ``expansions`` (default 4) best unvisited entries expanded per step,
    one fused ``[Q, E*R]`` distance block (Pallas gather-distance kernel
    on TPU), early exit on per-query convergence with ``iters`` (default
    ``beam_search.default_iters(beam)``) as the backstop cap.  ``dtype``
    downcasts the serving points copy (e.g. ``jnp.bfloat16``) or, with
    ``dtype="int8"``, serves the scalar-quantized packing (int8 points +
    per-point f32 scales, ~1/4 the f32 points footprint, int8 MXU
    distance kernel).  ``mesh`` (a single-axis ``jax.sharding.Mesh``)
    serves through the sharded packing instead: one partition-aligned
    shard per device under ``shard_map``, per-query results merged across
    shards (``distributed.serving.ShardedServingIndex``).
    ``with_stats=True`` returns ``(ids, stats)`` with per-query
    hop/distance-comp telemetry plus the resolved kernel path.

    ``batch=False`` is the pointer-chasing numpy reference
    (``beam_search_np``) — the recall/parity ORACLE, not a serving path:
    it walks one query at a time on the host and re-indexes ``x`` row by
    row per hop, so its cost is dominated by per-hop latency by design
    (that latency-bound pattern is what the paper eliminates from the
    build, and what the batched path amortizes away at query time).
    """
    from repro.core import beam_search as bs
    from repro.core.validation import validate_queries, validate_search_params

    validate_search_params(k=k, beam=beam)
    if batch:
        sv = serving_index(index, x, dtype=dtype, mesh=mesh)
        return sv.search(queries, k=k, beam=beam,
                         expansions=4 if expansions is None else expansions,
                         iters=iters, with_stats=with_stats)
    if (with_stats or iters is not None or dtype is not None
            or expansions is not None or mesh is not None):
        raise ValueError(
            "with_stats / iters / dtype / expansions / mesh are serving-"
            "path options; the batch=False np oracle expands one vertex "
            "per hop and does not support them")
    queries = validate_queries(queries, dim=x.shape[1])
    out = np.empty((queries.shape[0], k), dtype=np.int64)
    for i, q in enumerate(queries):
        ids, _, _ = bs.beam_search_np(
            index.graph, x, q, start=index.start, beam=beam,
            metric=index.params.metric,
        )
        out[i] = ids[:k] if len(ids) >= k else np.pad(ids, (0, k - len(ids)), constant_values=-1)
    return out
