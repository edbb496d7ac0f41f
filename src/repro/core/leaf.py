"""Leaf building (Sec. 4.2, Appendix A.3): pick candidate edges inside each
leaf of the overlapping partition.

TPU-native shape discipline: leaves are padded to ``c_max`` and stacked into
a regular batch ``[L, c_max]`` so that the all-pairs distance computation for
*every* leaf is one batched GEMM (`metrics.pairwise` under vmap, or the
fused Pallas FlashKNN kernel in ``repro/kernels/leaf_knn.py``).  Padding
entries carry +inf distance and can never enter a top-k.

Methods (A.3 ablation space):
  * ``bidirected`` k-NN  — the paper's default (k=2): edges to AND from each
    point's k nearest co-leaf points;
  * ``directed`` k-NN    — edges to the k nearest only;
  * ``inverted`` k-NN    — edges from the k nearest only;
  * ``mst``              — degree-capped (<=3) MST over the l-NN sparsified
    leaf graph (HCNNG's leaf method);
  * ``robust_prune``     — all-to-all RobustPrune per leaf point.

All methods emit a flat candidate edge list (src, dst, dist) ready for
``hashprune_flat``.  The k-NN methods and ``robust_prune`` additionally
have device-side emitters (``emit_knn_edges_jax`` /
``emit_robust_prune_edges_jax``) that the default streaming build fuses
with the HashPrune fold so candidate edges never land on the host; the
host-side ``build_leaf_edges``/``EdgeList`` path remains the oracle for
those methods, and the only path for ``mst`` (host-side Kruskal).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import metrics as _metrics
from repro.core.robust_prune import robust_prune_mask

LeafMethod = Literal["bidirected", "directed", "inverted", "mst", "robust_prune"]


@dataclasses.dataclass(frozen=True)
class LeafParams:
    method: LeafMethod = "bidirected"
    k: int = 2                 # leaf k-NN parameter (paper default 2, Fig. 11)
    metric: str = "l2"
    alpha: float = 1.2         # robust_prune leaf method only
    max_deg: int = 64          # robust_prune leaf method only
    mst_degree_cap: int = 3
    mst_sparsify: int = 10     # l-NN sparsification before Kruskal (A.3.1)
    leaf_chunk: int = 8        # leaves per batched GEMM launch (VMEM budget)
    stream_chunk: int | None = None  # leaves per streaming merge step; None =
    #                            auto-size so one chunk's candidate edges are
    #                            ~ the [n, l_max] reservoir (merge cost then
    #                            amortizes to O(E / (n*l_max)) global sorts
    #                            while peak memory stays reservoir-bounded)


@dataclasses.dataclass
class EdgeList:
    """Flat candidate edges. Padding rows have src == INVALID (-1)."""

    src: np.ndarray   # int32 [E]
    dst: np.ndarray   # int32 [E]
    dist: np.ndarray  # float32 [E]

    def valid(self) -> np.ndarray:
        return self.src >= 0

    def concat(self, other: "EdgeList") -> "EdgeList":
        return EdgeList(
            src=np.concatenate([self.src, other.src]),
            dst=np.concatenate([self.dst, other.dst]),
            dist=np.concatenate([self.dist, other.dist]),
        )


def iter_leaf_id_chunks(leaves_padded: np.ndarray, chunk: int):
    """Yield fixed-shape [chunk, c_max] int32 blocks of ``leaves_padded``.

    The last block is -1-padded to a full chunk so every block has the same
    static shape (one jit compilation for the whole stream).
    """
    nleaves, c = leaves_padded.shape
    chunk = max(1, chunk)
    for s in range(0, nleaves, chunk):
        ids = leaves_padded[s : s + chunk]
        if ids.shape[0] < chunk:
            pad = np.full((chunk - ids.shape[0], c), -1, dtype=np.int32)
            ids = np.concatenate([ids, pad], axis=0)
        yield ids


# ---------------------------------------------------------------------------
# Batched leaf distance matrices + k-NN picking (pure-JAX path)
# ---------------------------------------------------------------------------

def _lex_less(a, b):
    """Pair ``a`` = (value, index) comes before ``b``: the smaller value,
    equal values to the lower index."""
    return (a[0] < b[0]) | ((a[0] == b[0]) & (a[1] < b[1]))


def _pick(take_a, a, b):
    return (jnp.where(take_a, a[0], b[0]), jnp.where(take_a, a[1], b[1]))


def _merge_smallest(a, b):
    """The k first pairs, in order, of two ordered k-lists of pairs.

    Entry j of the merge is the least, over the splits p + q = j + 1, of
    the later of ``a[p - 1]`` and ``b[q - 1]`` (a missing entry comes
    first).  With distinct indices the order is total, so this combine is
    commutative and associative, as a reduction needs.
    """
    k = len(a)
    out = []
    for j in range(k):
        best = b[j]
        for p in range(1, j + 1):
            later = _pick(_lex_less(b[j - p], a[p - 1]), a[p - 1], b[j - p])
            best = _pick(_lex_less(later, best), later, best)
        best = _pick(_lex_less(a[j], best), a[j], best)
        out.append(best)
    return tuple(out)


def _smallest_k(d: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """The k smallest entries of each row of ``d`` [..., C], as
    ``lax.top_k(-d, k)`` picks them: (dist [..., k], idx [..., k]) in
    ascending distance, equal distances to the lower index, so a row with
    fewer than k finite entries yields +inf (at its lowest-index +inf
    entries) in the missing slots.  ``d`` holds no NaN, and -0.0 ties +0.0
    (l2 distances are clamped at +0.0).

    One variadic reduction over the last axis whose carry is the best k
    (value, index) pairs: it reads ``d`` once and sorts nothing, where
    ``lax.top_k`` lowers to a stable sort of each whole row on the TPU.
    """
    c = d.shape[-1]
    axis = d.ndim - 1
    idx = jax.lax.broadcasted_iota(jnp.int32, d.shape, axis)
    # each entry enters as a k-list: itself, then k - 1 empty slots that
    # sort after every column (+inf at the index c)
    empty = (jnp.full_like(d, jnp.inf), jnp.full(d.shape, c, jnp.int32))
    init = (jnp.array(jnp.inf, d.dtype), jnp.int32(c))
    best = jax.lax.reduce(((d, idx),) + (empty,) * (k - 1), (init,) * k,
                          _merge_smallest, (axis,))
    return (jnp.stack([v for v, _ in best], -1),
            jnp.stack([i for _, i in best], -1))


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def leaf_knn_jax(
    pts: jax.Array,     # [B, C, d] gathered leaf points (pad rows arbitrary)
    valid: jax.Array,   # [B, C] bool
    *,
    k: int,
    metric: str = "l2",
) -> tuple[jax.Array, jax.Array]:
    """Per-leaf k nearest co-leaf neighbors.

    Returns (nbr_idx [B, C, k] in-leaf indices, nbr_dist [B, C, k]); invalid
    slots yield (-1, +inf).
    """
    d = jax.vmap(lambda a: _metrics.pairwise(a, a, metric))(pts)  # [B, C, C]
    c = pts.shape[1]
    eye = jnp.eye(c, dtype=bool)
    mask = valid[:, None, :] & valid[:, :, None] & ~eye[None]
    d = jnp.where(mask, d, jnp.inf)
    with jax.named_scope("select_k"):
        nd, idx = _smallest_k(d, k)
    ok = jnp.isfinite(nd)
    return jnp.where(ok, idx, -1), jnp.where(ok, nd, jnp.inf)


def _emit_knn_edges(
    leaf_ids: np.ndarray,   # [B, C] global ids (-1 pad)
    nbr_idx: np.ndarray,    # [B, C, k] in-leaf indices (-1 pad)
    nbr_dist: np.ndarray,   # [B, C, k]
    direction: str,
) -> EdgeList:
    b, c, k = nbr_idx.shape
    rows = np.broadcast_to(leaf_ids[:, :, None], (b, c, k))
    safe = np.maximum(nbr_idx, 0)
    cols = np.take_along_axis(
        np.broadcast_to(leaf_ids[:, None, :], (b, c, c)), safe, axis=2
    )
    ok = (nbr_idx >= 0) & (rows >= 0) & (rows != cols)  # no self loops
    # (rows == cols can only arise from duplicate ids within a leaf; RBC
    # dedupes on merge, but guard against custom partitioners)
    src = np.where(ok, rows, -1).reshape(-1).astype(np.int32)
    dst = np.where(ok, cols, -1).reshape(-1).astype(np.int32)
    dist = np.where(ok, nbr_dist, np.inf).reshape(-1).astype(np.float32)
    fwd = EdgeList(src, dst, dist)
    if direction == "directed":
        return fwd
    rev = EdgeList(dst.copy(), src.copy(), dist.copy())
    if direction == "inverted":
        return rev
    return fwd.concat(rev)  # bidirected


def emit_knn_edges_jax(
    leaf_ids: jax.Array,   # [B, C] global ids (-1 pad)
    nbr_idx: jax.Array,    # [B, C, k] in-leaf indices (-1 pad)
    nbr_dist: jax.Array,   # [B, C, k]
    *,
    direction: str,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Device-side ``_emit_knn_edges``: flat (src, dst, dist) arrays.

    Fixed output shape [B*C*k] (or [2*B*C*k] bidirected); invalid slots are
    (-1, -1, +inf).  Traceable — the streaming build fuses this into the
    per-chunk jitted step so candidate edges never bounce through the host.
    """
    b, c, k = nbr_idx.shape
    rows = jnp.broadcast_to(leaf_ids[:, :, None], (b, c, k))
    safe = jnp.maximum(nbr_idx, 0)
    cols = jnp.take_along_axis(
        jnp.broadcast_to(leaf_ids[:, None, :], (b, c, c)), safe, axis=2
    )
    ok = (nbr_idx >= 0) & (rows >= 0) & (rows != cols)  # no self loops
    src = jnp.where(ok, rows, -1).reshape(-1).astype(jnp.int32)
    dst = jnp.where(ok, cols, -1).reshape(-1).astype(jnp.int32)
    dist = jnp.where(ok, nbr_dist, jnp.inf).reshape(-1).astype(jnp.float32)
    if direction == "directed":
        return src, dst, dist
    if direction == "inverted":
        return dst, src, dist
    return (jnp.concatenate([src, dst]), jnp.concatenate([dst, src]),
            jnp.concatenate([dist, dist]))  # bidirected


def emit_robust_prune_edges_jax(
    leaf_ids: jax.Array,   # [B, C] global ids (-1 pad)
    keep: jax.Array,       # [B, C, C] bool keep mask from _leaf_robust_prune
    d: jax.Array,          # [B, C, C] masked leaf distance matrix
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Device-side edge emitter for the ``robust_prune`` leaf method.

    Fixed output shape [B*C*C]; invalid slots are (-1, -1, +inf).  The
    ``robust_prune`` analogue of ``emit_knn_edges_jax``: traceable, so the
    streaming build fuses leaf RobustPrune into the per-chunk jitted step
    and its kept edges never bounce through the host.  Emits the same edge
    set as the host path in ``build_leaf_edges`` (which compacts via
    ``np.nonzero``), just padded instead of compacted — HashPrune's
    order-freedom makes the two interchangeable downstream.
    """
    b, c, _ = keep.shape
    rows = jnp.broadcast_to(leaf_ids[:, :, None], (b, c, c))
    cols = jnp.broadcast_to(leaf_ids[:, None, :], (b, c, c))
    ok = keep & (rows >= 0) & (cols >= 0)
    src = jnp.where(ok, rows, -1).reshape(-1).astype(jnp.int32)
    dst = jnp.where(ok, cols, -1).reshape(-1).astype(jnp.int32)
    dist = jnp.where(ok, d, jnp.inf).reshape(-1).astype(jnp.float32)
    return src, dst, dist


def _mst_edges(leaf_ids: np.ndarray, d: np.ndarray, valid: np.ndarray,
               cap: int, sparsify: int) -> EdgeList:
    """Degree-capped Kruskal per leaf over the l-NN sparsified graph."""
    srcs, dsts, dists = [], [], []
    b = leaf_ids.shape[0]
    for li in range(b):
        v = valid[li]
        n = int(v.sum())
        if n < 2:
            continue
        dm = d[li][:n, :n].copy()
        np.fill_diagonal(dm, np.inf)
        l = min(sparsify, n - 1)
        nbr = np.argpartition(dm, l - 1, axis=1)[:, :l]
        rows = np.repeat(np.arange(n), l)
        cols = nbr.reshape(-1)
        w = dm[rows, cols]
        order = np.argsort(w, kind="stable")
        parent = np.arange(n)

        def find(a):
            while parent[a] != a:
                parent[a] = parent[parent[a]]
                a = parent[a]
            return a

        deg = np.zeros(n, dtype=np.int32)
        gids = leaf_ids[li][:n]
        for e in order:
            a, bb = rows[e], cols[e]
            if deg[a] >= cap or deg[bb] >= cap:
                continue
            ra, rb = find(a), find(bb)
            if ra == rb:
                continue
            parent[ra] = rb
            deg[a] += 1
            deg[bb] += 1
            srcs += [gids[a], gids[bb]]
            dsts += [gids[bb], gids[a]]
            dists += [w[e], w[e]]
    return EdgeList(
        np.asarray(srcs, dtype=np.int32),
        np.asarray(dsts, dtype=np.int32),
        np.asarray(dists, dtype=np.float32),
    )


@functools.partial(jax.jit, static_argnames=("metric", "alpha", "max_deg"))
def _leaf_robust_prune(pts, valid, *, metric, alpha, max_deg):
    d = jax.vmap(lambda a: _metrics.pairwise(a, a, metric))(pts)
    c = pts.shape[1]
    eye = jnp.eye(c, dtype=bool)
    mask = valid[:, None, :] & valid[:, :, None] & ~eye[None]
    d = jnp.where(mask, d, jnp.inf)
    b = pts.shape[0]
    # flatten leaves into the batch dim: each leaf row is one "point"
    d_pc = d.reshape(b * c, c)
    d_cc = jnp.broadcast_to(d[:, None, :, :], (b, c, c, c)).reshape(b * c, c, c)
    ids = jnp.broadcast_to(jnp.arange(c, dtype=jnp.int32)[None], (b * c, c))
    keep = robust_prune_mask(d_pc, d_cc, ids, alpha=alpha, max_deg=max_deg)
    return keep.reshape(b, c, c), d


def build_leaf_edges(
    x: np.ndarray,
    leaves_padded: np.ndarray,  # [L, c_max] int32, -1 pad
    params: LeafParams,
    knn_fn=None,
) -> EdgeList:
    """Run the configured leaf method over all leaves; return candidate edges.

    ``knn_fn`` optionally overrides the (pts, valid, k, metric) -> (idx, dist)
    inner kernel — the Pallas FlashKNN kernel plugs in here.
    """
    xj = jnp.asarray(x)
    knn = knn_fn or (lambda pts, valid: leaf_knn_jax(
        pts, valid, k=params.k, metric=params.metric))
    pieces: list[EdgeList] = []
    for ids in iter_leaf_id_chunks(leaves_padded, params.leaf_chunk):
        valid = ids >= 0
        pts = xj[jnp.maximum(jnp.asarray(ids), 0)]
        vj = jnp.asarray(valid)
        if params.method in ("bidirected", "directed", "inverted"):
            ni, nd = knn(pts, vj)
            pieces.append(
                _emit_knn_edges(ids, np.asarray(ni), np.asarray(nd), params.method)
            )
        elif params.method == "mst":
            d = jax.vmap(lambda a: _metrics.pairwise(a, a, params.metric))(pts)
            pieces.append(
                _mst_edges(ids, np.asarray(d), valid, params.mst_degree_cap,
                           params.mst_sparsify)
            )
        elif params.method == "robust_prune":
            keep, d = _leaf_robust_prune(
                pts, vj, metric=params.metric, alpha=params.alpha,
                max_deg=params.max_deg,
            )
            keep = np.asarray(keep)
            d = np.asarray(d)
            li, ri, ci = np.nonzero(keep)
            src = ids[li, ri]
            dst = ids[li, ci]
            ok = (src >= 0) & (dst >= 0)
            pieces.append(EdgeList(
                src[ok].astype(np.int32), dst[ok].astype(np.int32),
                d[li, ri, ci][ok].astype(np.float32),
            ))
        else:
            raise ValueError(f"unknown leaf method {params.method!r}")
    # One concatenate per field: the previous per-piece ``EdgeList.concat``
    # loop re-copied the accumulated prefix every iteration (O(E^2) bytes).
    if not pieces:
        return EdgeList(np.empty(0, np.int32), np.empty(0, np.int32),
                        np.empty(0, np.float32))
    return EdgeList(
        src=np.concatenate([p.src for p in pieces]),
        dst=np.concatenate([p.dst for p in pieces]),
        dist=np.concatenate([p.dist for p in pieces]),
    )
