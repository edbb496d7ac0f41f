"""HashPrune — the paper's core contribution (Sec. 3, Algorithm 3).

An online, *history-independent* pruning reservoir.  Per point ``p`` a
reservoir holds at most ``l_max`` candidates keyed by the residual LSH hash
``h_p(c)`` (see sketch.py):

  * a candidate colliding with a stored one keeps whichever is closer to p;
  * a non-colliding candidate into a full reservoir evicts the farthest
    stored candidate iff the newcomer is closer.

Theorem 3.1 (history independence) has a closed form which this module
exploits for the TPU-native batch path:

    R(C) = the l_max nearest-of {min-dist candidate of each hash bucket}.

Two consequences we rely on (and property-test):

  (1) ORDER-FREEDOM: any insertion order yields R(C) — so the batch
      implementation may sort instead of probing a hash table (a
      latency-bound pattern TPUs cannot do).
  (2) MERGEABILITY: R(R(C1) ∪ C2) = R(C1 ∪ C2).  Proof sketch: bucket
      minima only decrease as candidates are added, so a candidate outside
      the l_max nearest bucket-minima of C1 can never re-enter after more
      candidates arrive.  This licenses bounded-memory streaming of
      *batches* (one leaf / one shard at a time) while holding only the
      [n, l_max] reservoir.  Two fold entry points, both donation-friendly
      so the [n, l_max] state never reallocates:

        * ``hashprune_merge_segmented`` (the ``pipnn.build`` and SPMD tile
          step default): applies the lemma twice — the chunk is reduced to
          its own [n, l_max] reservoir by ONE global sort over just the
          chunk's edges, then folded into the persistent reservoir by a
          bounded per-row width-2*l_max merge (per-row sort fallback, or
          the rank-based Pallas kernel in ``kernels/segmented_merge.py``).
          The persistent reservoir never enters a global sort.
        * ``hashprune_merge_flat`` (the oracle): re-expresses the reservoir
          as a flat edge list and re-sorts it together with the chunk —
          simple, but every fold pays O((n*l_max + E_chunk) log ...) sort
          work.  The segmented fold is property-tested bit-identical to it.

Tie-breaking: the paper implicitly assumes general position (distinct
distances).  We make determinism unconditional by ordering candidates by the
lexicographic key (dist, id); both implementations here use it, so they are
bit-identical even with duplicated candidates or tied distances.

Layout note: the paper packs a reservoir slot into 8 bytes (4B id, 2B hash,
2B bf16 dist).  We keep SoA arrays (ids int32, hashes int32, dists f32 —
bf16 optional) which is the TPU-friendly equivalent.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

INVALID_ID = jnp.int32(-1)
INF = jnp.float32(jnp.inf)


class Reservoir(NamedTuple):
    """Batched HashPrune state for n points. All arrays [n, l_max]."""

    ids: jax.Array    # int32, INVALID_ID marks an empty slot
    hashes: jax.Array  # int32 packed residual hash (< 2^16)
    dists: jax.Array  # float32, +inf marks an empty slot

    @property
    def l_max(self) -> int:
        return self.ids.shape[-1]


def reservoir_init(n: int, l_max: int) -> Reservoir:
    return Reservoir(
        ids=jnp.full((n, l_max), INVALID_ID, dtype=jnp.int32),
        hashes=jnp.zeros((n, l_max), dtype=jnp.int32),
        dists=jnp.full((n, l_max), INF, dtype=jnp.float32),
    )


# ---------------------------------------------------------------------------
# Closed-form batch evaluation (the TPU path)
# ---------------------------------------------------------------------------

def _dedup_bucket_min(hashes, dists, ids):
    """Sort candidates by (hash, dist, id); keep only each hash-run's head.

    Returns (dists', ids', hashes') sorted with non-heads masked to
    (+inf, INVALID_ID).  Works on the trailing axis; leading axes batch.
    """
    # lexicographic sort: primary hash, secondary dist, tertiary id
    s_hash, s_dist, s_id = jax.lax.sort(
        (hashes, dists, ids), dimension=-1, num_keys=3
    )
    prev = jnp.roll(s_hash, 1, axis=-1)
    first = jnp.ones_like(s_hash, dtype=bool).at[..., 1:].set(
        s_hash[..., 1:] != prev[..., 1:]
    )
    # Padding entries carry id == INVALID_ID and dist == +inf; hide them too.
    valid = s_id != INVALID_ID
    keep = first & valid
    return (
        jnp.where(keep, s_dist, INF),
        jnp.where(keep, s_id, INVALID_ID),
        jnp.where(keep, s_hash, jnp.int32(0x7FFFFFFF)),
    )


@functools.partial(jax.jit, static_argnames=("l_max",))
def hashprune_batch(
    cand_ids: jax.Array,
    cand_hashes: jax.Array,
    cand_dists: jax.Array,
    *,
    l_max: int,
) -> Reservoir:
    """Evaluate HashPrune's closed form on padded per-point candidate lists.

    cand_ids/hashes/dists: [n, n_cand] (INVALID_ID / +inf padding).
    Returns the Reservoir( [n, l_max] ) — identical to streaming Alg. 3.
    """
    d, i, h = _dedup_bucket_min(cand_hashes, cand_dists, cand_ids)
    # top-l_max by (dist, id): one more lexicographic sort, then truncate
    s_d, s_i, s_h = jax.lax.sort((d, i, h), dimension=-1, num_keys=2)
    n_cand = cand_ids.shape[-1]
    if n_cand >= l_max:
        s_d, s_i, s_h = s_d[..., :l_max], s_i[..., :l_max], s_h[..., :l_max]
    else:
        pad = l_max - n_cand
        s_d = jnp.pad(s_d, [(0, 0)] * (s_d.ndim - 1) + [(0, pad)], constant_values=INF)
        s_i = jnp.pad(s_i, [(0, 0)] * (s_i.ndim - 1) + [(0, pad)], constant_values=-1)
        s_h = jnp.pad(s_h, [(0, 0)] * (s_h.ndim - 1) + [(0, pad)], constant_values=0)
    s_h = jnp.where(s_i == INVALID_ID, 0, s_h)
    return Reservoir(ids=s_i, hashes=s_h, dists=s_d)


@functools.partial(jax.jit)
def hashprune_merge(res: Reservoir, batch: Reservoir | None = None,
                    cand_ids: jax.Array | None = None,
                    cand_hashes: jax.Array | None = None,
                    cand_dists: jax.Array | None = None) -> Reservoir:
    """Merge a new candidate batch into an existing reservoir.

    Valid by the mergeability lemma above; output == one-shot closed form on
    the union of everything ever inserted.
    """
    if batch is not None:
        cand_ids, cand_hashes, cand_dists = batch.ids, batch.hashes, batch.dists
    ids = jnp.concatenate([res.ids, cand_ids], axis=-1)
    hashes = jnp.concatenate([res.hashes, cand_hashes], axis=-1)
    dists = jnp.concatenate([res.dists, cand_dists], axis=-1)
    return hashprune_batch(ids, hashes, dists, l_max=res.l_max)


# ---------------------------------------------------------------------------
# Flat-edge-list evaluation (used by the PiPNN pipeline: one lexicographic
# sort over ALL candidate edges of ALL points at once)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n_points", "l_max"))
def hashprune_flat(
    src: jax.Array,
    dst: jax.Array,
    hashes: jax.Array,
    dists: jax.Array,
    *,
    n_points: int,
    l_max: int,
) -> Reservoir:
    """HashPrune over a flat edge list [(src -> dst, hash, dist)].

    Padding edges use src == n_points (sorts to the end, scattered with
    mode='drop').  This is the PiPNN hot path after leaf building: one
    global sort replaces n independent hash tables.
    """
    e = src.shape[0]
    # (1) bucket-min: sort by (src, hash, dist, dst); heads of (src, hash) runs
    s_src, s_hash, s_dist, s_dst = jax.lax.sort(
        (src, hashes, dists, dst), dimension=0, num_keys=4
    )
    same = (s_src == jnp.roll(s_src, 1)) & (s_hash == jnp.roll(s_hash, 1))
    same = same.at[0].set(False)
    keep = (~same) & (s_src < n_points) & (s_dst != INVALID_ID)
    m_dist = jnp.where(keep, s_dist, INF)
    m_src = jnp.where(keep, s_src, jnp.int32(n_points))
    # (2) per-src top-l_max by (dist, dst): sort by (src, dist, dst)
    f_src, f_dist, f_dst, f_hash = jax.lax.sort(
        (m_src, m_dist, s_dst, s_hash), dimension=0, num_keys=3
    )
    idx = jnp.arange(e, dtype=jnp.int32)
    seg_start = f_src != jnp.roll(f_src, 1)
    seg_start = seg_start.at[0].set(True)
    start_idx = jax.lax.cummax(jnp.where(seg_start, idx, 0))
    rank = idx - start_idx
    ok = (rank < l_max) & (f_src < n_points) & jnp.isfinite(f_dist)
    out = reservoir_init(n_points, l_max)
    row = jnp.where(ok, f_src, n_points)  # out-of-bounds => dropped
    col = jnp.where(ok, rank, l_max)
    ids = out.ids.at[row, col].set(f_dst, mode="drop")
    hs = out.hashes.at[row, col].set(f_hash, mode="drop")
    ds = out.dists.at[row, col].set(f_dist, mode="drop")
    return Reservoir(ids=ids, hashes=hs, dists=ds)


def reservoir_as_edges(
    ids: jax.Array, hashes: jax.Array, dists: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Flatten a reservoir [n, l_max] back into a flat edge list.

    Empty slots become padding edges (src == n) in the ``hashprune_flat``
    convention, so the result can be concatenated with a fresh candidate
    chunk and re-pruned — the mergeability lemma's R(C1) ∪ C2.
    """
    n, l_max = ids.shape
    row = jnp.broadcast_to(
        jnp.arange(n, dtype=jnp.int32)[:, None], (n, l_max)
    ).reshape(-1)
    flat_ids = ids.reshape(-1)
    empty = flat_ids == INVALID_ID
    src = jnp.where(empty, jnp.int32(n), row)
    return src, flat_ids, hashes.reshape(-1), dists.reshape(-1)


def merge_flat_edges(res_ids, res_hashes, res_dists,
                     src, dst, hashes, dists) -> Reservoir:
    """Traceable body of ``hashprune_merge_flat`` (no jit, no donation).

    Call this form when fusing the merge into a larger jitted step — the
    streaming ``pipnn.build`` chunk step and the distributed tile step both
    inline it so leaf k-NN, edge emission, hashing and the reservoir fold
    compile into one program.
    """
    n, l_max = res_ids.shape
    r_src, r_dst, r_h, r_d = reservoir_as_edges(res_ids, res_hashes, res_dists)
    return hashprune_flat(
        jnp.concatenate([r_src, src]),
        jnp.concatenate([r_dst, dst]),
        jnp.concatenate([r_h, hashes]),
        jnp.concatenate([r_d, dists]),
        n_points=n, l_max=l_max,
    )


# Buffer donation lets XLA reuse the old reservoir's [n, l_max] buffers for
# the new one, so the persistent state never reallocates across chunks.
# (On backends without donation support this silently degrades to a copy.)
_merge_flat_jit = jax.jit(merge_flat_edges, donate_argnums=(0, 1, 2))


def hashprune_merge_flat(
    res: Reservoir,
    src: jax.Array,
    dst: jax.Array,
    hashes: jax.Array,
    dists: jax.Array,
) -> Reservoir:
    """Fold a flat candidate-edge chunk into an existing reservoir.

    Equivalent (bit-identical, not just set-equal) to running
    ``hashprune_flat`` once over every edge ever folded in, by the
    mergeability lemma: the reservoir is re-expressed as a flat edge list
    and re-pruned together with the chunk in one global sort.  Peak
    intermediate memory is O(n*l_max + len(src)) — independent of the
    total number of candidate edges.

    ``res`` is DONATED: do not reuse it after the call.  Padding edges use
    the ``hashprune_flat`` convention (src == n, dst == INVALID_ID,
    dist == +inf).
    """
    ids, hs, ds = _merge_flat_jit(res.ids, res.hashes, res.dists,
                                  src, dst, hashes, dists)
    return Reservoir(ids=ids, hashes=hs, dists=ds)


# ---------------------------------------------------------------------------
# Segmented merge: chunk-local bucket dedup + bounded per-row reservoir merge
# ---------------------------------------------------------------------------

def merge_segmented_edges(res_ids, res_hashes, res_dists,
                          src, dst, hashes, dists, *,
                          use_pallas: bool = False,
                          interpret: bool | None = None) -> Reservoir:
    """Segmented fold of a flat candidate-edge chunk into a reservoir.

    ``merge_flat_edges`` re-expresses the whole [n, l_max] reservoir as a
    flat edge list and re-sorts it together with the chunk: every fold pays
    two global O((n*l_max + E_chunk) log ...) multi-key sorts.  This path
    exploits two invariants instead:

      (1) the chunk alone can be bucket-deduped and row-bucketed by ONE
          global sort over just its own edges (``hashprune_flat`` on the
          chunk -> a [n, l_max] chunk reservoir), and
      (2) both reservoirs are per-row sorted by (dist, id) with one slot
          per hash bucket, so folding them is a BOUNDED per-row merge on
          width-2*l_max rows (R(R(C1) ∪ R(C2)) = R(C1 ∪ C2) by Thm 3.1
          applied twice) — the persistent reservoir never enters a global
          sort at all.

    Bit-identical to ``merge_flat_edges`` (both produce rows sorted by
    (dist, id) with identical padding), which stays as the oracle.

    ``use_pallas`` routes the per-row merge through the
    ``kernels/segmented_merge.py`` kernel (rank-based merge of two sorted
    rows + cross-reservoir bucket dedup, no sort); the fallback is the
    per-row ``hashprune_batch`` sort.  Traceable either way — the streaming
    chunk step and the SPMD tile step inline it.  ``interpret=None`` runs
    the kernel compiled on TPU and interpreted elsewhere.
    """
    n, l_max = res_ids.shape
    chunk_res = hashprune_flat(src, dst, hashes, dists,
                               n_points=n, l_max=l_max)
    if use_pallas:
        from repro.kernels.ops import default_interpret
        from repro.kernels.segmented_merge import merge_sorted_reservoirs

        if interpret is None:
            interpret = default_interpret()
        return merge_sorted_reservoirs(
            res_ids, res_hashes, res_dists,
            chunk_res.ids, chunk_res.hashes, chunk_res.dists,
            interpret=interpret)
    return hashprune_batch(
        jnp.concatenate([res_ids, chunk_res.ids], axis=-1),
        jnp.concatenate([res_hashes, chunk_res.hashes], axis=-1),
        jnp.concatenate([res_dists, chunk_res.dists], axis=-1),
        l_max=l_max)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"),
                   donate_argnums=(0, 1, 2))
def _merge_segmented_jit(res_ids, res_hashes, res_dists,
                         src, dst, hashes, dists, *, use_pallas, interpret):
    return merge_segmented_edges(res_ids, res_hashes, res_dists,
                                 src, dst, hashes, dists,
                                 use_pallas=use_pallas, interpret=interpret)


def hashprune_merge_segmented(
    res: Reservoir,
    src: jax.Array,
    dst: jax.Array,
    hashes: jax.Array,
    dists: jax.Array,
    *,
    use_pallas: bool = False,
    interpret: bool | None = None,
) -> Reservoir:
    """Donating jit wrapper over ``merge_segmented_edges``.

    Same contract as ``hashprune_merge_flat`` (``res`` is DONATED; padding
    edges use src == n / dst == INVALID_ID / dist == +inf), but the global
    sort work per fold is O(E_chunk log E_chunk) instead of
    O((n*l_max + E_chunk) log (n*l_max + E_chunk)).
    """
    ids, hs, ds = _merge_segmented_jit(
        res.ids, res.hashes, res.dists, src, dst, hashes, dists,
        use_pallas=use_pallas, interpret=interpret)
    return Reservoir(ids=ids, hashes=hs, dists=ds)


# ---------------------------------------------------------------------------
# Workspace models (validated by the memory auditor, PIPM004)
# ---------------------------------------------------------------------------

def merge_flat_workspace_bytes(n: int, l_max: int, e: int) -> int:
    """Modeled XLA temp bytes of one ``_merge_flat_jit`` fold: the
    reservoir re-expressed as ``n * l_max`` padding edges concatenated
    with the ``e``-edge chunk (src/dst/hash/dist, 16 B/entry), plus one
    sorted copy of the concatenation.  The model is an upper bound the
    memory auditor checks the compiled ledger against at every lattice
    point (``repro.analysis.memory_audit``, PIPM004) and prices the
    deployment envelope with (PIPM003) — keep it in sync with the fold."""
    entries = n * l_max + e
    return 2 * entries * 16


def merge_segmented_workspace_bytes(n: int, l_max: int, e: int) -> int:
    """Modeled XLA temp bytes of one ``_merge_segmented_jit`` fold: the
    chunk-only global sort (``e`` edges in and one sorted copy), the
    [n, l_max] chunk reservoir it produces, and the width-2*l_max
    concatenated rows of the bounded per-row merge plus its sorted copy
    (12 B id+hash+dist per slot).  Independent of the total emitted edge
    count E — only the chunk and the reservoir appear.  Validated by
    PIPM004; priced at the envelope by PIPM003."""
    chunk_sort = 2 * e * 16
    chunk_res = n * l_max * 12
    # concat + sorted copy would be 4 reservoir-sized slot images, but the
    # donated rows are reused in place; the compiled ledger measures ~1x
    # (CPU XLA), so 2x is the calibrated upper bound PIPM004 enforces
    row_merge = 2 * n * l_max * 12
    return chunk_sort + chunk_res + row_merge


# ---------------------------------------------------------------------------
# Streaming reference (faithful Algorithm 3) — the oracle for property tests
# ---------------------------------------------------------------------------

def _less(d1, i1, d2, i2):
    """(dist, id) lexicographic strict less-than."""
    return (d1 < d2) | ((d1 == d2) & (i1 < i2))


def _insert_one(state, cand):
    ids, hashes, dists = state
    cid, chash, cdist = cand
    l_max = ids.shape[0]
    occupied = ids != INVALID_ID
    is_valid = cid != INVALID_ID

    match = occupied & (hashes == chash)
    any_match = jnp.any(match)
    # position of the (unique) hash match
    mpos = jnp.argmax(match)
    closer = _less(cdist, cid, dists[mpos], ids[mpos])

    count = jnp.sum(occupied)
    has_room = count < l_max
    # first empty slot
    epos = jnp.argmax(~occupied)
    # farthest occupied slot by (dist, id) — evict the max
    far_key = jnp.where(occupied, dists, -INF)
    zpos = jnp.argmax(far_key)  # ids tie-break: see note below
    # break dist ties toward larger id (mirror of (dist,id) max)
    is_max_d = occupied & (dists == far_key[zpos]) & jnp.isfinite(far_key[zpos])
    zpos = jnp.where(
        jnp.any(is_max_d), jnp.argmax(jnp.where(is_max_d, ids, -2)), zpos
    )
    evict_ok = _less(cdist, cid, dists[zpos], ids[zpos])

    # decide the write position (or no write)
    write = is_valid & (
        (any_match & closer) | (~any_match & (has_room | evict_ok))
    )
    pos = jnp.where(any_match, mpos, jnp.where(has_room, epos, zpos))
    ids = jnp.where(write, ids.at[pos].set(cid), ids)
    hashes = jnp.where(write, hashes.at[pos].set(chash), hashes)
    dists = jnp.where(write, dists.at[pos].set(cdist), dists)
    return (ids, hashes, dists), None


@functools.partial(jax.jit, static_argnames=("l_max",))
def hashprune_stream(
    cand_ids: jax.Array,
    cand_hashes: jax.Array,
    cand_dists: jax.Array,
    *,
    l_max: int,
) -> Reservoir:
    """Sequential Algorithm 3 for ONE point (candidates [n_cand]).

    O(n_cand * l_max) scan — the reference semantics.  vmap for batches.
    """
    init = (
        jnp.full((l_max,), INVALID_ID, dtype=jnp.int32),
        jnp.zeros((l_max,), dtype=jnp.int32),
        jnp.full((l_max,), INF, dtype=jnp.float32),
    )
    (ids, hashes, dists), _ = jax.lax.scan(
        _insert_one, init, (cand_ids, cand_hashes, cand_dists)
    )
    return Reservoir(ids=ids[None], hashes=hashes[None], dists=dists[None])


def canonicalize(res: Reservoir) -> Reservoir:
    """Sort reservoir slots by (dist, id) so representations compare equal."""
    d, i, h = jax.lax.sort((res.dists, res.ids, res.hashes), dimension=-1, num_keys=2)
    h = jnp.where(i == INVALID_ID, 0, h)
    return Reservoir(ids=i, hashes=h, dists=d)
