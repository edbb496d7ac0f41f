"""Pallas TPU kernel: bounded per-row merge of two sorted HashPrune
reservoirs (the segmented-merge hot loop, hashprune.py).

Inputs are two [n, l_max] reservoirs whose rows satisfy the HashPrune
invariants: sorted ascending by (dist, id), at most one slot per residual
hash bucket, padding (id == -1, dist == +inf) at the tail.  The kernel
produces R(A ∪ B) per row without any sort:

  * cross-reservoir bucket dedup — within a row each side already holds its
    bucket minima, so a collision can only pair an A slot with a B slot:
    one [l, l] hash-equality compare per side decides the losers
    (lexicographic (dist, id); ties keep A);
  * rank-based merge — each surviving slot's output position is its own
    survivor rank plus the count of survivors on the other side with a
    smaller key (two more [l, l] compares), so the merged row materializes
    through one-hot selects instead of a sort network;
  * truncate to l_max, pad with (id -1, hash 0, dist +inf).

Everything is elementwise compares + reductions on one row's [l, l]
tiles — pure VPU/XLU work, no MXU, no gather/scatter, no cumsum (Mosaic
has none: the prefix counts are one more [l, l] compare).  Bit-identical
to the ``hashprune_batch``-based fallback in ``merge_segmented_edges``
(asserted by tests in interpret mode) and to
``kernels.ref.merge_sorted_reservoirs_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.hashprune import Reservoir

_ROWS = 8  # row block per grid step (f32 sublane tile)


def _lt(d1, i1, d2, i2):
    """(dist, id) lexicographic strict less-than, broadcasting."""
    return (d1 < d2) | ((d1 == d2) & (i1 < i2))


def _merge_rows_kernel(a_i_ref, a_h_ref, a_d_ref,
                       b_i_ref, b_h_ref, b_d_ref,
                       o_i_ref, o_h_ref, o_d_ref, *, l: int):
    """One block of ``_ROWS`` rows, one row at a time on [l, l] tiles.

    Each pairwise quantity needs one side along sublanes (a column) and
    the other along lanes (a row); the columns come from in-register
    transposes of the [_ROWS, l] blocks.  The per-row results leave in
    column form and are transposed back once per block."""
    rows = a_i_ref.shape[0]
    ai, ah, ad = a_i_ref[...], a_h_ref[...], a_d_ref[...]   # [R, l]
    bi, bh, bd = b_i_ref[...], b_h_ref[...], b_d_ref[...]
    ai_t, ah_t, ad_t, bi_t, bh_t, bd_t = (
        x.T for x in (ai, ah, ad, bi, bh, bd))               # [l, R]
    sub = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
    before = sub < lane                      # [i', i]: i' strictly before i
    row_id = jax.lax.broadcasted_iota(jnp.int32, (rows, l), 0)
    col_id = jax.lax.broadcasted_iota(jnp.int32, (l, rows), 1)

    def row(x, r):
        return x[r:r + 1, :]                 # [1, l]

    def col(x_t, r):
        return x_t[:, r:r + 1]               # [l, 1]

    def b_lt_a(r):
        """[j, i]: B slot j's key is below A slot i's (B on sublanes)."""
        return _lt(col(bd_t, r), col(bi_t, r), row(ad, r), row(ai, r))

    def a_le_b(r):
        """[i, j]: A slot i's key is not above B slot j's (A on
        sublanes) — A wins exact (dist, id) ties."""
        return ~_lt(row(bd, r), row(bi, r), col(ad_t, r), col(ai_t, r))

    # bucket dedup: a cross-side hash collision drops the larger key
    keep_a = jnp.zeros((rows, l), jnp.int32)
    keep_b = jnp.zeros((rows, l), jnp.int32)
    for r in range(rows):
        va, vb = row(ai, r) != -1, row(bi, r) != -1
        va_t, vb_t = col(ai_t, r) != -1, col(bi_t, r) != -1
        hit_b = (col(bh_t, r) == row(ah, r)) & vb_t & va & b_lt_a(r)
        ka = va & ~jnp.any(hit_b, axis=0, keepdims=True)
        hit_a = (col(ah_t, r) == row(bh, r)) & va_t & vb & a_le_b(r)
        kb = vb & ~jnp.any(hit_a, axis=0, keepdims=True)
        keep_a = jnp.where(row_id == r, ka.astype(jnp.int32), keep_a)
        keep_b = jnp.where(row_id == r, kb.astype(jnp.int32), keep_b)
    keep_a_t, keep_b_t = keep_a.T, keep_b.T

    # survivor rank = own-side survivors before me + other-side survivors
    # with a smaller key; each survivor lands in output slot = its rank
    out_i = jnp.zeros((l, rows), jnp.int32)
    out_h = jnp.zeros((l, rows), jnp.int32)
    out_d = jnp.zeros((l, rows), jnp.float32)
    for r in range(rows):
        ka_t, kb_t = col(keep_a_t, r) == 1, col(keep_b_t, r) == 1
        ka, kb = row(keep_a, r) == 1, row(keep_b, r) == 1
        pos_a = (jnp.sum((ka_t & before).astype(jnp.int32), axis=0,
                         keepdims=True)
                 + jnp.sum((kb_t & b_lt_a(r)).astype(jnp.int32), axis=0,
                           keepdims=True))                   # [1, l]
        pos_b = (jnp.sum((kb_t & before).astype(jnp.int32), axis=0,
                         keepdims=True)
                 + jnp.sum((ka_t & a_le_b(r)).astype(jnp.int32), axis=0,
                           keepdims=True))
        oh_a = ka & (pos_a == sub)           # [slot, i]
        oh_b = kb & (pos_b == sub)           # [slot, j]

        def pick(oh, x, fill):
            v = jnp.sum(jnp.where(oh, x, 0), axis=1, keepdims=True)
            return jnp.where(jnp.any(oh, axis=1, keepdims=True), v, fill)

        taken = jnp.any(oh_a | oh_b, axis=1, keepdims=True)
        oi = (pick(oh_a, row(ai, r), 0) + pick(oh_b, row(bi, r), 0)
              - jnp.where(taken, 0, 1))
        oh = pick(oh_a, row(ah, r), 0) + pick(oh_b, row(bh, r), 0)
        od = jnp.minimum(pick(oh_a, row(ad, r), jnp.inf),
                         pick(oh_b, row(bd, r), jnp.inf))
        out_i = jnp.where(col_id == r, oi, out_i)
        out_h = jnp.where(col_id == r, oh, out_h)
        out_d = jnp.where(col_id == r, od, out_d)
    o_i_ref[...] = out_i.T
    o_h_ref[...] = out_h.T
    o_d_ref[...] = out_d.T


@functools.partial(jax.jit, static_argnames=("interpret",))
def merge_sorted_reservoirs(
    a_ids: jax.Array, a_hashes: jax.Array, a_dists: jax.Array,
    b_ids: jax.Array, b_hashes: jax.Array, b_dists: jax.Array,
    *,
    interpret: bool = False,
) -> Reservoir:
    """R(A ∪ B) for two per-row-sorted [n, l_max] reservoirs.

    Output rows sorted by (dist, id), padded with (-1, 0, +inf) — the same
    representation ``hashprune_batch`` produces.
    """
    n, l = a_ids.shape
    pad = (-n) % _ROWS
    if pad:
        pr = lambda x, v: jnp.pad(x, ((0, pad), (0, 0)), constant_values=v)
        a_ids, a_hashes, a_dists = pr(a_ids, -1), pr(a_hashes, 0), pr(a_dists, jnp.inf)
        b_ids, b_hashes, b_dists = pr(b_ids, -1), pr(b_hashes, 0), pr(b_dists, jnp.inf)
    rows = a_ids.shape[0]
    spec = pl.BlockSpec((_ROWS, l), lambda r: (r, 0))
    out = pl.pallas_call(
        functools.partial(_merge_rows_kernel, l=l),
        out_shape=(
            jax.ShapeDtypeStruct((rows, l), jnp.int32),
            jax.ShapeDtypeStruct((rows, l), jnp.int32),
            jax.ShapeDtypeStruct((rows, l), jnp.float32),
        ),
        grid=(rows // _ROWS,),
        in_specs=[spec] * 6,
        out_specs=(spec, spec, spec),
        interpret=interpret,
    )(a_ids, a_hashes, a_dists, b_ids, b_hashes, b_dists)
    ids, hs, ds = (x[:n] for x in out)
    return Reservoir(ids=ids, hashes=hs, dists=ds)
