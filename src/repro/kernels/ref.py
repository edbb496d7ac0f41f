"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantics; kernels must match them (tests sweep shapes and
dtypes with assert_allclose against these).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def pairwise_distance_ref(
    a: jax.Array, b: jax.Array, metric: str = "l2"
) -> jax.Array:
    """Batched pairwise dissimilarity. a: [B, M, D], b: [B, N, D] -> [B, M, N].

    Accumulation in f32 regardless of input dtype (bf16/f32 inputs).
    """
    a32 = a.astype(jnp.float32)
    b32 = b.astype(jnp.float32)
    ip = jnp.einsum("bmd,bnd->bmn", a32, b32,
                    precision=jax.lax.Precision.HIGHEST)
    if metric == "mips":
        return -ip
    if metric == "cosine":
        an = jnp.linalg.norm(a32, axis=-1)[:, :, None]
        bn = jnp.linalg.norm(b32, axis=-1)[:, None, :]
        return 1.0 - ip / jnp.maximum(an * bn, 1e-30)
    a2 = jnp.sum(a32 * a32, axis=-1)[:, :, None]
    b2 = jnp.sum(b32 * b32, axis=-1)[:, None, :]
    return jnp.maximum(a2 + b2 - 2.0 * ip, 0.0)


def pairwise_distance_int8_ref(a: jax.Array, b: jax.Array) -> jax.Array:
    """Quantized squared-L2 (paper Sec. 6 future work). int8 in, int32 out.

    ||a-b||^2 = a.a + b.b - 2 a.b, exact in int32 for d <= 2^15.
    """
    a32 = a.astype(jnp.int32)
    b32 = b.astype(jnp.int32)
    ip = jnp.einsum("bmd,bnd->bmn", a32, b32)
    a2 = jnp.sum(a32 * a32, axis=-1)[:, :, None]
    b2 = jnp.sum(b32 * b32, axis=-1)[:, None, :]
    return a2 + b2 - 2 * ip


def leaf_topk_ref(
    pts: jax.Array,    # [B, C, D]
    valid: jax.Array,  # [B, C] bool
    *,
    k: int,
    metric: str = "l2",
) -> tuple[jax.Array, jax.Array]:
    """FlashKNN oracle: per-row k nearest co-leaf points (self/pad excluded).

    Returns (idx [B, C, k] in-leaf positions, -1 pad; dist [B, C, k], +inf pad).
    Ties broken toward the smaller in-leaf index (matches kernel).
    """
    d = pairwise_distance_ref(pts, pts, metric)
    c = pts.shape[1]
    eye = jnp.eye(c, dtype=bool)
    mask = valid[:, None, :] & valid[:, :, None] & ~eye[None]
    d = jnp.where(mask, d, jnp.inf)
    # stable top-k with index tie-breaking: sort (dist, idx)
    iota = jnp.broadcast_to(jnp.arange(c, dtype=jnp.int32), d.shape)
    sd, si = jax.lax.sort((d, iota), dimension=-1, num_keys=2)
    if k > c:   # fewer co-leaf points than k: the tail stays padding
        pad = [(0, 0)] * (sd.ndim - 1) + [(0, k - c)]
        sd = jnp.pad(sd, pad, constant_values=jnp.inf)
        si = jnp.pad(si, pad, constant_values=-1)
    sd, si = sd[..., :k], si[..., :k]
    ok = jnp.isfinite(sd)
    return jnp.where(ok, si, -1), jnp.where(ok, sd, jnp.inf)


def rowwise_topk_ref(
    d: jax.Array,      # [B, M, N] dissimilarities (+inf = masked)
    *,
    k: int,
) -> tuple[jax.Array, jax.Array]:
    """Vectorized partial-sort oracle (the paper's VQPartialSort analogue).

    Returns (idx [B, M, k], vals [B, M, k]); ties toward smaller index.
    """
    iota = jnp.broadcast_to(
        jnp.arange(d.shape[-1], dtype=jnp.int32), d.shape
    )
    sd, si = jax.lax.sort((d, iota), dimension=-1, num_keys=2)
    sd, si = sd[..., :k], si[..., :k]
    ok = jnp.isfinite(sd)
    return jnp.where(ok, si, -1), jnp.where(ok, sd, jnp.inf)


def quantize_symmetric(
    v: jax.Array, eps: float = 1e-12
) -> tuple[jax.Array, jax.Array]:
    """Per-row symmetric int8 scalar quantization over the last axis.

    ``scale = max(|v|) / 127`` (clamped to ``eps`` so zero rows quantize to
    zeros instead of dividing by zero), ``q = clip(round(v / scale))``.
    Returns ``(q int8 [..., d], scale f32 [...])``.  This is THE
    quantization scheme of the repo — the SPMD build's int8 routing, the
    int8 ``ServingIndex`` packing, and the gather-distance kernel's
    query-side quantization all use it, so kernel and oracle quantize
    bit-identically (max is order-independent, round/clip elementwise).

    ``scale`` is formed as ``max * (1/127)`` — an explicit f32 reciprocal
    multiply, NOT a division: XLA strength-reduces constant-divisor
    divisions to reciprocal multiplies under jit but not eagerly, which
    would put jitted (kernel) and eager (oracle) scales one ulp apart and
    break the bit-for-bit interpret tests.
    """
    v32 = v.astype(jnp.float32)
    inv127 = jnp.float32(1.0 / 127.0)
    scale = jnp.maximum(jnp.max(jnp.abs(v32), axis=-1), eps) * inv127
    q = jnp.clip(jnp.round(v32 / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def gather_distance_int8_core(
    points: jax.Array,   # [n, d] int8 (quantize_symmetric packing)
    scales: jax.Array,   # [n] f32 per-point dequantization scales
    norms: jax.Array,    # [n] f32 EXACT norms (computed pre-quantization)
    q8: jax.Array,       # [Q, d] int8 pre-quantized queries
    sq: jax.Array,       # [Q] f32 query dequantization scales
    q_norms: jax.Array,  # [Q] f32 query norm terms (metrics.point_norms)
    nbr_ids: jax.Array,  # [Q, C] int32, -1 = padding
    *,
    metric: str = "l2",
) -> jax.Array:
    """Quantized gather + distance on PRE-quantized queries: [Q, C] f32.

    The serving engine's XLA path quantizes the (loop-invariant) query
    batch ONCE and calls this per beam-search step, skipping the
    per-step requantize that the self-contained oracle wrapper pays.
    """
    safe = jnp.maximum(nbr_ids, 0)
    g = points[safe].astype(jnp.int32)                   # [Q, C, d]
    sg = scales[safe]                                    # [Q, C] f32
    ip = jnp.einsum("qd,qcd->qc", q8.astype(jnp.int32), g)
    ipf = ip.astype(jnp.float32) * (sq[:, None] * sg)
    if metric == "mips":
        d = -ipf
    elif metric == "cosine":
        d = 1.0 - ipf / jnp.maximum(q_norms[:, None] * norms[safe], 1e-30)
    else:
        d = jnp.maximum(q_norms[:, None] + norms[safe] - 2.0 * ipf, 0.0)
    return jnp.where(nbr_ids >= 0, d, jnp.inf)


def gather_distance_int8_ref(
    points: jax.Array,   # [n, d] int8 (quantize_symmetric packing)
    scales: jax.Array,   # [n] f32 per-point dequantization scales
    norms: jax.Array,    # [n] f32 EXACT norms (computed pre-quantization)
    queries: jax.Array,  # [Q, d] f32
    q_norms: jax.Array,  # [Q] f32 query norm terms (metrics.point_norms)
    nbr_ids: jax.Array,  # [Q, C] int32, -1 = padding
    *,
    metric: str = "l2",
) -> jax.Array:
    """Quantized gather + distance oracle for int8 serving: [Q, C] f32.

    The query is quantized per-row with the SAME symmetric scheme as the
    packed points (``quantize_symmetric``), the inner product accumulates
    exactly in int32, and only that term is rescaled:
    ``ip ~= s_q * s_p * <q8, p8>``.  Both norm halves of the expansion
    stay EXACT — ``norms`` are f32 norms of the original points and
    ``q_norms`` of the f32 queries (``metrics.point_norms`` — a query
    is just a point on the norm side) — so
    quantization error enters through the inner product alone.  The
    Pallas kernel (``kernels.gather_distance.gather_distance_int8``)
    matches this bit-for-bit in interpret mode: integer ops are exact,
    every f32 op is written in the same order on both sides, and the
    quantization itself is row-local and order-independent, so WHERE it
    runs (per kernel tile, hoisted once per batch in the engine, or here
    per call) cannot change the bits.
    """
    q8, sq = quantize_symmetric(queries)
    return gather_distance_int8_core(points, scales, norms, q8, sq,
                                     q_norms, nbr_ids, metric=metric)


def gather_distance_ref(
    points: jax.Array,   # [n, d] (f32 or downcast)
    norms: jax.Array,    # [n] f32 metric-dependent norms (metrics.point_norms)
    queries: jax.Array,  # [Q, d]
    nbr_ids: jax.Array,  # [Q, C] int32, -1 = padding
    *,
    metric: str = "l2",
) -> jax.Array:
    """Fused gather + distance oracle for the serving path: [Q, C] f32.

    ``out[q, c]`` is the dissimilarity between ``queries[q]`` and
    ``points[nbr_ids[q, c]]`` (+inf where ``nbr_ids < 0``).  The point-side
    norm term comes from the precomputed ``norms`` (f32, computed before
    any dtype downcast of ``points``); the inner product is accumulated in
    f32 regardless of the points dtype.
    """
    q32 = queries.astype(jnp.float32)
    safe = jnp.maximum(nbr_ids, 0)
    g = points[safe].astype(jnp.float32)                 # [Q, C, d]
    # broadcast-multiply + reduce: XLA CPU lowers this far better than a
    # batched-matvec einsum (the TPU path is the Pallas kernel's MXU
    # dot_general; both accumulate in f32)
    ip = jnp.sum(q32[:, None, :] * g, axis=-1)
    if metric == "mips":
        d = -ip
    elif metric == "cosine":
        qn = jnp.linalg.norm(q32, axis=-1)
        d = 1.0 - ip / jnp.maximum(qn[:, None] * norms[safe], 1e-30)
    else:
        q2 = jnp.sum(q32 * q32, axis=-1)
        d = jnp.maximum(q2[:, None] + norms[safe] - 2.0 * ip, 0.0)
    return jnp.where(nbr_ids >= 0, d, jnp.inf)


def gather_distance_hbm_ref(
    points: jax.Array,   # [n, d] (f32 or downcast)
    norms: jax.Array,    # [n] f32 metric-dependent norms (metrics.point_norms)
    queries: jax.Array,  # [Q, d]
    nbr_ids: jax.Array,  # [Q, C] int32, -1 = padding
    *,
    metric: str = "l2",
) -> jax.Array:
    """Bit-identity oracle for the HBM-streaming f32 kernel: [Q, C] f32.

    Same SEMANTICS as ``gather_distance_ref`` (allclose-tested), but the
    f32 arithmetic mirrors the streaming kernel's shapes exactly so the
    match is bit-for-bit in interpret mode: ``d`` is zero-padded to the
    lane width (the kernel's VMEM scratch rows) and the inner product is
    the elementwise-multiply + last-axis ``jnp.sum`` the kernel performs
    per gathered row — f32 sum reductions are only bit-stable when both
    sides reduce the same padded extent in the same order.  The int8
    streaming kernel needs no separate oracle: its accumulation is int32
    (order-free) so ``gather_distance_int8_ref`` already matches it
    bit-for-bit.
    """
    lane = 128
    pad = (-queries.shape[1]) % lane
    q32 = jnp.pad(queries.astype(jnp.float32), ((0, 0), (0, pad)))
    pts = jnp.pad(points, ((0, 0), (0, pad)))
    safe = jnp.maximum(nbr_ids, 0)
    g = pts[safe].astype(jnp.float32)                    # [Q, C, dp]
    ip = jnp.sum(g * q32[:, None, :], axis=-1)
    if metric == "mips":
        d = -ip
    elif metric == "cosine":
        qn = jnp.sqrt(jnp.sum(q32 * q32, axis=-1))
        d = 1.0 - ip / jnp.maximum(qn[:, None] * norms[safe], 1e-30)
    else:
        q2 = jnp.sum(q32 * q32, axis=-1)
        d = jnp.maximum(q2[:, None] + norms[safe] - 2.0 * ip, 0.0)
    return jnp.where(nbr_ids >= 0, d, jnp.inf)


def sketch_hash_ref(
    x: jax.Array,           # [N, D] points
    hyperplanes: jax.Array,  # [M_BITS, D]
) -> jax.Array:
    """Fused sketch+nothing oracle: sketches [N, M_BITS] f32.

    (Bit packing happens per-edge; the kernel fuses the GEMM + padding.)
    """
    return jnp.matmul(x.astype(jnp.float32),
                      hyperplanes.astype(jnp.float32).T,
                      precision=jax.lax.Precision.HIGHEST)


def edge_hashes_ref(src_sketch: jax.Array, dst_sketch: jax.Array) -> jax.Array:
    """Packed residual hashes [E] int32 — oracle for ``edge_hashes``.

    Eq. 1: the concatenated sign bits of Sketch(dst) - Sketch(src),
    weighted by powers of two (bit i of the hash is sketch column i).
    """
    bits = ((dst_sketch - src_sketch) >= 0.0).astype(jnp.int32)
    m = bits.shape[-1]
    weights = 2 ** jnp.arange(m, dtype=jnp.int32)
    return jnp.sum(bits * weights, axis=-1)


def merge_sorted_reservoirs_ref(
    a_ids: jax.Array, a_hashes: jax.Array, a_dists: jax.Array,
    b_ids: jax.Array, b_hashes: jax.Array, b_dists: jax.Array,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """R(A ∪ B) oracle for ``merge_sorted_reservoirs`` — sort-based, so it
    shares no code with the kernel's rank-based one-hot merge.

    Per row: drop the loser of every cross-side hash collision (smaller
    (dist, id) key wins, exact ties keep A), sort the survivors of the
    concatenated row by (dist, id), truncate to l_max, pad with
    (id -1, hash 0, dist +inf).  Returns ``(ids, hashes, dists)``.
    """
    ad = a_dists.astype(jnp.float32)
    bd = b_dists.astype(jnp.float32)
    l = a_ids.shape[1]
    va, vb = a_ids != -1, b_ids != -1

    def lt(d1, i1, d2, i2):
        return (d1 < d2) | ((d1 == d2) & (i1 < i2))

    b_lt_a = lt(bd[:, None, :], b_ids[:, None, :],
                ad[:, :, None], a_ids[:, :, None])        # [n, lA, lB]
    pair_ok = va[:, :, None] & vb[:, None, :]
    collide = (a_hashes[:, :, None] == b_hashes[:, None, :]) & pair_ok
    keep_a = va & ~jnp.any(collide & b_lt_a, axis=2)
    keep_b = vb & ~jnp.any(collide & ~b_lt_a, axis=1)

    keep = jnp.concatenate([keep_a, keep_b], axis=1)
    ids = jnp.where(keep, jnp.concatenate([a_ids, b_ids], axis=1), -1)
    hs = jnp.where(keep, jnp.concatenate([a_hashes, b_hashes], axis=1), 0)
    ds = jnp.where(keep, jnp.concatenate([ad, bd], axis=1), jnp.inf)

    order = jnp.lexsort((ids, ds), axis=1)
    take = jnp.take_along_axis
    return (take(ids, order, 1)[:, :l], take(hs, order, 1)[:, :l],
            take(ds, order, 1)[:, :l])
