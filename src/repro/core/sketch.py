"""Residualized LSH sketches (paper Eq. 1 and the Implementation paragraph
of Sec. 3).

For a point ``p`` and candidate ``c``, HashPrune's individualized hash is

    h_p(c)[i] = 1  if  H_i . (c - p) >= 0  else 0,   i = 1..m

Instead of touching the d-dimensional vectors, we precompute m-dimensional
*sketches* ``Sketch(v) = v @ H.T``; then ``H_i.(c - p) = Sketch(c)[i] -
Sketch(p)[i]`` and the hash is the packed sign-bit pattern of the sketch
difference.  m <= 16 so hashes pack into a uint16 (matching the paper's
8-byte reservoir slot layout: 4B id + 2B hash + 2B bf16 distance).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.metrics import GEMM_PRECISION

MAX_BITS = 16

_POW2 = 2 ** jnp.arange(MAX_BITS, dtype=jnp.int32)  # bit i -> weight 2^i


def make_hyperplanes(key: jax.Array, m: int, d: int, dtype=jnp.float32) -> jax.Array:
    """Sample ``m`` random hyperplane normals through the origin, shape [m, d]."""
    if not 1 <= m <= MAX_BITS:
        raise ValueError(f"m must be in [1, {MAX_BITS}], got {m}")
    return jax.random.normal(key, (m, d), dtype=dtype)


def sketch(x: jax.Array, hyperplanes: jax.Array) -> jax.Array:
    """Project points [..., d] onto hyperplanes -> sketches [..., m].

    One GEMM over the whole dataset; the only place the full-dimensional
    vectors are touched by the hashing machinery.
    """
    return jnp.matmul(x, hyperplanes.T, precision=GEMM_PRECISION)


def hash_from_sketches(cand_sketch: jax.Array, point_sketch: jax.Array) -> jax.Array:
    """Packed residual hash h_p(c) from sketches.

    cand_sketch: [..., m] sketches of candidates c
    point_sketch: [..., m] sketches of the owning points p (broadcastable)
    returns int32 in [0, 2^m), the concatenated sign bits of Sketch(c)-Sketch(p).
    """
    bits = (cand_sketch - point_sketch) >= 0.0  # [..., m] bool
    m = bits.shape[-1]
    return jnp.sum(bits.astype(jnp.int32) * _POW2[:m], axis=-1)


@functools.partial(jax.jit)
def sketch_jit(x: jax.Array, hyperplanes: jax.Array) -> jax.Array:
    return sketch(x, hyperplanes)


def edge_hashes_from_ids(
    sketches: jax.Array,   # [n, m] precomputed point sketches
    src: jax.Array,        # [E] int32 edge sources (may contain -1 padding)
    dst: jax.Array,        # [E] int32 edge destinations
    *,
    use_pallas: bool = False,
    interpret: bool | None = None,
) -> jax.Array:
    """Residual hashes h_src(dst) [E] int32 for a flat edge list.

    Gathers the two sketch rows per edge and packs the sign bits, either
    through the fused Pallas kernel (``use_pallas=True``; ``interpret``
    runs it in the interpreter, and ``None`` does so only off-TPU) or the
    pure-jnp ``hash_from_sketches``.  Both produce identical int32 hashes.
    Traceable: the streaming build calls this inside its fused chunk step.
    """
    s_sk = sketches[jnp.maximum(src, 0)]
    d_sk = sketches[jnp.maximum(dst, 0)]
    if use_pallas:
        from repro.kernels.edge_hash import edge_hashes  # no core->kernels cycle
        from repro.kernels.ops import default_interpret

        if interpret is None:
            interpret = default_interpret()
        return edge_hashes(s_sk, d_sk, interpret=interpret)
    return hash_from_sketches(d_sk, s_sk)


def collision_probability(theta: jax.Array, m: int) -> jax.Array:
    """P[h_p(c) = h_p(c')] = (1 - theta/pi)^m for residual angle theta.

    The classic SimHash bound (Charikar'02) the paper cites in 'Why HashPrune
    Works'.  Used by tests to sanity-check the empirical collision rate.
    """
    return (1.0 - theta / jnp.pi) ** m
