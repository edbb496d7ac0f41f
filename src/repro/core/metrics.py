"""Dissimilarity measures used throughout PiPNN.

The paper evaluates on L2 (BigANN/DEEP/SPACEV/Turing/OpenAI) and MIPS
(WikiCohere, Text2Image).  All measures here are *dissimilarities*: smaller is
closer.  Squared L2 is used internally (order-equivalent to L2, cheaper, and
what the GEMM expansion produces natively).
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

Metric = Literal["l2", "mips", "cosine"]

VALID_METRICS = ("l2", "mips", "cosine")

# Precision of every f32 distance GEMM on device.  The TPU's default runs
# an f32 matmul as one bf16 pass (8-bit mantissa), which reorders near
# neighbours: on a v5e its inner products were off by 1.5e-3 relative,
# and a top-10 from those distances missed 2% of the exact neighbours.
# HIGHEST keeps f32 semantics (the host numpy mirrors' and CPU XLA's).
GEMM_PRECISION = jax.lax.Precision.HIGHEST


def _check(metric: str) -> None:
    if metric not in VALID_METRICS:
        raise ValueError(f"unknown metric {metric!r}; expected one of {VALID_METRICS}")


def pairwise(a: jax.Array, b: jax.Array, metric: Metric = "l2") -> jax.Array:
    """Dense dissimilarity matrix between rows of ``a`` [n,d] and ``b`` [m,d].

    Uses the GEMM expansion ``||a-b||^2 = ||a||^2 + ||b||^2 - 2 a.b`` so the
    hot loop is a matrix product (the paper's core implementation insight,
    Sec. 4.2 / Supplement A.4 — Eigen on CPU, the MXU here).
    """
    _check(metric)
    ip = jnp.matmul(a, b.T, precision=GEMM_PRECISION)  # [n, m] — the GEMM
    if metric == "mips":
        return -ip
    if metric == "cosine":
        an = jnp.linalg.norm(a, axis=-1, keepdims=True)
        bn = jnp.linalg.norm(b, axis=-1, keepdims=True)
        return 1.0 - ip / jnp.maximum(an * bn.T, 1e-30)
    # squared L2
    a2 = jnp.sum(a * a, axis=-1)[:, None]
    b2 = jnp.sum(b * b, axis=-1)[None, :]
    d = a2 + b2 - 2.0 * ip
    return jnp.maximum(d, 0.0)


def point_to_points(q: jax.Array, xs: jax.Array, metric: Metric = "l2") -> jax.Array:
    """Dissimilarity from a single point ``q`` [d] to rows of ``xs`` [m,d]."""
    return pairwise(q[None, :], xs, metric)[0]


@functools.partial(jax.jit, static_argnames=("metric",))
def pairwise_jit(a: jax.Array, b: jax.Array, metric: Metric = "l2") -> jax.Array:
    return pairwise(a, b, metric)


@functools.partial(jax.jit, static_argnames=("metric",))
def point_norms(x: jax.Array, metric: Metric = "l2") -> jax.Array:
    """Metric-dependent per-point norms used by the gather-distance path
    (``kernels.ref.gather_distance_ref`` / the Pallas kernel): squared L2
    norms for ``l2``, L2 norms for ``cosine``, zeros for ``mips`` (unused).
    Always f32 — compute these BEFORE any points-dtype downcast so the
    norm half of the expansion keeps full precision.
    """
    _check(metric)
    x32 = x.astype(jnp.float32)
    if metric == "cosine":
        return jnp.linalg.norm(x32, axis=-1)
    if metric == "l2":
        return jnp.sum(x32 * x32, axis=-1)
    return jnp.zeros((x.shape[0],), jnp.float32)
