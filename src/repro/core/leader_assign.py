"""Shared jitted leader-assignment step: the Stage-1 inner loop.

Every PiPNN partitioning variant reduces to the same primitive: given a
block of points and a (possibly padded) set of leaders, compute the
dissimilarity matrix as one GEMM (Sec. 4.1 / 4.2 — the paper's bulk-GEMM
insight) and select each point's ``f`` nearest leaders.  This module is
the single implementation used by

  * the host-orchestrated device ``ball_carve`` (``core/rbc.py``) — the
    recursion's per-subproblem math,
  * the fully-static two-level ``ball_carve_device`` (``core/rbc.py``),
  * the distributed SPMD build's level-0 bucket selection and level-1
    ``assign_chunk`` (``launch/build_index.py``).

The arithmetic mirrors the numpy oracle ``rbc._pairwise_np`` exactly
(same GEMM expansion, same ``max(d, 0)`` clamp for l2) and the top-f
selection uses ``lax.top_k`` on negated distances, which orders equal
distances by ascending leader index — the same tie-break as a stable
argsort.  On this container's CPU backend the XLA GEMM is bit-identical
to numpy's, so device leader assignment reproduces the host oracle's
decisions bit for bit (asserted by tests/test_partitioners.py).

``use_pallas=True`` routes the distance matrix through the Pallas MXU
kernel (``kernels/distance.py``) and the selection through the Pallas
partial-sort (``kernels/topk.py``) — the TPU production path, which keeps
the same semantics but is not tie-break-pinned to the oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.metrics import GEMM_PRECISION
from repro.kernels.topk import topf

INF = jnp.float32(jnp.inf)

__all__ = ["leader_dists", "leader_assign", "topf"]


def leader_dists(points: jax.Array, leaders: jax.Array,
                 *, metric: str = "l2") -> jax.Array:
    """Dissimilarity matrix [..., n, l] between ``points`` [..., n, d] and
    ``leaders`` [..., l, d] via the GEMM expansion (batched over leading
    dims).  Mirrors ``rbc._pairwise_np`` term for term."""
    ip = jnp.einsum("...nd,...ld->...nl", points, leaders,
                    precision=GEMM_PRECISION)
    if metric == "mips":
        return -ip
    if metric == "cosine":
        an = jnp.sqrt(jnp.sum(points * points, axis=-1))[..., :, None]
        bn = jnp.sqrt(jnp.sum(leaders * leaders, axis=-1))[..., None, :]
        return 1.0 - ip / jnp.maximum(an * bn, 1e-30)
    a2 = jnp.sum(points * points, axis=-1)[..., :, None]
    b2 = jnp.sum(leaders * leaders, axis=-1)[..., None, :]
    return jnp.maximum(a2 + b2 - 2.0 * ip, 0.0)


def leader_assign(
    points: jax.Array,          # [..., n, d]
    leaders: jax.Array,         # [..., l, d]
    f: int,
    *,
    metric: str = "l2",
    point_valid: jax.Array | None = None,    # [..., n] bool
    leader_valid: jax.Array | None = None,   # [..., l] bool
    use_pallas: bool = False,
    interpret: bool | None = None,           # None: interpret off-TPU only
) -> jax.Array:
    """Indices [..., n, f] of each point's f nearest leaders, ordered by
    ascending dissimilarity (ties by ascending leader index).

    Invalid leaders are masked to +inf (never selected while
    ``f <= n_valid_leaders``); invalid points see an all-inf row, whose
    arbitrary top-f output callers must mask downstream by their own
    validity — the same contract as the SPMD build's ``assign_chunk``.
    """
    if use_pallas:
        from repro.kernels.distance import pairwise_distance
        from repro.kernels.ops import default_interpret
        if interpret is None:
            interpret = default_interpret()
        batched = points.ndim >= 3
        pb = points if batched else points[None]
        lb = leaders if batched else leaders[None]
        d = pairwise_distance(pb.reshape((-1,) + pb.shape[-2:]),
                              lb.reshape((-1,) + lb.shape[-2:]),
                              metric=metric, interpret=interpret)
        d = d.reshape(points.shape[:-1] + (leaders.shape[-2],))
    else:
        d = leader_dists(points, leaders, metric=metric)
    if leader_valid is not None:
        d = jnp.where(leader_valid[..., None, :], d, INF)
    if point_valid is not None:
        d = jnp.where(point_valid[..., :, None], d, INF)
    if use_pallas:
        from repro.kernels.topk import rowwise_topk
        ni, _ = rowwise_topk(d.reshape((-1,) + d.shape[-2:]), k=f,
                             interpret=interpret)
        return ni.reshape(d.shape[:-1] + (f,))
    return topf(d, f)
