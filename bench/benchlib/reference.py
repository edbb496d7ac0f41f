"""The plain reference: exact nearest neighbours by brute force.

Independent of the program: it imports nothing of ``repro`` and reads only
the corpus and what the program returned.  Distances are squared L2.

* ``exact_topk`` — the exact top-k of each query.  Candidates come from a
  full distance GEMM at ``Precision.HIGHEST`` on the device (f32 within
  ~1e-6), and the top ``k + margin`` of them are ranked again in float64
  on the host, so the answer is the float64 top-k (ties by lower id).
* ``answer_sq_dists`` — float64 distances from each query to the ids it
  was answered with.
* ``edge_sq_dists`` — each graph edge's length, as the sum of squared
  differences (no norm expansion, so no cancellation), in f32 on the
  device, block by block.
* ``reachable`` — the points a graph reaches from its entry point.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


@functools.partial(jax.jit, static_argnames=("kk",))
def _candidates(x, x2, q, kk):
    ip = jnp.matmul(q, x.T, precision=HIGHEST)
    d = x2[None, :] - 2.0 * ip          # + |q|^2 leaves the order unchanged
    return jax.lax.top_k(-d, kk)[1]


def _f64_sq_dists(x: np.ndarray, qs: np.ndarray, ids: np.ndarray
                  ) -> np.ndarray:
    """float64 ||q_i - x[ids[i, j]]||^2; ids < 0 give +inf."""
    safe = np.maximum(ids, 0)
    diff = x[safe].astype(np.float64) - qs.astype(np.float64)[:, None, :]
    d = np.einsum("mkd,mkd->mk", diff, diff)
    return np.where(ids >= 0, d, np.inf)


def exact_topk(x: np.ndarray, queries: np.ndarray, k: int = 10, *,
               block: int = 1024, margin: int = 22) -> np.ndarray:
    """[Q, k] int64 ids of the exact nearest points, nearest first."""
    kk = min(k + margin, x.shape[0])
    xd = jnp.asarray(x, jnp.float32)
    x2 = jnp.sum(xd * xd, axis=1)
    out = np.empty((queries.shape[0], k), np.int64)
    for s in range(0, queries.shape[0], block):
        q = queries[s:s + block]
        nq = q.shape[0]
        qp = np.zeros((block, x.shape[1]), np.float32)
        qp[:nq] = q
        cand = np.asarray(_candidates(xd, x2, jnp.asarray(qp), kk))[:nq]
        d = _f64_sq_dists(x, q, cand)
        order = np.lexsort((cand, d), axis=1)[:, :k]
        out[s:s + nq] = np.take_along_axis(cand, order, axis=1)
    return out


def answer_sq_dists(x: np.ndarray, queries: np.ndarray, ids: np.ndarray,
                    *, block: int = 4096) -> np.ndarray:
    """[m, k] float64 squared distances from ``queries[i]`` to
    ``x[ids[i, j]]`` (+inf where ``ids`` is -1)."""
    out = np.empty(ids.shape, np.float64)
    for s in range(0, ids.shape[0], block):
        out[s:s + block] = _f64_sq_dists(x, queries[s:s + block],
                                         ids[s:s + block])
    return out


@jax.jit
def _edge_block(x, rows, nbrs):
    diff = x[jnp.maximum(nbrs, 0)] - x[rows][:, None, :]
    return jnp.sum(diff * diff, axis=-1)


def edge_sq_dists(x: np.ndarray, graph: np.ndarray, *, block: int = 8192
                  ) -> np.ndarray:
    """[n, R] f32 lengths of the graph's edges (+inf on -1 slots)."""
    n = graph.shape[0]
    xd = jnp.asarray(x, jnp.float32)
    out = np.empty(graph.shape, np.float32)
    block = min(block, n)
    for s in range(0, n, block):
        e = min(s + block, n)
        s0 = e - block                   # one compiled shape
        rows = jnp.arange(s0, e, dtype=jnp.int32)
        d = np.asarray(_edge_block(xd, rows, jnp.asarray(graph[s0:e])))
        out[s:e] = d[s - s0:]
    return np.where(graph >= 0, out, np.inf)


def reachable(graph: np.ndarray, start: int) -> np.ndarray:
    """bool [n]: the points a walk along the graph's edges reaches from
    ``start``."""
    seen = np.zeros(graph.shape[0], bool)
    seen[start] = True
    front = np.array([start])
    while front.size:
        nb = graph[front].ravel()
        nb = np.unique(nb[nb >= 0])
        nb = nb[~seen[nb]]
        seen[nb] = True
        front = nb
    return seen


def recall(found: np.ndarray, truth: np.ndarray, k: int) -> float:
    """Mean recall@k: the share of each exact top-k found among the
    first k answers, counting a repeated answer once."""
    f, t = np.asarray(found)[:, :k], np.asarray(truth)[:, :k]
    earlier = np.tril(np.ones((f.shape[1], f.shape[1]), bool), -1)
    hits = 0
    for s in range(0, f.shape[0], 65536):
        fb, tb = f[s:s + 65536], t[s:s + 65536]
        dup = np.any((fb[:, :, None] == fb[:, None, :]) & earlier, axis=2)
        hit = np.any(fb[:, :, None] == tb[:, None, :], axis=2)
        hits += int(np.sum(hit & ~dup & (fb >= 0)))
    return hits / (f.shape[0] * k)
