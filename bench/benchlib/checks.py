"""The comparison that decides ``correct``.

Every number compared has a limit in the configuration's ``guarantees``:
``{"max": v}`` or ``{"min": v}``.  The numbers:

serving cells (the ids the serving loop returned for the window's
requests, against ``reference.exact_topk``):

* ``recall_at_10``  — recall@10 over every answer; the configuration's
  quality target.
* ``order_gap``     — the widest gap by which an answer's neighbour lies
  farther from the query than the one ranked after it, relative to that
  one, in float64.  An answer is ranked by the program's own distances;
  computed at the configuration's precision it is in true order up to
  rounding, and a lower precision, or an answer altered or sent to the
  wrong request, breaks the order.
* ``bad_answers``   — answers without ``k`` distinct ids in range.
* ``failed``        — requests refused, errored, or never answered.

build cells (the graph the window's last build returned):

* ``recall_at_10``  — recall@10 of a beam search of the graph.
* ``edge_dist_gap`` — the widest relative gap between an edge's length as
  the program stored it and as the reference measures it.
* ``bad_edges``     — edges out of range, to the point itself, repeated in
  a row, or with no finite length.
* ``edgeless_rows`` — points with no out-edge.
* ``unreachable``   — points a walk from the entry point does not reach.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from benchlib import reference

_TINY = 1e-12


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float
    rule: str          # "max" or "min"

    @property
    def ok(self) -> bool:
        if not np.isfinite(self.value):
            return False
        return self.value <= self.limit if self.rule == "max" \
            else self.value >= self.limit

    def line(self) -> str:
        return (f"check {self.name} {self.value!r} {self.rule} "
                f"{self.limit!r} {'ok' if self.ok else 'FAILED'}")

    def record(self) -> dict:
        return {"value": self.value, self.rule: self.limit}


def _check(guarantees: dict, name: str, value: float) -> Check:
    g = guarantees[name]
    rule = "max" if "max" in g else "min"
    return Check(name, float(value), float(g[rule]), rule)


def order_gap(d: np.ndarray) -> float:
    """Widest relative inversion of consecutive answers, [m, k] float64
    exact distances of each answer in its served order."""
    if d.shape[1] < 2:
        return 0.0
    a, b = d[:, :-1], d[:, 1:]
    valid = np.isfinite(a) & np.isfinite(b)
    a, b = np.where(valid, a, 0.0), np.where(valid, b, 0.0)
    gap = np.where(valid, np.maximum(a - b, 0.0) / np.maximum(b, _TINY), 0.0)
    return float(gap.max(initial=0.0))


def bad_answer_rows(ids: np.ndarray, n: int, k: int) -> np.ndarray:
    """bool [m]: answers without k distinct ids in [0, n)."""
    ids = np.asarray(ids)[:, :k]
    in_range = (ids >= 0) & (ids < n)
    s = np.sort(ids, axis=1)
    dup = np.any(s[:, 1:] == s[:, :-1], axis=1)
    return ~in_range.all(axis=1) | dup | (ids.shape[1] < k)


def serving_checks(x: np.ndarray, queries: np.ndarray, truth: np.ndarray,
                   qidx: list[int], answers: list, failed: int,
                   guarantees: dict, k: int) -> list[Check]:
    """``answers[i]`` is the id row served for ``queries[qidx[i]]`` (None
    for a request with no answer, which ``failed`` counts)."""
    got = [(q, a) for q, a in zip(qidx, answers) if a is not None]
    if got:
        q = np.asarray([g[0] for g in got], np.int64)
        ids = np.stack([np.asarray(g[1], np.int64)[:k] for g in got])
    else:
        q, ids = np.zeros(0, np.int64), np.zeros((0, k), np.int64)
    bad = bad_answer_rows(ids, x.shape[0], k) if len(ids) else \
        np.zeros(0, bool)
    # an answer repeats for every pass over a query: measure each once
    rows = np.unique(np.concatenate([q[:, None], ids], axis=1), axis=0)
    gap = order_gap(reference.answer_sq_dists(
        x, queries[rows[:, 0]], rows[:, 1:])) if len(rows) else np.inf
    rec = reference.recall(ids, truth[q], k) if len(ids) else 0.0
    return [_check(guarantees, "recall_at_10", rec),
            _check(guarantees, "order_gap", gap),
            _check(guarantees, "bad_answers", int(bad.sum())),
            _check(guarantees, "failed", int(failed))]


def edge_dist_gap(stored: np.ndarray, measured: np.ndarray,
                  graph: np.ndarray) -> float:
    valid = graph >= 0
    s = np.where(valid, stored, 0.0).astype(np.float64)
    m = np.where(valid, measured, 0.0).astype(np.float64)
    rel = np.abs(s - m) / np.maximum(m, _TINY)
    return float(np.max(np.where(valid, rel, 0.0), initial=0.0))


def bad_edge_count(graph: np.ndarray, stored: np.ndarray) -> int:
    n = graph.shape[0]
    valid = graph >= 0
    out_of_range = (graph >= n) | (graph < -1)
    self_loop = graph == np.arange(n)[:, None]
    s = np.sort(np.where(valid, graph, -1 - np.arange(graph.shape[1])),
                axis=1)
    repeated = np.zeros_like(valid)
    repeated[:, 1:] = s[:, 1:] == s[:, :-1]
    no_length = valid & ~np.isfinite(stored)
    return int(out_of_range.sum() + self_loop.sum() + repeated.sum()
               + no_length.sum())


def build_checks(x: np.ndarray, graph: np.ndarray, stored: np.ndarray,
                 start: int, recall_at_10: float, guarantees: dict
                 ) -> list[Check]:
    graph = np.asarray(graph)
    measured = reference.edge_sq_dists(x, np.clip(graph, -1, x.shape[0] - 1))
    reached = reference.reachable(np.where(graph < x.shape[0], graph, -1),
                                  start)
    return [_check(guarantees, "recall_at_10", recall_at_10),
            _check(guarantees, "edge_dist_gap",
                   edge_dist_gap(stored, measured, graph)),
            _check(guarantees, "bad_edges", bad_edge_count(graph, stored)),
            _check(guarantees, "edgeless_rows",
                   int(np.sum(~(graph >= 0).any(axis=1)))),
            _check(guarantees, "unreachable", int((~reached).sum()))]
