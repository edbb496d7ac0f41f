"""Bookkeeping shared by the runners that send requests to the serving
loop (``bench/runners/*.py``).

A traffic mix is a data file, ``bench/traffic/<name>.json``, whose
``runner`` names the file in ``bench/runners/`` that runs it and whose
other keys are that runner's parameters.  This module holds what every
serving runner needs: the order in which queries are sent, the record of
each request (query, due time, answer, when it was answered), and the
calls into the loop under the benchmark's host spans.  It imports
nothing of the program: the loop is any object with ``submit`` /
``step`` / ``queue_depth``.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import numpy as np

from benchlib.spans import span

NO_ANSWER_MS = 1e9   # latency reported for a request that got no answer


def query_order(n_queries: int, seed: int) -> np.ndarray:
    return np.random.default_rng(seed).permutation(n_queries)


@dataclasses.dataclass
class ServeWindow:
    seconds: float                       # the measured window
    qidx: list[int]                      # per request: query index
    ids: list[np.ndarray | None]         # per request: answer (None: none)
    latency_ms: list[float]              # per request: due -> answered
    in_window: list[bool]                # answered inside the window
    failed: int                          # rejected, errored or unanswered
    steps: list[tuple[float, int]]       # per step in the window: (s, served)

    @property
    def attempted(self) -> int:
        return len(self.qidx)

    def answered_in_window(self) -> int:
        return sum(1 for ok, a in zip(self.in_window, self.ids)
                   if ok and a is not None)


class Requests:
    """The requests of one window: what was sent when it was due, and what
    came back when."""

    def __init__(self):
        self.qidx, self.due, self.done, self.ids = [], [], [], []
        self.by_rid: dict[int, int] = {}
        self.failed = 0

    def add(self, qi: int, due: float, rid: int | None) -> None:
        j = len(self.qidx)
        self.qidx.append(int(qi))
        self.due.append(due)
        self.done.append(None)
        self.ids.append(None)
        if rid is None:
            self.failed += 1
        else:
            self.by_rid[rid] = j

    def finish(self, results, when: float) -> int:
        served = 0
        for r in results:
            j = self.by_rid.pop(r.rid, None)
            if j is None:
                continue
            self.done[j] = when
            if r.error is None:
                self.ids[j] = np.asarray(r.ids)
                served += 1
            else:
                self.failed += 1
        return served

    def window(self, seconds: float, t_end: float,
               steps: list) -> ServeWindow:
        lat, in_win = [], []
        for due, done, ids in zip(self.due, self.done, self.ids):
            ok = done is not None and ids is not None
            lat.append((done - due) * 1e3 if ok else NO_ANSWER_MS)
            in_win.append(ok and done <= t_end)
        return ServeWindow(seconds=seconds, qidx=self.qidx, ids=self.ids,
                           latency_ms=lat, in_window=in_win,
                           failed=self.failed + len(self.by_rid),
                           steps=steps)


def submit(loop, query) -> int | None:
    """The request id, or None where the loop refused the request."""
    try:
        return loop.submit(query)
    except Exception as e:  # noqa: BLE001 — a refusal is a failed request
        if type(e).__name__ != "QueueFull":
            raise
        return None


def step(loop, reqs: Requests, steps: list, clock: Callable[[], float]
         ) -> float:
    """One serving step under its span; records its time and what it
    served in ``steps`` and returns the clock at its end."""
    s = clock()
    with span("bench.serve_loop.step"):
        results = loop.step()
    e = clock()
    steps.append((e - s, reqs.finish(results, e)))
    return e


def drain(loop, reqs: Requests, clock: Callable[[], float],
          limit_s: float) -> None:
    """Serve what is still queued, for at most ``limit_s``."""
    stop = clock() + limit_s
    while loop.queue_depth and clock() < stop:
        step(loop, reqs, [], clock)
