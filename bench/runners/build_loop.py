"""Runner ``build_loop``: rebuild the cell's corpus back to back.

Set-up builds the corpus from the seed and runs one full build, which
compiles every shape.  The window then rebuilds the same corpus: a build
starts only while the last one's time fits in what is left of the
window, and at least one is timed.  The window runs from the first
build's start to the last one's end; ``build_s`` is its length over the
builds in it.  After the window the last graph is checked against the
reference and searched at the configuration's operating point over its
queries, for ``recall_at_10``.  Mix parameters: ``query_chunk`` (the
batch of that search).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import numpy as np

from benchlib import cell, checks, corpus, reference
from benchlib.spans import span


@dataclasses.dataclass
class BuildWindow:
    seconds: float                  # first build's start to last one's end
    builds: list[dict]              # per build: {"wall_s", "timings"}
    last: Any                       # the last build's index

    @property
    def build_s(self) -> float:
        return self.seconds / len(self.builds)


def run_builds(build: Callable[[], Any], seconds: float, *,
               max_builds: int | None = None,
               clock: Callable[[], float] = time.perf_counter
               ) -> BuildWindow:
    t0 = clock()
    builds, index = [], None
    while True:
        s = clock()
        with span("bench.build"):
            index = build()
        e = clock()
        builds.append({"wall_s": e - s, "timings": dict(index.timings)})
        if max_builds is not None and len(builds) >= max_builds:
            break
        if e - s > t0 + seconds - e:
            break
    return BuildWindow(seconds=e - t0, builds=builds, last=index)


def run(args: cell.RunArgs) -> cell.Outcome:
    from repro.core import pipnn

    cfg = args.cfg
    x = corpus.make_points(cfg, args.seeds["data"])
    queries = corpus.make_queries(cfg, args.seeds["data"])
    params = cell.build_params(cfg, args.seeds["build"])
    t0 = time.perf_counter()
    with span("bench.setup.build"):
        warm = pipnn.build(x, params)           # compiles every shape
    cell.log(f"set-up: corpus {t0 - args.t_start:.1f}s, warm build "
             f"{time.perf_counter() - t0:.1f}s {cell.phases(warm.timings)}")
    del warm
    setup_s = time.perf_counter() - args.t_start
    with cell.window(args) as tf:
        win = run_builds(lambda: pipnn.build(x, params), args.seconds,
                         max_builds=1 if args.trace else None)
    mem = cell.memory_peak_bytes(args.chips)
    cell.log("window: builds " + ", ".join(
        f"{b['wall_s']:.3f}s {cell.phases(b['timings'])}"
        for b in win.builds))
    index = win.last
    graph, stored, start = np.asarray(index.graph), np.asarray(
        index.dists), int(index.start)
    srv = cfg["serving"]
    sv = cell.serving_index(index, x, srv["packing"])
    found = sv.search(queries, k=cfg["k"], beam=srv["beam"],
                      expansions=srv["expansions"],
                      query_chunk=args.traffic["query_chunk"])
    del sv, index, win.last
    truth = reference.exact_topk(x, queries, cfg["k"])
    rec = reference.recall(found, truth, cfg["k"])
    chk = checks.build_checks(x, graph, stored, start, rec,
                              cfg["guarantees"])
    ctx = {"builds": win.builds, "window_s": win.seconds}
    cell.reduce_trace(tf, ctx)
    values = {"setup_s": setup_s, "build_s": win.build_s,
              "recall_at_10": rec}
    return cell.Outcome(values, ctx, chk, attempted=len(win.builds),
                        failed=0, memory_peak_bytes=mem)
