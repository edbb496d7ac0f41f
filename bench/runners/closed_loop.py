"""Runner ``closed_loop``: an offline batch job.

Set-up builds the corpus and the index from the seed, packs it with the
configuration's packing and warms one ``ServeLoop`` (``cell.make_loop``)
at both of its engine shapes.  The window submits every query as one
pass, in an order drawn from the seed, and steps the loop; the next pass
starts when the queue is empty.  The window ends with the first serving
step that finishes after ``seconds``; ``qps`` is what was answered in it
over its length.  What was still queued is served after the window, up
to ``drain_s``, and every answer is checked against the reference.

Mix parameters: ``query_chunk``, ``straggler_chunk`` and ``max_queue``
of the loop, ``drain_s``, and ``trace_seconds``, the window of a traced
run.
"""
from __future__ import annotations

import time
from typing import Callable

import numpy as np

from benchlib import cell, checks, corpus, reference, traffic
from benchlib.spans import span


def run_passes(loop, queries: np.ndarray, order: np.ndarray,
               seconds: float, *, drain_s: float = 60.0,
               clock: Callable[[], float] = time.perf_counter
               ) -> traffic.ServeWindow:
    reqs, steps = traffic.Requests(), []
    t0 = end = clock()
    while end - t0 < seconds:
        if not loop.queue_depth:
            now = clock()
            for qi in order:
                reqs.add(qi, now, traffic.submit(loop, queries[qi]))
        end = traffic.step(loop, reqs, steps, clock)
    traffic.drain(loop, reqs, clock, drain_s)
    return reqs.window(end - t0, end, steps)


def run(args: cell.RunArgs) -> cell.Outcome:
    from repro.core import pipnn

    cfg, mix = args.cfg, args.traffic
    t0 = time.perf_counter()
    x = corpus.make_points(cfg, args.seeds["data"])
    queries = corpus.make_queries(cfg, args.seeds["data"])
    t1 = time.perf_counter()
    with span("bench.setup.build"):
        index = pipnn.build(x, cell.build_params(cfg, args.seeds["build"]))
    t2 = time.perf_counter()
    sv = cell.serving_index(index, x, cfg["serving"]["packing"])
    del index
    loop = cell.make_loop(sv, cfg, mix)
    cell.warm_loop(loop, queries)
    cell.log(f"set-up: corpus {t1 - t0:.1f}s, build {t2 - t1:.1f}s, pack "
             f"and warm {time.perf_counter() - t2:.1f}s")
    setup_s = time.perf_counter() - args.t_start
    seconds = min(args.seconds, mix["trace_seconds"]) if args.trace \
        else args.seconds
    order = traffic.query_order(len(queries), args.seeds["order"])
    loop.index.dist_comps = 0
    with cell.window(args) as tf:
        win = run_passes(loop, queries, order, seconds,
                         drain_s=mix["drain_s"])
    mem = cell.memory_peak_bytes(args.chips)
    st = np.asarray([t for t, _ in win.steps]) * 1e3
    cell.log(f"window: {len(st)} steps, step ms median "
             f"{np.median(st):.2f} max {st.max():.2f}, "
             f"{int(np.sum(st > 2 * np.median(st)))} over twice the median")
    ctx = {"window_s": win.seconds, "dist_comps": loop.index.dist_comps,
           "d": cfg["d"], "packing": cfg["serving"]["packing"]}
    if args.trace:
        srv = cfg["serving"]
        _, stats = sv.search(queries, k=cfg["k"], beam=srv["beam"],
                             expansions=srv["expansions"],
                             query_chunk=loop.query_chunk, with_stats=True)
        ctx["engine_dist_comps_mean"] = float(np.mean(stats["dist_comps"]))
    del loop, sv
    truth = reference.exact_topk(x, queries, cfg["k"])
    chk = checks.serving_checks(x, queries, truth, win.qidx, win.ids,
                                win.failed, cfg["guarantees"], cfg["k"])
    cell.reduce_trace(tf, ctx)
    values = {"setup_s": setup_s,
              "qps": win.answered_in_window() / win.seconds,
              "recall_at_10": next(c.value for c in chk
                                   if c.name == "recall_at_10")}
    return cell.Outcome(values, ctx, chk, attempted=win.attempted,
                        failed=win.failed, memory_peak_bytes=mem)
