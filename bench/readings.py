"""Readings that set the limits of ``correct``: the program's numbers on
many seeds, and the low-precision control's on a few.

    python3 bench/readings.py --config sift-128-l2 --seeds 1-12 --control-seeds 101-103

Per seed it builds the configuration's index as a run does, and reads

* the build's numbers (``checks.build_checks``) on the graph, and
* the serving numbers (``checks.serving_checks``) on one pass of all
  queries through ``ServeLoop`` at the traffic mix's batch settings
  (``--traffic``; answers do not depend on how requests are batched).

The control (``--control-seeds``) puts the next lower precision below
the configuration's f32 in the program's place: the program's bf16
packing serves the pass, and the build runs its distance GEMMs on
bf16-rounded inputs (one bf16 pass, what ``Precision.DEFAULT`` does on a
TPU).

One JSON line per reading goes to stdout and to ``--out``.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import numpy as np  # noqa: E402

from benchlib import cell as cell_mod  # noqa: E402
from benchlib import checks, corpus, reference, spec  # noqa: E402


def seed_list(text: str) -> list[int]:
    out = []
    for part in text.split(","):
        if "-" in part:
            a, b = part.split("-")
            out.extend(range(int(a), int(b) + 1))
        elif part:
            out.append(int(part))
    return out


@contextlib.contextmanager
def bf16_distance_gemms():
    """The program's build with every ``metrics.pairwise`` GEMM on
    bf16-rounded inputs and f32 accumulation: one bf16 pass."""
    import jax
    import jax.numpy as jnp

    from repro.core import metrics

    original = metrics.pairwise

    def pairwise_bf16(a, b, metric="l2"):
        ip = jnp.matmul(a.astype(jnp.bfloat16).astype(jnp.float32),
                        b.astype(jnp.bfloat16).astype(jnp.float32).T,
                        precision=jax.lax.Precision.HIGHEST)
        if metric != "l2":
            raise ValueError("the bf16 control covers l2 only")
        a2 = jnp.sum(a * a, axis=-1)[:, None]
        b2 = jnp.sum(b * b, axis=-1)[None, :]
        return jnp.maximum(a2 + b2 - 2.0 * ip, 0.0)

    jax.clear_caches()
    metrics.pairwise = pairwise_bf16
    try:
        yield
    finally:
        metrics.pairwise = original
        jax.clear_caches()


def serve_pass(sv, cfg, traffic_cfg, queries):
    """All queries through one warmed ServeLoop: (qidx, answers, failed)."""
    loop = cell_mod.make_loop(sv, cfg, traffic_cfg)
    cell_mod.warm_loop(loop, queries)
    rid = {loop.submit(q): i for i, q in enumerate(queries)}
    got = {r.rid: r for r in loop.run_until_drained()}
    qidx = list(range(len(queries)))
    answers = [None] * len(queries)
    failed = 0
    for r, i in rid.items():
        res = got.get(r)
        if res is None or res.error is not None:
            failed += 1
        else:
            answers[i] = res.ids
    return qidx, answers, failed


def numbers(chk) -> dict:
    return {c.name: c.value for c in chk}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", default="batch",
                    help="mix whose batch settings serve the pass")
    ap.add_argument("--seeds", default="1-12")
    ap.add_argument("--control-seeds", default="101-103")
    ap.add_argument("--n", type=int, default=None)
    ap.add_argument("--queries", type=int, default=None)
    ap.add_argument("--out", default="bench_out/readings.jsonl")
    args = ap.parse_args(argv)

    import jax

    from repro.core import pipnn

    cfg = spec.load_named_config(args.config)
    for key in ("n", "queries"):
        if getattr(args, key) is not None:
            cfg[key] = getattr(args, key)
    if cfg["serving"]["packing"] != "f32":
        raise SystemExit("readings.py: the control covers f32 "
                         "configurations only")
    traffic_cfg = spec.load_traffic(args.traffic)
    k, g = cfg["k"], cfg["guarantees"]
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    out = open(args.out, "a")

    def emit(rec):
        rec = {"config": args.config, "device": jax.devices()[0].device_kind,
               **rec}
        line = json.dumps(rec)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    def one(seed: int, control: bool):
        t0 = time.perf_counter()
        seeds = corpus.streams(seed)
        x = corpus.make_points(cfg, seeds["data"])
        queries = corpus.make_queries(cfg, seeds["data"])
        params = cell_mod.build_params(cfg, seeds["build"])
        rec = {"seed": seed, "control": control}
        ctx = bf16_distance_gemms() if control else contextlib.nullcontext()
        with ctx:
            index = pipnn.build(x, params)
        sv = cell_mod.serving_index(index, x, "f32")
        found = sv.search(queries, k=k, beam=cfg["serving"]["beam"],
                          expansions=cfg["serving"]["expansions"],
                          query_chunk=traffic_cfg["query_chunk"])
        del sv
        truth = reference.exact_topk(x, queries, k)
        rec["build"] = numbers(checks.build_checks(
            x, index.graph, index.dists, index.start,
            reference.recall(found, truth, k), g))
        if control:                     # serve the program's own graph
            index = pipnn.build(x, params)
        sv = cell_mod.serving_index(index, x,
                                    "bfloat16" if control else "f32")
        qidx, answers, failed = serve_pass(sv, cfg, traffic_cfg, queries)
        del sv
        rec["serve"] = numbers(checks.serving_checks(
            x, queries, truth, qidx, answers, failed, g, k))
        rec["seconds"] = time.perf_counter() - t0
        emit(rec)

    try:
        for s in seed_list(args.seeds):
            one(s, False)
        for s in seed_list(args.control_seeds):
            one(s, True)
    finally:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
