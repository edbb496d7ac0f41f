"""Record the small chip trace the trace reduction is tested against.

    python3 bench/record_trace.py [--out DIR]

On a TPU it builds a 4,096-point corpus, packs it with the f32 packing
on the HBM-streaming kernel (``vmem_budget=0``), and traces eight
``ServeLoop`` steps of 32 queries inside the benchmark's spans, with
the Python tracer off.  It writes ``serve_v5e.xplane.pb`` to ``--out``
(``bench/tests/data``) and, beside it, ``serve_v5e.json``: what ``benchlib.trace.reduce`` read
from it on the machine that recorded it.  ``test_bench_harness.py``
reduces the file again and must read the same.
"""
from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import shutil
import sys

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))
DATA = BENCH / "tests" / "data"


def main(argv=None) -> int:
    import argparse

    import jax

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default=str(DATA))
    out = pathlib.Path(ap.parse_args(argv).out)

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace.py: JAX found no TPU")
    from benchlib import cell, corpus, spec, trace
    from benchlib.spans import span
    from repro.core import pipnn
    from repro.core.serving import ServingIndex

    bench = spec.load_benchmark()
    cfg = {**spec.load_config(bench, spec.find_cell(bench,
                                                    "sift-serve-batch")),
           "n": 4096, "queries": 256}
    seeds = corpus.streams(1)
    x = corpus.make_points(cfg, seeds["data"])
    q = corpus.make_queries(cfg, seeds["data"])
    index = pipnn.build(x, cell.build_params(cfg, seeds["build"]))
    sv = ServingIndex.from_index(index, x, vmem_budget=0)
    assert sv.kernel_path == "hbm", sv.kernel_path
    loop = cell.make_loop(sv, cfg, {"query_chunk": 32, "straggler_chunk": 8,
                                    "max_queue": 10000})
    cell.warm_loop(loop, q)
    for row in q:
        loop.submit(row)
    loop.index.dist_comps = 0
    with cell.traced(True) as holder:
        for _ in range(8):
            with span("bench.serve_loop.step"):
                loop.step()
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(holder.path, out / "serve_v5e.xplane.pb")
    shutil.rmtree(holder.dir, ignore_errors=True)
    s = trace.reduce(str(out / "serve_v5e.xplane.pb"))
    rec = {k: v for k, v in dataclasses.asdict(s).items() if k != "ops"}
    rec["kernel_seconds"] = {"gather_distance_hbm":
                             s.kernel_seconds("gather_distance_hbm")}
    rec["n_ops"] = len(s.ops)
    rec["dist_comps"] = loop.index.dist_comps
    with open(out / "serve_v5e.json", "w") as f:
        json.dump(rec, f, indent=1)
    print(json.dumps(rec)[:2000])
    print("size", os.path.getsize(out / "serve_v5e.xplane.pb"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
