"""Record the small chip trace the program-trace reduction is tested
against: a build and serving steps, with the program's spans and scopes.

    python3 bench/record_program_trace.py [--out DIR]

On a TPU it builds a 4,096-point corpus of the ``sift-serve-batch``
configuration once (compiling every shape), packs it with the f32 packing
on the HBM-streaming kernel (``vmem_budget=0``) and warms a ``ServeLoop``
as ``record_trace.py`` does.  It then traces a second build inside
``bench.build`` and eight ``ServeLoop`` steps of 32 queries inside
``bench.serve_loop.step``, with the Python tracer off.  It writes
``build_serve_v5e.xplane.pb`` to ``--out`` (``bench/tests/data``) and,
beside it, ``build_serve_v5e.stream_step.txt.gz``, the compiled text of
the stream step that ran, and ``build_serve_v5e.json``: what
``benchlib.program_trace`` read from it on the machine that recorded it
(the program spans with their counts, what they add up to, the idle gaps
by span), the scope of each stream-step op in the trace, the scope
seconds, and the loop's counters over the traced steps.
``test_program_trace.py`` reduces the file again and must read the same,
and holds the scopes against the op names the chip recorded.
"""
from __future__ import annotations

import gzip
import json
import os
import pathlib
import shutil
import sys

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))
DATA = BENCH / "tests" / "data"
NAME = "build_serve_v5e"


def record(out: pathlib.Path, n: int = 4096, steps: int = 8) -> dict:
    """Trace one build and ``steps`` serving steps; write the trace and
    its reduction to ``out``; return the reduction."""
    from benchlib import cell, corpus, program_trace, spec, trace
    from benchlib.spans import span
    from repro import tracing
    from repro.core import pipnn
    from repro.core.serving import ServingIndex

    bench = spec.load_benchmark()
    cfg = {**spec.load_config(bench, spec.find_cell(bench,
                                                    "sift-serve-batch")),
           "n": n, "queries": 256}
    seeds = corpus.streams(1)
    x = corpus.make_points(cfg, seeds["data"])
    q = corpus.make_queries(cfg, seeds["data"])
    params = cell.build_params(cfg, seeds["build"])
    index = pipnn.build(x, params)
    sv = ServingIndex.from_index(index, x, vmem_budget=0)
    loop = cell.make_loop(sv, cfg, {"query_chunk": 32, "straggler_chunk": 8,
                                    "max_queue": 10000})
    cell.warm_loop(loop, q)
    for row in q:
        loop.submit(row)
    before = dict(loop.counters)
    with cell.traced(True) as holder:
        with span("bench.build"):
            pipnn.build(x, params)
        for _ in range(steps):
            with span("bench.serve_loop.step"):
                loop.step()
    out.mkdir(parents=True, exist_ok=True)
    path = out / f"{NAME}.xplane.pb"
    shutil.copy(holder.path, path)
    shutil.rmtree(holder.dir, ignore_errors=True)

    text = pipnn.stream_step_text()
    with gzip.open(out / f"{NAME}.stream_step.txt.gz", "wt") as f:
        f.write(text)
    spans = program_trace.read_spans(str(path))
    scopes = tracing.op_scopes(text)
    ops = [o for o in trace.reduce(str(path)).ops
           if o.program == program_trace.STREAM_PROGRAM]
    rec = {
        "spans": [[s.name, s.seconds, s.args] for s in spans],
        "span_metrics": program_trace.span_metrics(spans),
        "idle_by_span": program_trace.idle_by_span(str(path)),
        "stream_scopes": {o.name: scopes.get(o.name, tracing.UNSCOPED)
                          for o in ops},
        "counters": {k: loop.counters[k] - before.get(k, 0)
                     for k in ("served", "rerun_phase2")},
    }
    rec["scope_seconds"] = program_trace.scope_seconds(
        ops, rec["stream_scopes"], program_trace.STREAM_PROGRAM)
    with open(out / f"{NAME}.json", "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main(argv=None) -> int:
    import argparse

    import jax

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default=str(DATA))
    out = pathlib.Path(ap.parse_args(argv).out)
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_program_trace.py: JAX found no TPU")
    rec = record(out)
    print(json.dumps({k: v for k, v in rec.items()
                      if k not in ("spans", "stream_scopes")}))
    print("size", os.path.getsize(out / f"{NAME}.xplane.pb"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
