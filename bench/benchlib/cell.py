"""One run of one cell: set up, measure, check, report.

``run_cell`` finds the cell's configuration and traffic mix, hands them
to the mix's runner (``bench/runners/<runner>.py``), and turns what the
runner returns into the result line.  A runner builds the corpus from the
seed, builds and packs the index and runs every shape the window will
run, so nothing compiles inside the window (compiles there are counted
and printed); it then measures inside ``window`` and checks what the
window returned, after it, once the device memory peak is read and the
program's state is freed.  The helpers the runners share — the build
parameters, the serving loop at its one operating point, the window —
live here.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import pathlib
import shutil
import sys
import tempfile
import time
from typing import Any

import numpy as np

from benchlib import corpus, spec
from benchlib import trace as trace_mod
from benchlib.spans import span


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------- program ----

# Fields the run supplies: the seed comes from --seed, the groups are
# written out field by field.
_SUPPLIED = {"seed", "rbc", "leaf"}


def build_params(cfg: dict, seed: int):
    """``PiPNNParams`` exactly as the configuration writes them out.  A
    field the program does not have, or a field of the program that the
    file does not write out, is an error: a program default must not
    move the yardstick."""
    from repro.core.leaf import LeafParams
    from repro.core.pipnn import PiPNNParams
    from repro.core.rbc import RBCParams

    def make(cls, fields: dict, **extra):
        known = {f.name for f in dataclasses.fields(cls)} - _SUPPLIED
        unknown, missing = set(fields) - known, known - set(fields)
        if unknown or missing:
            raise ValueError(
                f"configuration's {cls.__name__} does not match the "
                f"program: not in the program {sorted(unknown)}, not in "
                f"the configuration {sorted(missing)}")
        kw = {k: (tuple(v) if isinstance(v, list) else v)
              for k, v in fields.items()}
        return cls(**kw, **extra)

    b = dict(cfg["build"])
    rbc = make(RBCParams, b.pop("rbc"), seed=seed)
    leaf = make(LeafParams, b.pop("leaf"))
    return make(PiPNNParams, b, rbc=rbc, leaf=leaf, seed=seed)


class CountingIndex:
    """The serving index as the serving loop sees it, with a span around
    each search and a running count of the distance computations the
    searches report."""

    def __init__(self, index):
        self._index = index
        self.dist_comps = 0

    def __getattr__(self, name):
        return getattr(self._index, name)

    def search(self, queries, **kw):
        with span("bench.index.search"):
            out = self._index.search(queries, **kw)
        if kw.get("with_stats"):
            self.dist_comps += int(np.sum(out[1]["dist_comps"]))
        return out


def make_loop(index, cfg: dict, traffic_cfg: dict):
    """A ``ServeLoop`` with one rung, the configuration's operating point,
    and no latency target: it never downshifts and reads no file."""
    from repro.launch.serve_loop import OperatingPoint, ServeLoop

    srv = cfg["serving"]
    point = OperatingPoint(f"beam{srv['beam']}", beam=srv["beam"],
                           expansions=srv["expansions"])
    return ServeLoop(CountingIndex(index), k=cfg["k"],
                     query_chunk=traffic_cfg["query_chunk"],
                     straggler_chunk=traffic_cfg["straggler_chunk"],
                     max_queue=traffic_cfg["max_queue"],
                     ladder=(point,), slo_p99=None)


def warm_loop(loop, queries: np.ndarray) -> None:
    """Compile and run the loop's two engine shapes — a full batch at the
    drain cap and a straggler batch at the backstop — then drain a few
    batches through the loop itself."""
    sv = loop.index._index
    op = loop.operating_point
    for n, iters in ((loop.query_chunk, loop.drain_iters),
                     (loop.straggler_chunk, loop.backstop_iters)):
        sv.search(queries[:n], k=loop.k, beam=op.beam,
                  expansions=op.expansions, iters=iters, query_chunk=n,
                  with_stats=True)
    for q in queries[:2 * loop.query_chunk]:
        loop.submit(q)
    loop.run_until_drained()


def serving_index(index, x: np.ndarray, packing: str):
    """The program's ``ServingIndex`` of the graph with the configuration's
    packing (``f32``, or a dtype the program packs, e.g. ``bfloat16``)."""
    from repro.core.serving import ServingIndex

    return ServingIndex.from_index(index, x,
                                   dtype=None if packing == "f32" else packing)


# ------------------------------------------------------------- the run ----

class CompileCounter:
    """Counts the executables JAX compiles or loads from its persistent
    cache while ``active``, and totals what compiling cost over the whole
    run (``totals``: seconds per duration event, counts per event)."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_hits")

    def __init__(self):
        import jax

        self.active = False
        self.count = 0
        self.totals: dict[str, float] = {}

        def on_duration(event, duration, **kw):
            self.totals[event] = self.totals.get(event, 0.0) + duration
            if self.active and event == self.EVENTS[0]:
                self.count += 1

        def on_event(event, **kw):
            self.totals[event] = self.totals.get(event, 0.0) + 1
            if self.active and event == self.EVENTS[1]:
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    @contextlib.contextmanager
    def window(self):
        self.active = True
        try:
            yield
        finally:
            self.active = False


@dataclasses.dataclass
class RunArgs:
    """What a runner's ``run`` gets: the cell as declared, and the run."""
    cfg: dict                     # the configuration's file
    traffic: dict                 # the mix's file
    seeds: dict[str, int]         # ``corpus.streams(--seed)``
    seconds: float
    trace: bool
    chips: int
    compiles: CompileCounter
    t_start: float                # the process's start, for ``setup_s``


@dataclasses.dataclass
class Outcome:
    """What a runner's ``run`` returns."""
    values: dict[str, float]          # every end-to-end metric it measured
    ctx: dict[str, Any]               # what the per-layer readers read
    checks: list
    attempted: int
    failed: int
    memory_peak_bytes: int


def memory_peak_bytes(n_chips: int) -> int:
    import jax

    peaks = []
    for d in jax.devices()[:n_chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)


def device_record(n_chips: int) -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "device_kind": d.device_kind, "count": n_chips}


@dataclasses.dataclass
class TraceFile:
    dir: str | None = None        # the profiler's output directory
    path: str | None = None       # its ``.xplane.pb``, once tracing ended


@contextlib.contextmanager
def traced(enabled: bool):
    """Run the block under the profiler, inside the ``bench.window`` span,
    when ``enabled``; yields a ``TraceFile`` that names the trace once the
    block has ended."""
    import jax

    out = TraceFile()
    if not enabled:
        yield out
        return
    out.dir = tempfile.mkdtemp(prefix="bench_trace_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0      # spans only, no Python calls
    options.host_tracer_level = 2
    jax.profiler.start_trace(out.dir, profiler_options=options)
    try:
        with span("bench.window"):
            yield out
    finally:
        jax.profiler.stop_trace()
        out.path = trace_mod.find_trace_file(out.dir)


@contextlib.contextmanager
def window(args: RunArgs):
    """The measured window: compiles counted, the profiler on when the run
    traces, and Python's garbage collector held off, so that no collection
    over the objects set-up made lands inside one timed call.  Yields the
    ``TraceFile``."""
    gc.collect()
    gc.freeze()
    gc.disable()
    try:
        with args.compiles.window(), traced(args.trace) as tf:
            yield tf
    finally:
        gc.enable()
        gc.unfreeze()


def reduce_trace(tf: TraceFile, ctx: dict) -> None:
    """Reduce the window's trace into ``ctx["trace"]`` and delete it."""
    if tf.dir is None:
        return
    try:
        if tf.path is not None:
            ctx["trace"] = trace_mod.reduce(tf.path)
    finally:
        shutil.rmtree(tf.dir, ignore_errors=True)


def phases(timings: dict) -> str:
    return "(" + ", ".join(f"{k} {v:.1f}s" for k, v in timings.items()
                           if k != "total") + ")"


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float | None = None,
             root: pathlib.Path | None = None) -> dict:
    """The result line of one run, as a dict; ``root`` is the checkout
    whose declarations are read (by default this one)."""
    t_start = time.perf_counter() if t_start is None else t_start
    bench = spec.load_benchmark(root)
    cell = spec.find_cell(bench, cell_name)
    traffic_cfg = spec.load_traffic(cell["traffic"], root)
    args = RunArgs(cfg=spec.load_config(bench, cell, root),
                   traffic=traffic_cfg, seeds=corpus.streams(seed),
                   seconds=seconds, trace=trace, chips=cell["chips"],
                   compiles=CompileCounter(), t_start=t_start)
    out = spec.load_runner(traffic_cfg["runner"], root).run(args)
    log(f"compiles inside the window: {args.compiles.count}")
    log("compile totals over the run: " + ", ".join(
        f"{k.rsplit('/', 1)[-1]}={v:.3f}"
        for k, v in sorted(args.compiles.totals.items())))
    device = device_record(cell["chips"])
    device["memory_peak_bytes"] = out.memory_peak_bytes
    result: dict[str, Any] = {"correct": all(c.ok for c in out.checks),
                              "attempted": out.attempted,
                              "failed": out.failed}
    if trace:
        metrics = {}
        peak = spec.peaks(device["kind"], root) \
            if device["platform"] == "tpu" else None
        ctx = {**out.ctx, "peak": peak, "config": args.cfg,
               "memory_peak_bytes": out.memory_peak_bytes,
               "compiles_in_window": args.compiles.count}
        for m in spec.per_layer_for(bench, cell_name):
            v = spec.load_reader(m["name"], root)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        tr = ctx.get("trace")
        if tr is not None:
            device["busy_s"] = tr.busy_s
            device["window_s"] = tr.window_s
            result["breakdown"] = {"device_ops": tr.top_ops,
                                   "idle_gaps": tr.idle_by_span}
    else:
        metrics = {m["name"]: {"value": float(out.values[m["name"]]),
                               "unit": m["unit"]}
                   for m in spec.end_to_end_for(bench, cell_name)}
    result["metrics"] = metrics
    result["device"] = device
    for c in out.checks:
        log(c.line())
    result["checks"] = {c.name: c.record() for c in out.checks}
    return result
