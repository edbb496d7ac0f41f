"""Program spans and op scopes (``repro.tracing``): spans nest and carry
their counts into the profiler's trace, ``into`` fills the build's
timings, nothing is recorded without a profiler, the stream step's
stages own its ops, the jitted programs have names of their own, the
serving loop's step counts agree with its counters, and tracing leaves
the graph bit-identical."""
import glob
import re
import time

import jax
import numpy as np
import pytest

from repro import tracing
from repro.core import pipnn, rbc
from repro.core.leaf import LeafParams
from repro.core.pipnn import PiPNNParams
from repro.core.rbc import RBCParams

STREAM_SCOPES = ("leaf_knn", "edge_hash", "chunk_sort", "reservoir_merge")
TIMING_KEYS = ["partition", "hashprune", "build_leaves", "final_prune",
               "connect", "total"]


def _params():
    return PiPNNParams(rbc=RBCParams(c_max=128, c_min=16),
                       leaf=LeafParams(k=4, leaf_chunk=4), l_max=16,
                       max_deg=16, seed=3)


def _points(n=1024, d=16, seed=0):
    return np.random.default_rng(seed).normal(size=(n, d)).astype(
        np.float32)


def _traced(tmp_path, fn):
    """``fn()`` under a profiler trace; returns (its result, the program
    spans of the trace as (name, start, end, stats) in time order)."""
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        out = fn()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(("pipnn.", "rbc.", "serve_loop.",
                                          "t.")):
                        spans.append((e.name, int(e.start_ns),
                                      int(e.start_ns + e.duration_ns),
                                      dict(e.stats)))
    return out, sorted(spans, key=lambda s: (s[1], -s[2]))


# ------------------------------------------------------------------ span --

def test_span_nests_and_records_counts_in_the_trace(tmp_path):
    def work():
        with tracing.span("t.outer", a=1) as outer:
            with tracing.span("t.inner") as inner:
                inner["b"] = 2
            outer["c"] = 0.5

    _, spans = _traced(tmp_path, work)
    (o_name, o0, o1, o_args), (i_name, i0, i1, i_args) = spans
    assert (o_name, i_name) == ("t.outer", "t.inner")
    assert o0 <= i0 and i1 <= o1
    assert o_args == {"a": 1, "c": 0.5}
    assert i_args == {"b": 2}


def test_span_into_adds_host_seconds():
    into = {}
    t0 = time.perf_counter()
    with tracing.span("t.a", into=into, key="k"):
        time.sleep(0.02)
    wall = time.perf_counter() - t0
    with tracing.span("t.b", into=into, key="k"):
        pass
    assert list(into) == ["k"]
    assert abs(into["k"] - wall) < 1e-3
    with tracing.span("t.c", into=into):
        pass
    assert list(into) == ["k", "t.c"]


def test_span_records_nothing_without_a_profiler(monkeypatch):
    class Refused:
        def __init__(self, *a, **kw):
            raise AssertionError("an event was opened with no profiler")

        is_enabled = staticmethod(lambda: False)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Refused)
    into = {}
    with tracing.span("t.x", into=into, key="k", n=3) as counts:
        counts["m"] = 4
    assert counts == {"n": 3, "m": 4}
    assert list(into) == ["k"]


def test_build_timings_keep_their_keys():
    index = pipnn.build(_points(), _params())
    assert list(index.timings) == TIMING_KEYS
    t = index.timings
    assert t["total"] == pytest.approx(sum(v for k, v in t.items()
                                           if k != "total"), rel=1e-12)
    assert "leaf_size_mean" not in index.stats
    assert index.stats["n_leaves"] > 0 and index.stats["pad_ratio"] >= 1


def test_build_spans_carry_the_partition_counts(tmp_path):
    params = _params().with_(rbc=RBCParams(c_max=128, c_min=16,
                                           execution="device"))
    index, spans = _traced(tmp_path, lambda: pipnn.build(_points(), params))
    names = [s[0] for s in spans]
    for name in ("pipnn.partition", "rbc.worklist", "rbc.assign",
                 "pipnn.sketch", "pipnn.stream", "pipnn.final_prune",
                 "pipnn.link_entry_hubs", "pipnn.connect_from_start"):
        assert name in names, name
    args = {s[0]: s[3] for s in spans}
    assert args["pipnn.partition"]["n_leaves"] == index.stats["n_leaves"]
    assert args["pipnn.partition"]["pad_ratio"] == pytest.approx(
        index.stats["pad_ratio"])
    assert args["pipnn.connect_from_start"]["connect_edges"] == \
        index.stats["connect_edges"]
    (_, w0, w1, _), = [s for s in spans if s[0] == "rbc.worklist"]
    assigns = [s for s in spans if s[0] == "rbc.assign"]
    assert assigns and all(w0 <= a0 and a1 <= w1 for _, a0, a1, _ in assigns)


def test_build_graph_is_identical_under_a_profiler_trace(tmp_path):
    x, params = _points(seed=1), _params()
    plain = pipnn.build(x, params)
    traced, _ = _traced(tmp_path, lambda: pipnn.build(x, params))
    np.testing.assert_array_equal(plain.graph, traced.graph)
    np.testing.assert_array_equal(plain.dists, traced.dists)
    assert plain.start == traced.start


# ------------------------------------------------------------ op scopes --

def test_scope_of_strips_wrappers_and_the_primitive():
    assert tracing.scope_of("jit(stream_step)/leaf_knn/while/body/dot") \
        == "leaf_knn/while/body"
    assert tracing.scope_of("jit(f)/jit(g)/add") == tracing.UNSCOPED
    assert tracing.scope_of("sort") == tracing.UNSCOPED


COMPILED = """HloModule jit_f, is_scheduled=true

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %multiply.1 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(f)/edge_hash/mul"}
}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %gte = f32[8]{0} get-tuple-element(%p), index=1
  %copy.3 = f32[8]{0} copy(%gte)
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%gte, %copy.3)
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0), metadata={op_name="x"}
  %fusion.1 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation
  %copy.1 = f32[8]{0} copy(%fusion.1)
  %while.1 = (s32[], f32[8]{0}) while(%t), condition=%cond, body=%body, metadata={op_name="jit(f)/leaf_knn/while"}
  %sort.1 = f32[8]{0} sort(%copy.1), dimensions={0}, to_apply=%cmp, metadata={op_name="jit(f)/chunk_sort/sort"}
  ROOT %add.1 = f32[8]{0} add(%sort.1, %x), metadata={op_name="jit(f)/add"}
}
"""


def test_op_scopes_settle_what_the_compiler_made():
    got = tracing.op_scopes(COMPILED)
    assert got["multiply.1"] == "edge_hash"
    assert got["fusion.1"] == "edge_hash"        # what the fusion holds
    assert got["copy.1"] == "edge_hash"          # its operand's
    assert got["copy.3"] == "leaf_knn"           # the loop that runs it
    assert got["sort.1"] == "chunk_sort"
    assert got["add.1"] == tracing.UNSCOPED      # traced outside scopes
    assert got["x"] == tracing.UNSCOPED


def test_stream_step_stages_own_its_sorts_and_dots():
    pipnn.build(_points(), _params())
    text = pipnn.stream_step_text()
    assert text.startswith("HloModule jit_stream_step")
    scopes = tracing.op_scopes(text)
    owners = {v.split("/")[0] for v in scopes.values()}
    assert set(STREAM_SCOPES) <= owners
    heavy = re.findall(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = \S+ (?:sort|dot)\(",
                       text, re.M)
    assert heavy
    for name in heavy:
        assert scopes[name].split("/")[0] in STREAM_SCOPES, name


@pytest.mark.parametrize("make, name", [
    (lambda: pipnn._make_stream_step(None, 4, "l2", "bidirected", False,
                                     True, 4, 1.2, 16, "segmented", False),
     "stream_step"),
    (lambda: rbc._make_carve_step(2, "l2", 8), "carve_step"),
    (lambda: rbc._make_static_carve(
        **{k: v for k, v in rbc.carve_chunks(4096, RBCParams()).items()
           if k in ("n_pad", "l0", "f0", "f0r", "cap_b", "l1", "f1", "sub",
                    "bucket_chunk", "cap_chunk")},
        c_max=1024, metric="l2"), "static_carve_step"),
])
def test_jitted_programs_have_names_of_their_own(make, name):
    """The trace's ``XLA Modules`` line names a program ``jit_<name>``."""
    assert make().__name__ == name


# ------------------------------------------------------------ serve loop --

def test_serve_loop_step_counts_agree_with_its_counters(tmp_path):
    from repro.core.serving import ServingIndex
    from repro.launch.serve_loop import OperatingPoint, ServeLoop

    n, d = 512, 8
    x = np.zeros((n, d), np.float32)
    x[:, 0] = np.arange(n)
    x[:, 1:] = 0.01 * np.random.default_rng(5).standard_normal((n, d - 1))
    graph = np.full((n, 2), -1, np.int32)          # a path: the far end
    graph[:, 0] = np.arange(n) - 1                 # straggles
    graph[: n - 1, 1] = np.arange(1, n)
    sv = ServingIndex.from_graph(graph, x, start=0)
    q = np.concatenate([x[:6], x[n - 2:], x[3:9]]) + 0.001
    loop = ServeLoop(sv, k=4, query_chunk=8, straggler_chunk=2,
                     ladder=(OperatingPoint("b8", beam=8),), drain_iters=8,
                     backstop_iters=32, clock=iter(range(10**6)).__next__)
    for row in q:
        loop.submit(row)
    _, spans = _traced(tmp_path, loop.run_until_drained)
    steps = [s[3] for s in spans if s[0] == "serve_loop.step"]
    assert len(steps) == 2
    assert sum(a["batch"] for a in steps) == loop.counters["served"] \
        == len(q)
    assert sum(a["stragglers"] for a in steps) == \
        loop.counters["rerun_phase2"] >= 2
    # one drain per step, then a rerun per straggler chunk, each inside
    # its step's span
    bounds = [(s[1], s[2]) for s in spans if s[0] == "serve_loop.step"]
    searches = [(s[1], s[2]) for s in spans if s[0] == "serve_loop.search"]
    per_step = [sum(t0 <= a and b <= t1 for a, b in searches)
                for t0, t1 in bounds]
    assert sum(per_step) == len(searches)
    assert per_step == [1 + -(-a["stragglers"] // 2) for a in steps]
