"""The reduction of the program's own names in a trace
(``benchlib.program_trace``) and the stream-scope readers: idle gaps go
to the innermost span, program spans add up, scope seconds follow the
scope map, the readers read what they are given and ``None`` where the
program cannot say, a trace recorded on the chip reads as it did there,
and the scope map agrees with the op names the chip recorded."""
from __future__ import annotations

import bisect
import gzip
import json
import pathlib
import re
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
for p in (str(BENCH), str(BENCH.parent / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchlib import program_trace as pt  # noqa: E402
from benchlib import spec, trace  # noqa: E402
from repro import tracing  # noqa: E402

DATA = BENCH / "tests" / "data"
SCOPE_READERS = {"build.stream.leaf_knn_s": "leaf_knn",
                 "build.stream.sort_s": "chunk_sort",
                 "build.stream.merge_s": "reservoir_merge"}


def _span(name, s, e, **args):
    return pt.Span(name, s, e, args)


def test_idle_gap_goes_to_the_program_span_inside_the_benchmarks():
    spans = [("bench.window", 0, 100), ("bench.build", 0, 100),
             ("pipnn.partition", 5, 30), ("rbc.worklist", 5, 30),
             ("rbc.assign", 10, 12),
             ("pipnn.link_entry_hubs", 60, 90)]
    gaps = [(20, 24), (11, 11 + 1), (70, 80), (95, 99)]
    got = {k: v * 1e9 for k, v in trace.attribute(gaps, spans)}
    assert got == pytest.approx({"rbc.worklist": 4.0, "rbc.assign": 1.0,
                                 "pipnn.link_entry_hubs": 10.0,
                                 "bench.build": 4.0})


def test_self_seconds_leave_out_the_children():
    spans = [_span("rbc.worklist", 0, 100), _span("rbc.assign", 10, 30),
             _span("rbc.assign", 50, 60), _span("rbc.worklist", 200, 210),
             _span("rbc.assign", 300, 400)]      # outside every parent
    assert pt.self_seconds(spans, "rbc.worklist", "rbc.assign") == \
        pytest.approx(80e-9)


def test_span_metrics_add_up_the_spans():
    spans = [_span("pipnn.partition", 0, 10), _span("rbc.worklist", 0, 10),
             _span("rbc.assign", 2, 6),
             _span("pipnn.link_entry_hubs", 20, 32),
             _span("serve_loop.step", 100, 200, batch=8, stragglers=2),
             _span("serve_loop.search", 110, 150),
             _span("serve_loop.search", 160, 190),
             _span("serve_loop.step", 200, 260, batch=8, stragglers=0),
             _span("serve_loop.search", 210, 250)]
    got = pt.span_metrics(spans)
    assert got["partition_host_s"] == pytest.approx(6e-9)
    assert got["hub_links_s"] == pytest.approx(12e-9)
    assert got["serve_host_ms"] == pytest.approx(1e3 * (30 + 20) / 2 * 1e-9)
    assert got["straggler_share"] == pytest.approx(100 * 2 / 16)
    assert pt.span_metrics([]) == dict.fromkeys(got)


def _ops():
    op = trace.Op
    return [op("sort.62", 0, 50, pt.STREAM_PROGRAM),
            op("fusion.3", 50, 20, pt.STREAM_PROGRAM),
            op("while.1", 0, 60, pt.STREAM_PROGRAM),       # a container
            op("fusion.6", 70, 10, pt.STREAM_PROGRAM),
            op("merge_sorted_reservoirs.1", 80, 15, pt.STREAM_PROGRAM),
            op("copy.9", 95, 5, pt.STREAM_PROGRAM),         # not in the map
            op("sort.62", 200, 40, "jit_carve_step")]     # another program


SCOPES = {"sort.62": "leaf_knn/while/body", "fusion.3": "edge_hash",
          "while.1": "leaf_knn", "fusion.6": "chunk_sort/jit(f)",
          "merge_sorted_reservoirs.1": "reservoir_merge"}


def test_scope_seconds_follow_the_map():
    got = pt.scope_seconds(_ops(), SCOPES, pt.STREAM_PROGRAM)
    assert {k: v * 1e9 for k, v in got.items()} == pytest.approx(
        {"leaf_knn": 50, "edge_hash": 20, "chunk_sort": 10,
         "reservoir_merge": 15, pt.UNSCOPED: 5})


def _summary(ops):
    return trace.TraceSummary(window_s=1.0, busy_s=0.5, top_ops=[],
                              idle_by_span=[], ops=ops)


@pytest.mark.parametrize("metric", sorted(SCOPE_READERS))
def test_stream_scope_readers(metric):
    read = spec.load_reader(metric)
    ctx = {"trace": _summary(_ops()), "stream_scopes": SCOPES}
    want = {"leaf_knn": 50, "chunk_sort": 10, "reservoir_merge": 15}
    assert read(ctx) * 1e9 == pytest.approx(want[SCOPE_READERS[metric]])
    assert read({"stream_scopes": SCOPES}) is None          # no trace
    assert read({"trace": _summary(_ops()), "stream_scopes": {}}) is None
    assert read({"trace": _summary([]), "stream_scopes": SCOPES}) is None


@pytest.mark.parametrize("metric", sorted(SCOPE_READERS))
def test_stream_scope_readers_read_none_from_an_older_program(
        metric, monkeypatch):
    """A program without ``stream_step_text`` (the parent's) gives the
    readers nothing to read, and they do not raise."""
    from repro.core import pipnn

    monkeypatch.delattr(pipnn, "stream_step_text")
    assert spec.load_reader(metric)({"trace": _summary(_ops())}) is None


def test_reduction_of_the_recorded_build_and_serve_trace():
    """``tests/data/build_serve_v5e.xplane.pb`` is a 4,096-point build and
    eight ServeLoop steps traced on a TPU v5e
    (``record_program_trace.py``); ``build_serve_v5e.json`` is what the
    reduction read there."""
    path = str(DATA / "build_serve_v5e.xplane.pb")
    with open(DATA / "build_serve_v5e.json") as f:
        want = json.load(f)
    spans = pt.read_spans(path)
    assert [[s.name, s.args] for s in spans] == \
        [[n, a] for n, _, a in want["spans"]]
    assert [s.seconds for s in spans] == pytest.approx(
        [sec for _, sec, _ in want["spans"]], rel=1e-12)
    assert pt.span_metrics(spans) == pytest.approx(want["span_metrics"],
                                                   rel=1e-12)
    got_idle = pt.idle_by_span(path)
    assert [k for k, _ in got_idle] == [k for k, _ in want["idle_by_span"]]
    assert [v for _, v in got_idle] == pytest.approx(
        [v for _, v in want["idle_by_span"]], rel=1e-12)
    ops = trace.reduce(path).ops
    assert pt.scope_seconds(ops, want["stream_scopes"], pt.STREAM_PROGRAM) \
        == pytest.approx(want["scope_seconds"], rel=1e-12)
    # the counts agree with the loop's counters, and every span the
    # program opens is there
    steps = [s for s in spans if s.name == "serve_loop.step"]
    assert len(steps) == 8
    assert sum(s.args["batch"] for s in steps) == want["counters"]["served"]
    assert sum(s.args["stragglers"] for s in steps) == \
        want["counters"]["rerun_phase2"]
    names = {s.name for s in spans}
    assert {"pipnn.partition", "rbc.worklist", "rbc.assign", "pipnn.sketch",
            "pipnn.stream", "pipnn.final_prune", "pipnn.link_entry_hubs",
            "pipnn.connect_from_start", "serve_loop.search"} <= names
    assert set(want["scope_seconds"]) >= {"leaf_knn", "edge_hash",
                                          "chunk_sort", "reservoir_merge"}


# The part of the profiler's XSpace proto (``xplane.proto``) that holds a
# device op's metadata: each ``XLA Ops`` event names an event metadata,
# whose ``tf_op`` stat is the op's ``op_name`` path as the chip ran it.
_XSPACE = {
    "XStat": [("metadata_id", 1, "int"), ("str_value", 5, "str")],
    "XStatMetadata": [("id", 1, "int"), ("name", 2, "str")],
    "XEventMetadata": [("id", 1, "int"), ("name", 2, "str"),
                       ("stats", 5, "*XStat")],
    "XEvent": [("metadata_id", 1, "int"), ("offset_ps", 2, "int"),
               ("duration_ps", 3, "int")],
    "XLine": [("name", 2, "str"), ("timestamp_ns", 3, "int"),
              ("events", 4, "*XEvent")],
    "EventMetadataEntry": [("key", 1, "int"),
                           ("value", 2, "XEventMetadata")],
    "StatMetadataEntry": [("key", 1, "int"), ("value", 2, "XStatMetadata")],
    "XPlane": [("name", 2, "str"), ("lines", 3, "*XLine"),
               ("event_metadata", 4, "*EventMetadataEntry"),
               ("stat_metadata", 5, "*StatMetadataEntry")],
    "XSpace": [("planes", 1, "*XPlane")],
}


def _xspace(path):
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)

    fdp = descriptor_pb2.FieldDescriptorProto
    kinds = {"int": fdp.TYPE_INT64, "str": fdp.TYPE_STRING}
    fd = descriptor_pb2.FileDescriptorProto(
        name="xspace_subset.proto", package="xs", syntax="proto3")
    for name, fields in _XSPACE.items():
        m = fd.message_type.add(name=name)
        for field, number, kind in fields:
            f = m.field.add(name=field, number=number,
                            label=(fdp.LABEL_REPEATED if kind[0] == "*"
                                   else fdp.LABEL_OPTIONAL))
            kind = kind.lstrip("*")
            if kind in kinds:
                f.type = kinds[kind]
            else:
                f.type, f.type_name = fdp.TYPE_MESSAGE, ".xs." + kind
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    space = message_factory.GetMessageClass(
        pool.FindMessageTypeByName("xs.XSpace"))()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def _device_ops(path, program):
    """[(instruction, the op_name path the chip recorded or None, its HLO
    text, duration ps)] of ``program``'s ops on the first TPU, containers
    left out.  An op belongs to the program run its start falls in."""
    plane = next(p for p in _xspace(path).planes
                 if p.name == trace.DEVICE_PREFIX + "0")
    stat = {e.value.id: e.value.name for e in plane.stat_metadata}
    meta = {e.value.id: e.value for e in plane.event_metadata}
    lines = {line.name: line for line in plane.lines}

    def timed(line):
        return [(line.timestamp_ns * 1000 + e.offset_ps, e)
                for e in line.events]

    runs = sorted((t, trace.program_name(meta[e.metadata_id].name))
                  for t, e in timed(lines[trace.MODULES_LINE]))
    starts = [t for t, _ in runs]
    out = []
    for t, e in timed(lines[trace.OPS_LINE]):
        i = bisect.bisect_right(starts, t) - 1
        m = meta[e.metadata_id]
        name = trace.instruction(m.name)
        if i < 0 or runs[i][1] != program or \
                trace.base_name(name) in trace.CONTAINERS:
            continue
        op_name = next((s.str_value for s in m.stats
                        if stat.get(s.metadata_id) == "tf_op"), None)
        traced = op_name is not None and tracing._WRAPPER.match(
            op_name.split("/")[0]) is not None
        out.append((name, op_name if traced else None, m.name,
                    e.duration_ps))
    return out


def _top(scope):
    return scope.split("/")[0]


def _body_scopes(text, hlo):
    """Top-level scopes of the traced ``op_name``s inside the computations
    an op calls (a fusion's body, and the fusions nested in it), from the
    compiled text."""
    out, todo = set(), re.findall(r"calls=%([\w.\-]+)", hlo)
    while todo:
        body = re.search(r"^%" + re.escape(todo.pop()) + r" \(.*?^}", text,
                         re.M | re.S).group(0)
        todo += re.findall(r"calls=%([\w.\-]+)", body)
        out |= {_top(tracing.scope_of(n))
                for n in re.findall(r'op_name="([^"]*)"', body)
                if tracing._WRAPPER.match(n.split("/")[0])}
    return out


def test_stream_scopes_match_the_op_names_the_chip_recorded():
    """The scope map behind ``build.stream.*``, made by ``op_scopes`` from
    the compiled text of the stream step that ran, against the chip's own
    record: each op the chip names an ``op_name`` path for is in that
    path's top-level scope, and each op over 1% of the step's device time
    that the chip names none for (a compiler-made fusion) is in the one
    scope every traced instruction of its body names."""
    path = str(DATA / "build_serve_v5e.xplane.pb")
    with gzip.open(DATA / "build_serve_v5e.stream_step.txt.gz", "rt") as f:
        text = f.read()
    with open(DATA / "build_serve_v5e.json") as f:
        want = json.load(f)
    scopes = tracing.op_scopes(text)
    ops = _device_ops(path, pt.STREAM_PROGRAM)
    names = {name for name, *_ in ops}
    assert names <= set(scopes)              # the text is what ran
    assert {n: scopes.get(n, tracing.UNSCOPED)
            for n in want["stream_scopes"]} == want["stream_scopes"]
    total = sum(ps for *_, ps in ops)
    named = sum(ps for _, op_name, _, ps in ops if op_name)
    assert named > 0.8 * total
    wrong = [(name, op_name, scopes[name]) for name, op_name, _, _ in ops
             if op_name and
             _top(tracing.scope_of(op_name)) != _top(scopes[name])]
    assert wrong == []
    per_op = {}
    for name, op_name, hlo, ps in ops:
        if op_name is None:
            per_op[name, hlo] = per_op.get((name, hlo), 0) + ps
    for (name, hlo), ps in per_op.items():
        if ps > 0.01 * total:
            assert _body_scopes(text, hlo) == {_top(scopes[name])}, name
