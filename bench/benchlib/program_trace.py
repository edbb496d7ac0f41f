"""The program's own names in a profiler trace: its host spans, and the
scopes of one program's device ops.

The program (``repro.tracing``) opens spans named ``pipnn.*``, ``rbc.*``
and ``serve_loop.*`` around its host work, each with counts recorded as
the event's stats, and names the stages of its fused stream step with
``jax.named_scope``.  This module reads them from the same
``.xplane.pb`` as ``trace.reduce``, and leaves what that reads alone:

* program spans — the host spans whose names start with
  ``PROGRAM_PREFIXES``, with their counts;
* idle gaps — as ``trace.attribute`` puts them, but down to the innermost
  span among the benchmark's and the program's, so a gap inside
  ``pipnn.link_entry_hubs`` within ``bench.build`` goes to the former;
* scope seconds — device seconds per top-level scope of one program's
  ops, from the map ``repro.tracing.op_scopes`` makes of that program's
  compiled text;
* span metrics — the host seconds and counts the spans add up to.

Nothing here imports the program at import time: a checkout whose
program has no spans or scopes reads ``None``.
"""
from __future__ import annotations

import dataclasses

from benchlib import trace

PROGRAM_PREFIXES = ("pipnn.", "rbc.", "serve_loop.")
STREAM_PROGRAM = "jit_stream_step"     # ``pipnn._make_stream_step``'s
UNSCOPED = "(unscoped)"


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start_ns: int
    end_ns: int
    args: dict

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


def read_spans(path: str) -> list[Span]:
    """The program spans of ``path``, in time order, with their stats."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PROGRAM_PREFIXES):
                    start = int(e.start_ns)
                    out.append(Span(e.name, start,
                                    start + int(e.duration_ns),
                                    dict(e.stats)))
    return sorted(out, key=lambda s: (s.start_ns, -s.end_ns))


def idle_by_span(path: str) -> list[list]:
    """[[span name, idle seconds]] of the window's idle gaps on the first
    device, each put down to the innermost benchmark or program span
    around its midpoint, most first (``trace.attribute``)."""
    devices, spans = trace._events(path)
    spans = spans + [(s.name, s.start_ns, s.end_ns)
                     for s in read_spans(path)]
    windows = [s for s in spans if s[0] == trace.WINDOW_SPAN]
    if not devices or not windows:
        return []
    lo, hi = windows[0][1], windows[0][2]
    busy = trace.union_ns([(o.start_ns, o.start_ns + o.dur_ns)
                           for o in devices[0]], lo, hi)
    return trace.attribute(trace.gaps_ns(busy, lo, hi), spans)


def self_seconds(spans: list[Span], parent: str, child: str) -> float:
    """Seconds inside ``parent`` spans and outside the ``child`` spans
    within them."""
    total = 0
    for p in (s for s in spans if s.name == parent):
        inner = trace.union_ns([(c.start_ns, c.end_ns) for c in spans
                                if c.name == child], p.start_ns, p.end_ns)
        total += (p.end_ns - p.start_ns) - sum(e - s for s, e in inner)
    return total * 1e-9


def span_metrics(spans: list[Span]) -> dict:
    """What the program spans add up to (None where they are missing):
    per build, the partition worklist's host seconds outside its
    assignments and the entry-hub links' seconds; per serving step, its
    host milliseconds outside the engine's dispatches; and the share of
    served requests rerun as stragglers (%)."""
    def named(name):
        return [s for s in spans if s.name == name]

    builds, steps = len(named("pipnn.partition")), named("serve_loop.step")
    hubs = named("pipnn.link_entry_hubs")
    batch = sum(s.args.get("batch", 0) for s in steps)
    return {
        "partition_host_s": (self_seconds(spans, "rbc.worklist",
                                          "rbc.assign") / builds
                             if builds and named("rbc.worklist") else None),
        "hub_links_s": (sum(s.seconds for s in hubs) / len(hubs)
                        if hubs else None),
        "serve_host_ms": (1e3 * self_seconds(spans, "serve_loop.step",
                                             "serve_loop.search")
                          / len(steps) if steps else None),
        "straggler_share": (100.0 * sum(s.args.get("stragglers", 0)
                                        for s in steps) / batch
                            if batch else None),
    }


def scope_seconds(ops: list, scopes: dict, program: str) -> dict:
    """{top-level scope: device seconds} of ``program``'s ops, with the
    scope path of each op from ``scopes`` (``UNSCOPED`` where it has
    none).  Loops and calls count through the ops they hold."""
    out: dict[str, int] = {}
    for o in ops:
        if o.program != program or trace.base_name(o.name) in \
                trace.CONTAINERS:
            continue
        key = scopes.get(o.name, UNSCOPED).split("/")[0]
        out[key] = out.get(key, 0) + o.dur_ns
    return {k: v * 1e-9 for k, v in out.items()}


def _stream_scopes() -> dict:
    """``op_scopes`` of the stream step as the last build ran it; empty
    where the program cannot say."""
    try:
        from repro import tracing
        from repro.core import pipnn

        text = pipnn.stream_step_text()
    except (ImportError, AttributeError):
        return {}
    return tracing.op_scopes(text) if text else {}


def stream_scope_seconds(ctx: dict, scope: str) -> float | None:
    """Device seconds the traced window's stream steps spent in ``scope``;
    None without a trace, a scope map or a stream-step op.  The map is
    made once per run (kept in ``ctx``), after the window."""
    tr = ctx.get("trace")
    if tr is None:
        return None
    if "stream_scopes" not in ctx:
        ctx["stream_scopes"] = _stream_scopes()
    if not ctx["stream_scopes"]:
        return None
    by_scope = scope_seconds(tr.ops, ctx["stream_scopes"], STREAM_PROGRAM)
    return by_scope.get(scope, 0.0) if by_scope else None
