"""Reduction of a profiler trace to the device's busy time, kernel times
and idle gaps.

A trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  It holds one
plane per TPU (``/device:TPU:<i>``).  Its ``XLA Ops`` line has an event
per operation the device ran, named by the HLO instruction's text
(``%gather_distance_hbm.3 = f32[...] custom-call(...)``), and its ``XLA
Modules`` line an event per program run (``jit_step(<hash>)``).  The host
plane holds the benchmark's spans (``bench.*``, ``spans.py``) on the line
of the thread that ran them.  All are on the profiler's one clock.

* busy time — the union of the operations' intervals inside the window
  (the ``bench.window`` span), averaged over the devices;
* kernel time — the summed durations of the operations whose
  instruction is the kernel's (a Pallas kernel's custom call is named
  after the jitted function that wraps it: ``gather_distance_hbm``);
* top operations — device time per ``<program>/<instruction>``, leaving
  out loops and calls, whose time is that of the operations they hold;
* idle gaps — the stretches of the window with no operation on the
  device, each put down to the innermost benchmark span around its
  midpoint, and summed per span name.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
# ops that only hold other ops: their time is their children's
CONTAINERS = ("while", "conditional", "call")
SPAN_PREFIX = "bench."
NO_SPAN = "(outside the benchmark's spans)"


@dataclasses.dataclass(frozen=True)
class Op:
    name: str                  # instruction, e.g. "gather_distance_hbm.3"
    start_ns: int
    dur_ns: int
    program: str               # the program it ran in, e.g. "jit_step"


def instruction(text: str) -> str:
    """``"%fusion.4 = f32[...] fusion(...)"`` -> ``"fusion.4"``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def base_name(name: str) -> str:
    """An instruction's name without its numeric suffix."""
    return re.sub(r"\.\d+$", "", name)


def program_name(text: str) -> str:
    """``"jit_step(1407...)"`` -> ``"jit_step"``."""
    return text.split("(", 1)[0]


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    top_ops: list              # [[name, seconds]], most time first
    idle_by_span: list         # [[span name, idle seconds]], most first
    ops: list                  # every Op inside the window (first device)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s if self.window_s else 0.0

    def kernel_seconds(self, kernel: str) -> float:
        return sum(o.dur_ns for o in self.ops
                   if base_name(o.name) == kernel) * 1e-9


def find_trace_file(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def _events(path: str):
    """(device op lists, host spans) from an ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            raw, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    raw = [(instruction(e.name), int(e.start_ns),
                            int(e.duration_ns)) for e in line.events]
                elif line.name == MODULES_LINE:
                    modules = sorted((int(e.start_ns), program_name(e.name))
                                     for e in line.events)
            starts = [m[0] for m in modules]
            ops = []
            for name, start, dur in raw:
                j = bisect.bisect_right(starts, start) - 1
                ops.append(Op(name, start, dur,
                              modules[j][1] if j >= 0 else "?"))
            devices.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, int(e.start_ns),
                                      int(e.start_ns + e.duration_ns)))
    return devices, spans


def union_ns(intervals: list[tuple[int, int]], lo: int, hi: int
             ) -> list[tuple[int, int]]:
    """The union of ``intervals`` clipped to [lo, hi], as sorted disjoint
    intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps_ns(busy: list[tuple[int, int]], lo: int, hi: int
            ) -> list[tuple[int, int]]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def attribute(gaps: list[tuple[int, int]], spans: list[tuple[str, int, int]]
              ) -> list[list]:
    """[[span name, seconds]] of idle time per innermost span at each
    gap's midpoint, most first.  Spans of one thread nest, so a stack
    swept in time order holds the innermost one on top."""
    inner = sorted((s for s in spans if s[0] != WINDOW_SPAN),
                   key=lambda s: (s[1], -s[2]))
    total: dict[str, int] = {}
    stack: list[tuple[str, int, int]] = []
    j = 0
    for g0, g1 in sorted(gaps):
        mid = (g0 + g1) // 2
        while j < len(inner) and inner[j][1] <= mid:
            while stack and stack[-1][2] < inner[j][1]:
                stack.pop()
            stack.append(inner[j])
            j += 1
        while stack and stack[-1][2] < mid:
            stack.pop()
        key = stack[-1][0] if stack else NO_SPAN
        total[key] = total.get(key, 0) + (g1 - g0)
    ranked = sorted(total.items(), key=lambda t: -t[1])
    return [[k, v * 1e-9] for k, v in ranked[:10]]


def reduce(path: str) -> TraceSummary:
    devices, spans = _events(path)
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    if windows:
        lo, hi = windows[0][1], windows[0][2]
    else:
        ends = [(o.start_ns, o.start_ns + o.dur_ns) for d in devices
                for o in d] + [(s, e) for _, s, e in spans]
        lo = min((s for s, _ in ends), default=0)
        hi = max((e for _, e in ends), default=0)
    window_s = (hi - lo) * 1e-9
    busy_each, first_ops, first_busy = [], [], []
    for k, ops in enumerate(devices):
        inside = [o for o in ops if o.start_ns < hi
                  and o.start_ns + o.dur_ns > lo]
        busy = union_ns([(o.start_ns, o.start_ns + o.dur_ns)
                         for o in inside], lo, hi)
        busy_each.append(sum(e - s for s, e in busy) * 1e-9)
        if k == 0:
            first_ops, first_busy = inside, busy
    by_name: dict[str, int] = {}
    for o in first_ops:
        if base_name(o.name) in CONTAINERS:
            continue
        key = f"{o.program}/{o.name}"
        by_name[key] = by_name.get(key, 0) + o.dur_ns
    top = sorted(by_name.items(), key=lambda t: -t[1])[:10]
    idle = attribute(gaps_ns(first_busy, lo, hi), spans) if devices else []
    return TraceSummary(
        window_s=window_s,
        busy_s=float(np.mean(busy_each)) if busy_each else 0.0,
        top_ops=[[k, v * 1e-9] for k, v in top],
        idle_by_span=idle, ops=first_ops)
