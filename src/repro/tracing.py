"""Program spans and op scopes: the names a profiler trace gives the
program's host code and device ops.

``span`` is a ``jax.profiler.TraceAnnotation``: its event lands in the
profiler's trace on the same clock as the device's operations, so a trace
reduction can put each stretch the device sat idle down to the innermost
span around it.  Counts set in the dict the span yields go out as the
event's stats when it ends.  With ``into`` the span also adds its
host-clock duration into that dict under ``key`` (the build's
``PiPNNIndex.timings``).  With no profiler running a span costs a flag
check and, with ``into``, a clock pair and one dict add; it records
nothing else.

``op_scopes`` reads a compiled module's text (``jitted.lower(...)
.compile().as_text()``) and maps each instruction, by the name a trace's
``XLA Ops`` line gives it, to the ``jax.named_scope`` path it was traced
under.  It compiles nothing itself; the text comes from a program's
cached executable, outside any timed window.
"""
from __future__ import annotations

import contextlib
import re
import time
from typing import Iterator

import jax

UNSCOPED = "(unscoped)"

# a computation's header: ``%name (params) -> type {`` or ``ENTRY %name ...``
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
# an instruction: ``[ROOT] %name = type opcode(operands), attributes``
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(
    r"(?:calls|to_apply|body|condition|true_computation|false_computation)"
    r"=%([\w.\-]+)|branch_computations=\{([^}]*)\}")
_NAME = re.compile(r"%([\w.\-]+)")
# name-stack entries that wrap a traced function: jit(f), pjit(f), vmap(f)
_WRAPPER = re.compile(r"^\w+\(.*\)$")


@contextlib.contextmanager
def span(name: str, *, into: dict | None = None, key: str | None = None,
         **args) -> Iterator[dict]:
    """A trace event named ``name`` around the block; yields ``args``, the
    counts recorded with it (set more before the block ends).  ``into``
    gets the block's host-clock seconds added under ``key`` (default:
    ``name``)."""
    t0 = time.perf_counter()
    on = jax.profiler.TraceAnnotation.is_enabled()
    with (jax.profiler.TraceAnnotation(name) if on
          else contextlib.nullcontext()) as event:
        try:
            yield args
        finally:
            if event is not None and args:
                event.set_metadata(**args)
            if into is not None:
                k = name if key is None else key
                into[k] = into.get(k, 0.0) + time.perf_counter() - t0


def scope_of(op_name: str) -> str:
    """``"jit(f)/leaf_knn/while/body/dot_general"`` -> ``"leaf_knn/while/
    body"``: the path left once the wrapping ``jit(...)`` entries and the
    primitive are taken off (``UNSCOPED`` when nothing is left)."""
    parts = op_name.split("/")
    while parts and _WRAPPER.match(parts[0]):
        parts.pop(0)
    return "/".join(parts[:-1]) or UNSCOPED


def _operands(rest: str, opcode: re.Match) -> list[str]:
    """The ``%names`` between the opcode's parentheses."""
    depth, i = 0, opcode.end() - 1
    for j in range(i, len(rest)):
        depth += {"(": 1, ")": -1}.get(rest[j], 0)
        if depth == 0:
            return _NAME.findall(rest[i:j])
    return []


def op_scopes(compiled_text: str) -> dict[str, str]:
    """{instruction name: scope path} for every instruction of
    ``compiled_text``.  An instruction traced from the program takes the
    scope its ``op_name`` names (``UNSCOPED`` outside every scope).  One
    the compiler made (a layout copy, a fusion it rooted in a copy, a
    loop body's bookkeeping: no ``op_name``, or one that is not a traced
    path) takes, in this order, the scope of what its fusion or loop
    holds (the root first), that of its first operand that has one, or
    that of the instruction whose body it is in.  The text lists operands
    before their users and a body before its caller, so one pass forward
    and one back settle every instruction."""
    made: list[tuple[str, str]] = []     # compiler-made: (name, computation)
    bodies: dict[str, list[str]] = {}    # computation -> its instructions
    caller: dict[str, str] = {}          # computation -> calling instruction
    scope: dict[str, str] = {}
    comp = None
    for line in compiled_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            comp = c.group(1) if c else comp
            continue
        name, rest = m.groups()
        called = [n for a, b in _CALLED.findall(rest)
                  for n in ([a] if a else _NAME.findall(b))]
        bodies.setdefault(comp, []).append(name)
        for c in called:
            caller.setdefault(c, name)
        own = _OP_NAME.search(rest)
        if own and _WRAPPER.match(own.group(1).split("/")[0]):
            scope[name] = scope_of(own.group(1))
            continue
        op = _OPCODE.search(rest)
        held = [scope.get(n, UNSCOPED) for c in called
                for n in reversed(bodies.get(c, []))]
        fed = [scope.get(n, UNSCOPED)
               for n in (_operands(rest, op) if op else [])]
        scope[name] = next((s for s in held + fed if s != UNSCOPED),
                           UNSCOPED)
        made.append((name, comp))
    for name, comp in reversed(made):
        if scope[name] == UNSCOPED and comp in caller:
            scope[name] = scope[caller[comp]]
    return scope
