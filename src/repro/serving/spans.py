"""Named host spans of the served path, on the profiler's clock.

Every span is a ``jax.profiler.TraceAnnotation``: it lands in the same
profile as the device ops, on the thread that ran it, so each idle gap
of a device trace lies inside a named ``navix.*`` span. When no profiler
is running a TraceAnnotation costs next to nothing, so the spans are
always on. A span that covers the same interval as a program counter
feeds that counter (``counters[key] += elapsed ms``) rather than keeping
a second clock.

The spans (docs/API.md, "Responses, spans and counters", lists where
each runs): ``navix.tick`` (a step annotation per service tick) holding
``navix.expire``, ``navix.wait``, ``navix.finalize`` (with
``navix.rerank``), ``navix.evict``, ``navix.admit``, ``navix.dispatch``
and ``navix.resolve``; ``navix.submit`` holding ``navix.prefilter``,
``navix.prep_query`` and ``navix.enqueue``.

Inside the engine's chunk program, ``jax.named_scope``s (``navix.visited``,
``navix.semimask``, ``navix.gather``, ``navix.merge``,
``navix.frontier``) put the scope into each op's HLO ``op_name``. A TPU
profile's op events carry the instruction's text but not its
``op_name``, so :func:`op_scopes` maps instructions to scopes from the
optimized HLO of the chunk programs this process dispatched.
"""

from __future__ import annotations

import contextlib
import re
import time
from typing import Callable, Optional

import jax


@contextlib.contextmanager
def span(name: str, counters: Optional[dict] = None,
         key: Optional[str] = None, **attrs):
    """A named host span; with ``counters``, also add its elapsed
    milliseconds to ``counters[key]``."""
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name, **attrs):
            yield
    finally:
        if counters is not None:
            counters[key] += (time.perf_counter() - t0) * 1e3


# -- device op -> named scope ------------------------------------------------
# Process-wide, as a profile is: the chunk programs dispatched so far, each
# a thunk that lowers it from abstract shapes (it holds no device buffer),
# and its instruction -> scope map once built. Nothing is compiled until a
# profile's reader asks.
_PROGRAMS: dict = {}

_INSTR = re.compile(r"\s*(?:ROOT )?(%[\w.\-]+) = ")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_OPERAND = re.compile(r"%[\w.\-]+")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(r"navix\.\w+")


def note_program(key, lower: Callable[[], "jax.stages.Lowered"]) -> None:
    """Record a dispatched chunk program (once per ``key``)."""
    _PROGRAMS.setdefault(key, [lower, None])


def op_key(text: str) -> Optional[tuple]:
    """An HLO instruction's identity from its text -- a line of the
    optimized HLO, or a profile's op event name: the instruction's name
    and its operands' names, e.g. ``("%fusion.7", ("%a", "%b"))``."""
    m = _INSTR.match(text)
    if m is None:
        return None
    rest = text[m.end():]
    call = _OPCODE.search(rest)
    if call is None:
        return m.group(1), ()
    depth, end = 0, len(rest)
    for i in range(call.end() - 1, len(rest)):
        depth += {"(": 1, ")": -1}.get(rest[i], 0)
        if depth == 0:
            end = i
            break
    return m.group(1), tuple(_OPERAND.findall(rest[call.end():end]))


def scope_map(hlo_text: str) -> dict:
    """:func:`op_key` -> the last ``navix.*`` scope of the instruction's
    ``op_name``, over one optimized HLO module's text."""
    out = {}
    for line in hlo_text.splitlines():
        op_name = _OP_NAME.search(line)
        found = _SCOPE.findall(op_name.group(1)) if op_name else []
        key = op_key(line) if found else None
        if key is not None:
            out[key] = found[-1]
    return out


def op_scopes() -> dict:
    """:func:`op_key` -> ``navix.*`` scope over every chunk program
    dispatched in this process. Compiles each program once, on first
    call (a persistent compilation cache makes that a lookup)."""
    out = {}
    for entry in list(_PROGRAMS.values()):
        if entry[1] is None:
            entry[1] = scope_map(entry[0]().compile().as_text())
        out.update(entry[1])
    return out
