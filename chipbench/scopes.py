"""Device time by the engine's named scopes (``jax.named_scope`` in
core/search_batch.py: ``navix.visited``, ``navix.semimask``, ...).

An op's scope is the last ``navix.*`` component of its HLO ``op_name``.
A TPU trace's op events carry the instruction's text but no
``op_name``, so the program's own map names it
(``repro.serving.spans.op_scopes``: instruction -> scope, from the
optimized HLO of the chunk programs the process dispatched). A fusion
takes the ``op_name`` of its root, so a fusion that mixes two scopes
counts in one. A program without that map gives no scope, and the
readers then return None.
"""

from __future__ import annotations

from collections import defaultdict
from typing import Optional

import tracing


def _program_map():
    """The program's instruction -> scope map and its key function;
    an empty map where the program has none."""
    try:
        from repro.serving.spans import op_key, op_scopes
    except ImportError:
        return {}, None
    return op_scopes(), op_key


def seconds(summary: tracing.Summary) -> dict:
    """Scope -> device self seconds of the traced stretch's ops,
    averaged over devices."""
    table, key_of = _program_map()
    if not table:
        return {}
    scope_of: dict = {}                  # op event name -> scope
    out: dict = defaultdict(float)
    for e, own in tracing.self_times(summary.events):
        if e.name not in scope_of:
            scope_of[e.name] = table.get(key_of(e.name))
        if scope_of[e.name] is not None:
            out[scope_of[e.name]] += own * 1e-9 / summary.n_devices
    return dict(out)


def busy_share(ctx, scope: str) -> Optional[float]:
    """100 × the device time under ``scope`` over the busy time; None
    where no op of the trace lies under any scope."""
    if ctx.trace is None or ctx.trace.busy_s <= 0:
        return None
    by_scope = seconds(ctx.trace)
    if not by_scope:
        return None
    return 100.0 * by_scope.get(scope, 0.0) / ctx.trace.busy_s
