"""Engine (core/search_batch.py): mean lower-level loop iterations per
answered request of the window, from ``Response.iters`` (the engine's
per-lane ``SearchStats.iters``, fetched at finalize). It depends on the
seed only, not on timing. None where the responses carry no counter."""

import statistics


def read(ctx):
    got = [getattr(r.resp, "iters", None) for r in ctx.window]
    got = [x for x in got if x is not None]
    return statistics.fmean(got) if got else None
