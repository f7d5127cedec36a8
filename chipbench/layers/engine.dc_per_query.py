"""Engine (core/search_batch.py): mean distance computations per answered
request of the window, from ``Response.dc`` (``SearchStats.t_dc +
upper_dc``: the lower level's and the greedy upper descent's). It
depends on the seed only, not on timing. None where the responses carry
no counter."""

import statistics


def read(ctx):
    got = [getattr(r.resp, "dc", None) for r in ctx.window]
    got = [x for x in got if x is not None]
    return statistics.fmean(got) if got else None
