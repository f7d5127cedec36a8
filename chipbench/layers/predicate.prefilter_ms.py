"""Predicate (query/operators.py via ``NavixDB.prefilter``): mean
milliseconds the system spent evaluating a request's Q_S,
``Response.prefilter_ms``, over the window's answered requests. A
request whose Q_S the service had cached reads 0. None where the window
answered nothing or the responses carry no such field."""

import statistics


def read(ctx):
    got = [getattr(r.resp, "prefilter_ms", None) for r in ctx.window]
    got = [x for x in got if x is not None]
    return statistics.fmean(got) if got else None
