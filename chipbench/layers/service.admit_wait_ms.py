"""Front door (serving/service.py): mean milliseconds from a request's
enqueue in ``SearchService.submit`` to the admission that gave it a
lane, ``Response.queue_ms``, over the window's answered requests."""

import statistics


def read(ctx):
    got = [r.resp.queue_ms for r in ctx.window]
    return statistics.fmean(got) if got else None
