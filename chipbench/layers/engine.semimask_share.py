"""Engine (core/search_batch.py): the share of the traced stretch's busy
device time spent in ops under the ``navix.semimask`` named scope (the
test of candidates against each lane's selected set S, first-degree and
second-hop), in percent (``scopes.busy_share``)."""

import scopes


def read(ctx):
    return scopes.busy_share(ctx, "navix.semimask")
