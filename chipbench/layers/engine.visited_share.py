"""Engine (core/search_batch.py): the share of the traced stretch's busy
device time spent in ops under the ``navix.visited`` named scope (test
and set on each lane's visited bitset, first-degree and second-hop), in
percent (``scopes.busy_share``)."""

import scopes


def read(ctx):
    return scopes.busy_share(ctx, "navix.visited")
