"""Engine (core/search_batch.py): the share of the traced stretch's busy
device time spent in ops under the ``navix.semimask`` named scope, in
percent (``scopes.busy_share``). The scope holds the first-degree test
of candidates against each lane's selected set S and the whole
second-hop eligibility test, ``batch_eligible``, which tests S and the
visited set in one call (one kernel on the TPU). The first-degree
visited test and the setting of visited bits count under
``navix.visited``."""

import scopes


def read(ctx):
    return scopes.busy_share(ctx, "navix.semimask")
