"""Lanes (serving/lanes.py): mean milliseconds of the exact-tier re-rank
per finalize (``ExactTier.rerank_many`` on the host, the ``navix.rerank``
span), from ``LaneBatch.timing()`` deltas: rerank_ms / n_rerank over the
window. None where no re-rank ran (an f32-resident index)."""


def read(ctx):
    t = ctx.timing
    if t.get("n_rerank", 0) <= 0:
        return None
    return t["rerank_ms"] / t["n_rerank"]
