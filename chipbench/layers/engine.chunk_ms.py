"""Engine (core/search_batch.py): milliseconds from dispatching one
``step_iters`` chunk to its result, from ``LaneBatch.timing()`` deltas:
(host_overlap + device_wait) / n_chunks over the window."""


def read(ctx):
    t = ctx.timing
    if t["n_chunks"] <= 0:
        return None
    return (t["host_overlap_ms"] + t["device_wait_ms"]) / t["n_chunks"]
