"""Kernel (kernels/gather_distance.py): the int8 batched gather+distance
kernel's share of its roofline, in percent: the least time the chip
could take for the kernel's calls in the traced stretch (``work.py``:
one int8 row and its scale per candidate) over their summed device
time. Each event's result shape ``[lanes, 1, candidates]`` gives its
call's size."""

import work

#: the kernel's name in the device trace
KERNEL = "quantized_gather_distance_batch_pallas"
ITEMSIZE = 1
OPS_PEAK = "int8_ops_per_s"


def read(ctx):
    if ctx.trace is None:
        return None
    events = ctx.trace.kernel(KERNEL)
    seconds = sum(e.dur for e in events) * 1e-9
    if seconds <= 0:
        return None
    ops = nbytes = 0.0
    for e in events:
        lanes, _, cands = e.result_dims()
        o, b = work.gather_distance(lanes, cands, ctx.cfg["d"], ITEMSIZE,
                                    ctx.cfg["metric"])
        ops, nbytes = ops + o, nbytes + b
    share, _ = work.roofline_share(ops, nbytes, seconds,
                                   work.peaks(ctx.device_kind), OPS_PEAK)
    return 100.0 * share
