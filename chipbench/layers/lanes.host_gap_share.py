"""Lanes (serving/lanes.py): the share of the window's chunk loop in
which the device waited for host work that no chunk overlapped, from
``LaneBatch.timing()`` deltas: host_gap / (host_gap + host_overlap +
device_wait), in percent."""


def read(ctx):
    t = ctx.timing
    total = t["host_gap_ms"] + t["host_overlap_ms"] + t["device_wait_ms"]
    return 100.0 * t["host_gap_ms"] / total if total > 0 else None
