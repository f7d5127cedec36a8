"""Operations and bytes a kernel call needs, from the call's shapes.

The yardstick for a roofline share: what the algorithm needs, not what
a kernel happens to fetch. The gather+distance kernels are counted at
one row per candidate (``d * itemsize`` bytes, plus a 4-byte scale for
int8 rows), whatever tile the kernel reads around the row, and at the
logical width ``d``, whatever lane padding the store carries. A later
change that fetches less moves the kernel's time, not this count.

Peaks come from ``peaks.json`` (keyed by JAX's ``device_kind``); a
device that is not in the table is an error.
"""

from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"

#: vector operations per element of one distance
_OPS_PER_ELEMENT = {"l2": 3, "cos": 2, "dot": 2}


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; add its published numbers")
    return table[device_kind]


def gather_distance(lanes: int, cands: int, d: int, itemsize: int,
                    metric: str) -> tuple[float, float]:
    """``(ops, bytes)`` of one batched gather+distance call: ``lanes``
    queries of width ``d`` (f32), ``cands`` candidate ids per lane (int32),
    one distance out per candidate (f32). ``itemsize`` 1 is an int8 row
    with its f32 scale, dequantized at one multiply per element."""
    rows = lanes * cands
    row_bytes = d * itemsize + (4 if itemsize == 1 else 0)
    nbytes = rows * row_bytes + lanes * d * 4 + rows * 4 + rows * 4
    ops = rows * d * (_OPS_PER_ELEMENT[metric] + (1 if itemsize == 1
                                                  else 0))
    return float(ops), float(nbytes)


def roofline_share(ops: float, nbytes: float, seconds: float,
                   peak: dict, ops_peak_key: str) -> tuple[float, str]:
    """The least time the chip could take (the larger of ops over peak
    ops and bytes over peak bandwidth) as a share of ``seconds``, and
    which of the two bounds it."""
    t_ops = ops / peak[ops_peak_key]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return max(t_ops, t_bytes) / seconds, ("bytes" if t_bytes >= t_ops
                                           else "ops")
