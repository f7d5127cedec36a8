"""Public jit'd wrappers around the Pallas kernels.

Pad-and-dispatch layer: the distance-matrix wrappers pad arbitrary shapes
up to MXU-aligned tile multiples, run the kernel, and slice results back;
the gather wrappers take the store as it is resident (any width; on the
TPU the store is lane-padded once at residency, see
``repro.core.graph.resident_width``, so nothing store-sized is copied per
call). On hosts without a TPU the wrappers route to the pure-jnp oracles
(``ref.py``) so the whole framework runs anywhere; the kernels themselves
stay validated in interpret mode by ``tests/test_kernels.py`` and compiled
for a described v5e by ``tests/test_tpu_compile.py``. Set
``REPRO_FORCE_PALLAS=1`` to force interpret-mode kernels on CPU (slow;
used by the kernel benchmarks).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.bit_test import LANES, second_hop_eligible_pallas
from repro.kernels.distance_matrix import distance_matrix_pallas
from repro.kernels.gather_distance import (
    gather_distance_batch_pallas, gather_distance_pallas,
    quantized_gather_distance_batch_pallas, quantized_gather_distance_pallas)
from repro.kernels.quantized import quantized_distance_pallas
from repro.kernels.segment_sum import csr_segment_sum_pallas, plan_tiles


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _force_pallas() -> bool:
    return os.environ.get("REPRO_FORCE_PALLAS", "0") == "1"


def _use_pallas() -> bool:
    return _on_tpu() or _force_pallas()


def _pad_to(x: jax.Array, axis: int, mult: int, value=0) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


# ---------------------------------------------------------------------------


def distance_matrix(Q, X, metric: str = "l2", bq: int = 128, bn: int = 128,
                    bd: int = 128):
    """All-pairs distances with automatic padding. f32[b, n]."""
    if not _use_pallas():
        return ref.distance_matrix(Q, X, metric)
    b, n = Q.shape[0], X.shape[0]
    bq = min(bq, max(8, 1 << (b - 1).bit_length()))
    Qp = _pad_to(_pad_to(Q, 1, bd), 0, bq)
    Xp = _pad_to(_pad_to(X, 1, bd), 0, bn)
    out = distance_matrix_pallas(Qp, Xp, metric, bq=bq, bn=bn,
                                 bd=min(bd, Qp.shape[1]),
                                 interpret=not _on_tpu())
    return out[:b, :n]


def gather_distance(q, vectors, ids, metric: str = "l2"):
    """Fused gather+distance: dist(q, vectors[ids]); ids<0 -> inf. f32[k]."""
    if not _use_pallas():
        return ref.gather_distance(q, vectors, ids, metric)
    return gather_distance_pallas(q, vectors, ids, metric,
                                  interpret=not _on_tpu())


def gather_distance_batch(Q, vectors, ids, metric: str = "l2"):
    """Batched fused gather+distance: dist(Q[b], vectors[ids[b]]). f32[b,k].

    One pallas_call grid streams all B id lists (the batched engine's
    distance primitive); ids<0 -> inf.
    """
    if not _use_pallas():
        return ref.gather_distance_batch(Q, vectors, ids, metric)
    return gather_distance_batch_pallas(Q, vectors, ids, metric,
                                        interpret=not _on_tpu())


def quantized_gather_distance(q, codes, scale, ids, metric: str = "l2"):
    """Fused int8 gather+distance: dist(q, scale[ids] * codes[ids]);
    ids<0 -> inf. f32[k]. The single-query primitive of the
    quantized-resident engine."""
    if not _use_pallas():
        return ref.quantized_gather_distance(q, codes, scale, ids, metric)
    return quantized_gather_distance_pallas(q, codes, scale, ids, metric,
                                            interpret=not _on_tpu())


def quantized_gather_distance_batch(Q, codes, scale, ids, metric: str = "l2"):
    """Batched fused int8 gather+distance:
    dist(Q[b], scale[ids[b]] * codes[ids[b]]); ids<0 -> inf. f32[b,k].

    One pallas_call grid streams all B id lists over the int8 store --
    the batched-frontier engine's distance primitive when the index is
    quantized-resident (d + 4 HBM bytes per candidate instead of 4d).
    """
    if not _use_pallas():
        return ref.quantized_gather_distance_batch(Q, codes, scale, ids,
                                                   metric)
    return quantized_gather_distance_batch_pallas(Q, codes, scale, ids,
                                                  metric,
                                                  interpret=not _on_tpu())


#: the most 128-word rows a lane's bitset may have for
#: :func:`second_hop_eligible` to take the Pallas kernel, whose cost grows
#: with the rows where the XLA gathers' does not: on one v5e (64 lanes x
#: 4,096 ids) the kernel takes 0.04 ms at 16 rows and 6.4 ms at 1,024, the
#: XLA gathers 6.4-7.7 ms at any width, and the two cross near 1,200 rows
#: (n about 4.9M; PERF.md)
BIT_TEST_MAX_ROWS = 1024


def second_hop_eligible(sel, visited, ids):
    """The second hop's candidate test: ``ids >= 0 & bit(sel[b], ids) &
    ~bit(visited[b], ids)`` -> bool[B, L], for per-lane bitsets
    ``sel``/``visited`` ``[B, W]`` and ids ``[B, L]``.

    One Pallas pass with both bitsets in VMEM while W fits
    ``BIT_TEST_MAX_ROWS`` rows of 128 words; the two XLA bit tests
    otherwise and off the TPU."""
    if not _use_pallas() or -(-sel.shape[1] // LANES) > BIT_TEST_MAX_ROWS:
        return ref.second_hop_eligible(sel, visited, ids)
    return second_hop_eligible_pallas(sel, visited, ids,
                                      interpret=not _on_tpu())


def quantized_distance_matrix(Q, codes, scale, metric: str = "l2",
                              bq: int = 128, bn: int = 128, bd: int = 128):
    """Distances against int8 codes with per-vector scales. f32[b, n]."""
    if not _use_pallas():
        return ref.quantized_distance_matrix(Q, codes, scale, metric)
    b, n = Q.shape[0], codes.shape[0]
    bq = min(bq, max(8, 1 << (b - 1).bit_length()))
    Qp = _pad_to(_pad_to(Q, 1, bd), 0, bq)
    Cp = _pad_to(_pad_to(codes, 1, bd), 0, bn)
    Sp = _pad_to(scale, 0, bn)
    out = quantized_distance_pallas(Qp, Cp, Sp, metric, bq=bq, bn=bn,
                                    bd=min(bd, Qp.shape[1]),
                                    interpret=not _on_tpu())
    return out[:b, :n]


def csr_segment_sum(messages, dst_sorted, n: int, bn: int = 128,
                    be: int = 256):
    """Sorted segment sum -> f32[n, d]. messages[E,d], dst_sorted[E]
    ascending; -1 padding allowed anywhere only if pre-sorted as if it were
    +inf (callers usually produce it at the end)."""
    if not _use_pallas():
        return ref.csr_segment_sum(messages, dst_sorted, n)
    from repro.kernels.segment_sum import PAD_SENTINEL
    mp = _pad_to(messages, 0, be)
    dp = _pad_to(dst_sorted, 0, be, value=PAD_SENTINEL)
    dp = jnp.where(dp < 0, PAD_SENTINEL, dp)
    first, t_max = plan_tiles(np.asarray(dp), n, bn, be, mp.shape[0])
    out = csr_segment_sum_pallas(mp, dp, jnp.asarray(first), n, bn=bn, be=be,
                                 t_max=t_max, interpret=not _on_tpu())
    return out[:n]
