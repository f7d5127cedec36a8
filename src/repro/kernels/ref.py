"""Pure-jnp oracles for every Pallas kernel (the correctness contracts).

These are small, obviously-correct implementations; the kernel tests sweep
shapes/dtypes and assert_allclose kernels (interpret mode) against these.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import bitset


def distance_matrix(Q: jax.Array, X: jax.Array, metric: str) -> jax.Array:
    """f32[b,n] distances; see repro.core.distances.dist_matrix."""
    Qf = Q.astype(jnp.float32)
    Xf = X.astype(jnp.float32)
    dots = Qf @ Xf.T
    if metric == "l2":
        return (jnp.sum(Qf * Qf, -1)[:, None] + jnp.sum(Xf * Xf, -1)[None, :]
                - 2.0 * dots)
    if metric == "cos":
        return 1.0 - dots
    if metric == "dot":
        return -dots
    raise ValueError(metric)


def gather_distance(q: jax.Array, vectors: jax.Array, ids: jax.Array,
                    metric: str) -> jax.Array:
    """f32[k]: dist(q, vectors[ids]); ids < 0 -> +inf.

    Elementwise forms match ``repro.core.distances`` exactly, so the
    engines stay bitwise-identical when routed through this fallback.
    """
    rows = vectors[jnp.maximum(ids, 0)].astype(jnp.float32)
    qf = q.astype(jnp.float32)
    if metric == "l2":
        diff = rows - qf
        d = jnp.sum(diff * diff, axis=-1)
    elif metric == "cos":
        d = 1.0 - jnp.sum(rows * qf, axis=-1)
    elif metric == "dot":
        d = -jnp.sum(rows * qf, axis=-1)
    else:
        raise ValueError(metric)
    return jnp.where(ids >= 0, d, jnp.inf)


def gather_distance_batch(Q: jax.Array, vectors: jax.Array, ids: jax.Array,
                          metric: str) -> jax.Array:
    """f32[b,k]: dist(Q[b], vectors[ids[b]]); ids < 0 -> +inf.

    Same elementwise forms as ``distances.gathered_dist_batch`` (see
    :func:`gather_distance`).
    """
    rows = vectors[jnp.maximum(ids, 0)].astype(jnp.float32)  # [b, k, d]
    Qf = Q.astype(jnp.float32)[:, None, :]
    if metric == "l2":
        diff = rows - Qf
        d = jnp.sum(diff * diff, axis=-1)
    elif metric == "cos":
        d = 1.0 - jnp.sum(rows * Qf, axis=-1)
    elif metric == "dot":
        d = -jnp.sum(rows * Qf, axis=-1)
    else:
        raise ValueError(metric)
    return jnp.where(ids >= 0, d, jnp.inf)


def quantized_distance_matrix(Q: jax.Array, codes: jax.Array,
                              scale: jax.Array, metric: str) -> jax.Array:
    """Distances against int8-quantized vectors x_i ~= scale_i * codes_i."""
    X = codes.astype(jnp.float32) * scale[:, None].astype(jnp.float32)
    return distance_matrix(Q, X, metric)


def quantized_gather_distance(q: jax.Array, codes: jax.Array,
                              scale: jax.Array, ids: jax.Array,
                              metric: str) -> jax.Array:
    """f32[k]: dist(q, scale[ids] * codes[ids]); ids < 0 -> +inf.

    Rows dequantize per gathered id -- bitwise what
    ``gather_distance(q, dequantize(store), ids)`` computes (a gather of
    an elementwise product is the product of the gathers), with no
    ``[n, d]`` f32 buffer live.
    """
    safe = jnp.maximum(ids, 0)
    rows = codes[safe].astype(jnp.float32) * \
        scale[safe].astype(jnp.float32)[..., None]
    qf = q.astype(jnp.float32)
    if metric == "l2":
        diff = rows - qf
        d = jnp.sum(diff * diff, axis=-1)
    elif metric == "cos":
        d = 1.0 - jnp.sum(rows * qf, axis=-1)
    elif metric == "dot":
        d = -jnp.sum(rows * qf, axis=-1)
    else:
        raise ValueError(metric)
    return jnp.where(ids >= 0, d, jnp.inf)


def quantized_gather_distance_batch(Q: jax.Array, codes: jax.Array,
                                    scale: jax.Array, ids: jax.Array,
                                    metric: str) -> jax.Array:
    """f32[b,k]: dist(Q[b], scale[ids[b]] * codes[ids[b]]); ids < 0 -> +inf.

    The int8-resident engine's distance primitive; same elementwise forms
    as :func:`quantized_gather_distance` (and as
    ``distances.gathered_dist_batch`` over a QuantizedStore), so the
    batched and single-query paths stay bitwise-identical.
    """
    safe = jnp.maximum(ids, 0)
    rows = codes[safe].astype(jnp.float32) * \
        scale[safe].astype(jnp.float32)[..., None]       # [b, k, d]
    Qf = Q.astype(jnp.float32)[:, None, :]
    if metric == "l2":
        diff = rows - Qf
        d = jnp.sum(diff * diff, axis=-1)
    elif metric == "cos":
        d = 1.0 - jnp.sum(rows * Qf, axis=-1)
    elif metric == "dot":
        d = -jnp.sum(rows * Qf, axis=-1)
    else:
        raise ValueError(metric)
    return jnp.where(ids >= 0, d, jnp.inf)


def csr_segment_sum(messages: jax.Array, dst_sorted: jax.Array,
                    n: int) -> jax.Array:
    """out[v] = sum of messages whose (sorted, padded=-1) destination is v."""
    safe = jnp.where(dst_sorted >= 0, dst_sorted, n)
    contrib = jnp.where((dst_sorted >= 0)[:, None], messages, 0)
    return jax.ops.segment_sum(contrib.astype(jnp.float32), safe,
                               num_segments=n + 1)[:n]


def second_hop_eligible(sel: jax.Array, visited: jax.Array,
                        ids: jax.Array) -> jax.Array:
    """bool[B, L]: ``ids >= 0 & bit(sel[b], ids) & ~bit(visited[b], ids)``,
    as the batched engine's second hop composed it from two lane-wise bit
    tests (one single-word gather per id and bitset)."""
    return ((ids >= 0) & bitset.test_batch(sel, ids)
            & ~bitset.test_batch(visited, ids))
