"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs ref oracle."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bitset
from repro.kernels import ref
from repro.kernels.bit_test import second_hop_eligible_pallas
from repro.kernels.distance_matrix import distance_matrix_pallas
from repro.kernels.gather_distance import (
    gather_distance_batch_pallas, gather_distance_pallas,
    quantized_gather_distance_batch_pallas, quantized_gather_distance_pallas)
from repro.kernels.quantized import quantized_distance_pallas
from repro.kernels.segment_sum import (PAD_SENTINEL, csr_segment_sum_pallas,
                                       plan_tiles)

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("metric", ["l2", "cos", "dot"])
@pytest.mark.parametrize("b,n,d,bq,bn,bd", [
    (8, 128, 128, 8, 128, 128),
    (16, 256, 256, 16, 128, 128),
    (32, 384, 128, 8, 128, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_distance_matrix_sweep(metric, b, n, d, bq, bn, bd, dtype):
    Q = jnp.asarray(RNG.normal(size=(b, d)), dtype)
    X = jnp.asarray(RNG.normal(size=(n, d)), dtype)
    got = distance_matrix_pallas(Q, X, metric, bq=bq, bn=bn, bd=bd,
                                 interpret=True)
    exp = ref.distance_matrix(Q, X, metric)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("metric", ["l2", "cos", "dot"])
@pytest.mark.parametrize("n,d,k", [(64, 128, 7), (256, 256, 33), (100, 128, 1)])
def test_gather_distance_sweep(metric, n, d, k):
    q = jnp.asarray(RNG.normal(size=(d,)), jnp.float32)
    X = jnp.asarray(RNG.normal(size=(n, d)), jnp.float32)
    ids = jnp.asarray(RNG.integers(-1, n, size=k), jnp.int32)
    got = gather_distance_pallas(q, X, ids, metric, interpret=True)
    exp = ref.gather_distance(q, X, ids, metric)
    g, e = np.asarray(got), np.asarray(exp)
    np.testing.assert_array_equal(np.isinf(g), np.isinf(e))
    fin = np.isfinite(e)
    np.testing.assert_allclose(g[fin], e[fin], rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("metric", ["l2", "cos", "dot"])
@pytest.mark.parametrize("b,n,d,k", [(4, 64, 128, 7), (8, 128, 128, 16),
                                     (1, 100, 256, 5)])
def test_gather_distance_batch_sweep(metric, b, n, d, k):
    """One pallas_call grid serves all B id lists (incl. -1 padded lanes,
    the engine's retired-query masking contract)."""
    Q = jnp.asarray(RNG.normal(size=(b, d)), jnp.float32)
    X = jnp.asarray(RNG.normal(size=(n, d)), jnp.float32)
    ids = jnp.asarray(RNG.integers(-1, n, size=(b, k)), jnp.int32)
    ids = ids.at[0].set(-1)                     # a fully-retired lane
    got = gather_distance_batch_pallas(Q, X, ids, metric, interpret=True)
    exp = ref.gather_distance_batch(Q, X, ids, metric)
    g, e = np.asarray(got), np.asarray(exp)
    np.testing.assert_array_equal(np.isinf(g), np.isinf(e))
    fin = np.isfinite(e)
    np.testing.assert_allclose(g[fin], e[fin], rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("metric", ["l2", "cos", "dot"])
@pytest.mark.parametrize("b,n,d", [(8, 128, 128), (16, 256, 256)])
def test_quantized_distance_sweep(metric, b, n, d):
    Q = jnp.asarray(RNG.normal(size=(b, d)), jnp.float32)
    codes = jnp.asarray(RNG.integers(-127, 128, size=(n, d)), jnp.int8)
    scale = jnp.asarray(RNG.random(n) * 0.02 + 1e-3, jnp.float32)
    got = quantized_distance_pallas(Q, codes, scale, metric, bq=8,
                                    interpret=True)
    exp = ref.quantized_distance_matrix(Q, codes, scale, metric)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("metric", ["l2", "cos", "dot"])
@pytest.mark.parametrize("n,d,k", [(64, 128, 7), (200, 256, 17)])
def test_quantized_gather_distance_sweep(metric, n, d, k):
    """Int8 gather+distance kernel vs the pure-jnp dequantizing ref."""
    q = jnp.asarray(RNG.normal(size=(d,)), jnp.float32)
    codes = jnp.asarray(RNG.integers(-127, 128, size=(n, d)), jnp.int8)
    scale = jnp.asarray(RNG.random(n) * 0.02 + 1e-3, jnp.float32)
    ids = jnp.asarray(RNG.integers(-1, n, size=k), jnp.int32)
    got = quantized_gather_distance_pallas(q, codes, scale, ids, metric,
                                           interpret=True)
    exp = ref.quantized_gather_distance(q, codes, scale, ids, metric)
    g, e = np.asarray(got), np.asarray(exp)
    np.testing.assert_array_equal(np.isinf(g), np.isinf(e))
    fin = np.isfinite(e)
    np.testing.assert_allclose(g[fin], e[fin], rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("metric", ["l2", "cos", "dot"])
@pytest.mark.parametrize("b,n,d,k", [(4, 64, 128, 7), (8, 128, 128, 16)])
def test_quantized_gather_distance_batch_sweep(metric, b, n, d, k):
    Q = jnp.asarray(RNG.normal(size=(b, d)), jnp.float32)
    codes = jnp.asarray(RNG.integers(-127, 128, size=(n, d)), jnp.int8)
    scale = jnp.asarray(RNG.random(n) * 0.02 + 1e-3, jnp.float32)
    ids = jnp.asarray(RNG.integers(-1, n, size=(b, k)), jnp.int32)
    ids = ids.at[0].set(-1)                     # a fully-retired lane
    got = quantized_gather_distance_batch_pallas(Q, codes, scale, ids,
                                                 metric, interpret=True)
    exp = ref.quantized_gather_distance_batch(Q, codes, scale, ids, metric)
    g, e = np.asarray(got), np.asarray(exp)
    np.testing.assert_array_equal(np.isinf(g), np.isinf(e))
    fin = np.isfinite(e)
    np.testing.assert_allclose(g[fin], e[fin], rtol=1e-4, atol=1e-4)


def test_quantized_gather_matches_dequantize_then_gather():
    """The per-row dequantizing gather is bitwise what dequantize-the-
    store-then-gather computes (gather of a product == product of the
    gathers), so the quantized-resident engine's distances are exactly
    the dequantized engine's distances."""
    n, d, k = 90, 32, 21
    codes = jnp.asarray(RNG.integers(-127, 128, size=(n, d)), jnp.int8)
    scale = jnp.asarray(RNG.random(n) * 0.02 + 1e-3, jnp.float32)
    q = jnp.asarray(RNG.normal(size=(d,)), jnp.float32)
    ids = jnp.asarray(RNG.integers(-1, n, size=k), jnp.int32)
    full = codes.astype(jnp.float32) * scale[:, None]   # the [n, d] buffer
    exp = ref.gather_distance(q, full, ids, "l2")       # ...we never build
    got = ref.quantized_gather_distance(q, codes, scale, ids, "l2")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))


@pytest.mark.parametrize("metric", ["l2", "cos", "dot"])
@pytest.mark.parametrize("b,n,d", [
    (5, 130, 61),      # every axis off the 128 tile
    (3, 127, 32),      # n one short of a tile
    (9, 200, 100),
])
def test_quantized_distance_matrix_padding(monkeypatch, metric, b, n, d):
    """ops.quantized_distance_matrix at non-multiple-of-128 b/n/d: the
    wrapper zero-pads codes AND scales, so padded rows carry scale == 0
    (a legal store row: an all-zero vector quantizes to scale 0). Real
    rows must come back exactly as the ref computes them."""
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    from repro.kernels import ops
    Q = jnp.asarray(RNG.normal(size=(b, d)), jnp.float32)
    codes = jnp.asarray(RNG.integers(-127, 128, size=(n, d)), jnp.int8)
    scale = jnp.asarray(RNG.random(n) * 0.02 + 1e-3, jnp.float32)
    got = ops.quantized_distance_matrix(Q, codes, scale, metric)
    assert got.shape == (b, n)
    exp = ref.quantized_distance_matrix(Q, codes, scale, metric)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               rtol=1e-3, atol=1e-3)


def test_quantized_distance_matrix_zero_scale_rows(monkeypatch):
    """Zero-scale rows INSIDE the store (all-zero vectors) under l2:
    their distance is ||q||^2, not inf/nan, both in kernel and ref."""
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    from repro.kernels import ops
    b, n, d = 4, 70, 48
    Q = jnp.asarray(RNG.normal(size=(b, d)), jnp.float32)
    codes = jnp.asarray(RNG.integers(-127, 128, size=(n, d)), jnp.int8)
    scale = jnp.asarray(RNG.random(n) * 0.02 + 1e-3, jnp.float32)
    scale = scale.at[::7].set(0.0)
    got = np.asarray(ops.quantized_distance_matrix(Q, codes, scale, "l2"))
    exp = np.asarray(ref.quantized_distance_matrix(Q, codes, scale, "l2"))
    np.testing.assert_allclose(got, exp, rtol=1e-3, atol=1e-3)
    qn = np.sum(np.asarray(Q) ** 2, axis=1)
    np.testing.assert_allclose(got[:, ::7],
                               np.broadcast_to(qn[:, None],
                                               got[:, ::7].shape),
                               rtol=1e-3, atol=1e-3)
    assert np.isfinite(got).all()


def test_quantized_gather_ops_pad_odd_shapes(monkeypatch):
    """The ops wrappers zero-pad d to the lane multiple; padded dims
    contribute 0 under every metric, so odd-d results match the ref."""
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    from repro.kernels import ops
    n, d, k, b = 80, 61, 13, 5
    codes = jnp.asarray(RNG.integers(-127, 128, size=(n, d)), jnp.int8)
    scale = jnp.asarray(RNG.random(n) * 0.02 + 1e-3, jnp.float32)
    for metric in ("l2", "cos", "dot"):
        q = jnp.asarray(RNG.normal(size=(d,)), jnp.float32)
        ids = jnp.asarray(RNG.integers(-1, n, size=k), jnp.int32)
        got = ops.quantized_gather_distance(q, codes, scale, ids, metric)
        exp = ref.quantized_gather_distance(q, codes, scale, ids, metric)
        g, e = np.asarray(got), np.asarray(exp)
        fin = np.isfinite(e)
        np.testing.assert_array_equal(np.isinf(g), np.isinf(e))
        np.testing.assert_allclose(g[fin], e[fin], rtol=1e-4, atol=1e-4)
        Q = jnp.asarray(RNG.normal(size=(b, d)), jnp.float32)
        idsb = jnp.asarray(RNG.integers(-1, n, size=(b, k)), jnp.int32)
        gotb = ops.quantized_gather_distance_batch(Q, codes, scale, idsb,
                                                   metric)
        expb = ref.quantized_gather_distance_batch(Q, codes, scale, idsb,
                                                   metric)
        gb, eb = np.asarray(gotb), np.asarray(expb)
        finb = np.isfinite(eb)
        np.testing.assert_array_equal(np.isinf(gb), np.isinf(eb))
        np.testing.assert_allclose(gb[finb], eb[finb], rtol=1e-4, atol=1e-4)


def _bits(shape, fill):
    if fill == "random":
        return RNG.integers(0, 2**32, size=shape, dtype=np.uint64).astype(
            np.uint32)
    return np.full(shape, 0xFFFFFFFF if fill == "ones" else 0, np.uint32)


@pytest.mark.parametrize("case,n,b,l", [
    ("random", 65_536, 3, 4096),      # the cells' W: 16 rows, unrolled
    ("random", 163_840, 2, 512),      # 40 rows: the rolled row loop
    ("random", 5_000, 4, 300),        # W 157 and L 300, both padded
    ("edges", 40_000, 2, 256),        # word and row boundaries, n - 1
    ("fills", 9_000, 5, 256),         # all-zero and all-one lanes
])
def test_second_hop_eligible_sweep(case, n, b, l):
    """The kernel is bitwise the two bit tests it replaces."""
    w = bitset.n_words(n)
    ids = RNG.integers(0, n, size=(b, l)).astype(np.int32)
    ids[RNG.random((b, l)) < 0.2] = -1
    sel, vis = _bits((b, w), "random"), _bits((b, w), "random")
    if case == "edges":
        edge = np.array([0, 31, 32, 33, 4095, 4096, 4097, 8191, 8192,
                         n - 32, n - 2, n - 1, -1], np.int32)
        ids[:, :edge.size] = edge
    if case == "fills":
        for lane, (fs, fv) in enumerate([("zeros", "random"),
                                         ("ones", "zeros"),
                                         ("ones", "ones"),
                                         ("ones", "random"),
                                         ("random", "zeros")]):
            sel[lane], vis[lane] = _bits(w, fs), _bits(w, fv)
    sel, vis, ids = jnp.asarray(sel), jnp.asarray(vis), jnp.asarray(ids)
    got = second_hop_eligible_pallas(sel, vis, ids, interpret=True)
    exp = ref.second_hop_eligible(sel, vis, ids)
    assert got.dtype == exp.dtype and got.shape == exp.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))
    if case == "fills":
        assert not np.asarray(got)[0].any() and not np.asarray(got)[2].any()
        np.testing.assert_array_equal(np.asarray(got)[1],
                                      np.asarray(ids)[1] >= 0)


@pytest.mark.parametrize("force,over", [("1", 0), ("1", 1), ("0", 0)])
def test_second_hop_eligible_takes_kernel_up_to_the_row_constant(
        monkeypatch, force, over):
    """The kernel serves bitsets of up to ``BIT_TEST_MAX_ROWS`` rows of
    128 words; a wider bitset, or a host without the TPU (and no
    ``REPRO_FORCE_PALLAS``), takes the XLA bit tests."""
    monkeypatch.setenv("REPRO_FORCE_PALLAS", force)
    from repro.kernels import ops
    calls = []

    def spy(sel, vis, ids, interpret):
        calls.append(sel.shape)
        return ref.second_hop_eligible(sel, vis, ids)

    monkeypatch.setattr(ops, "second_hop_eligible_pallas", spy)
    w = (ops.BIT_TEST_MAX_ROWS + over) * 128
    sel = jnp.asarray(_bits((2, w), "random"))
    vis = jnp.asarray(_bits((2, w), "random"))
    ids = jnp.asarray(RNG.integers(-1, 32 * w, size=(2, 256)), jnp.int32)
    got = ops.second_hop_eligible(sel, vis, ids)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(
        ref.second_hop_eligible(sel, vis, ids)))
    assert calls == ([] if over or force == "0" else [(2, w)])


@pytest.mark.parametrize("e,d,n,bn,be", [
    (512, 64, 100, 128, 256),
    (1024, 128, 300, 128, 256),
    (256, 32, 1000, 128, 256),   # many empty blocks
])
def test_segment_sum_sweep(e, d, n, bn, be):
    dst = np.sort(RNG.integers(0, n, size=e)).astype(np.int32)
    msgs = jnp.asarray(RNG.normal(size=(e, d)), jnp.float32)
    first, t_max = plan_tiles(dst, n, bn, be, e)
    got = csr_segment_sum_pallas(msgs, jnp.asarray(dst), jnp.asarray(first),
                                 n, bn=bn, be=be, t_max=t_max, interpret=True)
    exp = ref.csr_segment_sum(msgs, jnp.asarray(dst), n)
    np.testing.assert_allclose(np.asarray(got)[:n], np.asarray(exp),
                               rtol=1e-5, atol=1e-5)


def test_segment_sum_with_sentinel_padding():
    n, e, d = 50, 256, 16
    dst = np.sort(RNG.integers(0, n, size=e - 20)).astype(np.int32)
    dst = np.concatenate([dst, np.full(20, PAD_SENTINEL, np.int32)])
    msgs = jnp.asarray(RNG.normal(size=(e, d)), jnp.float32)
    first, t_max = plan_tiles(dst, n, 128, 256, e)
    got = csr_segment_sum_pallas(msgs, jnp.asarray(dst), jnp.asarray(first),
                                 n, t_max=t_max, interpret=True)
    exp = ref.csr_segment_sum(msgs[:-20], jnp.asarray(dst[:-20]), n)
    np.testing.assert_allclose(np.asarray(got)[:n], np.asarray(exp),
                               rtol=1e-5, atol=1e-5)


def test_ops_wrappers_pad_odd_shapes(monkeypatch):
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    from repro.kernels import ops
    Q = jnp.asarray(RNG.normal(size=(5, 61)), jnp.float32)
    X = jnp.asarray(RNG.normal(size=(77, 61)), jnp.float32)
    got = ops.distance_matrix(Q, X, "l2")
    exp = ref.distance_matrix(Q, X, "l2")
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               rtol=1e-4, atol=1e-4)
