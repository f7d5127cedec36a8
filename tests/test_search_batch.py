"""Batched-frontier engine: exact equivalence with per-query search.

The engine's contract is *lane-for-lane identity*: for any semimask and
heuristic, lane b of ``search_many`` evolves through exactly the same
beam states as ``search`` on query b alone, so ids, dists, AND the dc
stats must match exactly (not approximately)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import bitset
from repro.core.search import search, search_batch
from repro.core.search_batch import search_many

HEURISTICS = ["onehop_s", "directed", "blind", "adaptive_g",
              "adaptive_local", "onehop_a"]


def _params(index, k=10, efs=40, heuristic="adaptive_local"):
    return index._params(k, efs, heuristic)


def _sel_and_sigma(index, sigma, seed=3):
    if sigma >= 1.0:
        sel = index.full_semimask()
    else:
        rng = np.random.default_rng(seed)
        sel = bitset.pack(jnp.asarray(rng.random(index.graph.n) < sigma))
    return sel, float(bitset.count(sel)) / index.graph.n


@pytest.mark.parametrize("sigma", [1.0, 0.5, 0.15, 0.03])
def test_batched_matches_single_exactly(index, queries, sigma):
    Q = jnp.asarray(queries[:6])
    sel, sg = _sel_and_sigma(index, sigma)
    for h in HEURISTICS:
        params = _params(index, heuristic=h)
        batched = search_many(index.graph, Q, sel, params, sigma_g=sg)
        singles = [search(index.graph, Q[i], sel, params, sigma_g=sg)
                   for i in range(Q.shape[0])]
        np.testing.assert_array_equal(
            np.asarray(batched.ids),
            np.stack([np.asarray(r.ids) for r in singles]),
            err_msg=f"ids diverge for {h} at sigma={sigma}")
        np.testing.assert_array_equal(
            np.asarray(batched.dists),
            np.stack([np.asarray(r.dists) for r in singles]),
            err_msg=f"dists diverge for {h} at sigma={sigma}")


def test_batched_stats_match_single(index, queries):
    """Per-lane stats (iters, t_dc, s_dc, upper_dc, picks) are the
    single-query stats: converged lanes stop paying distance
    computations while the batch finishes."""
    Q = jnp.asarray(queries[:6])
    sel, sg = _sel_and_sigma(index, 0.2)
    params = _params(index)
    batched = search_many(index.graph, Q, sel, params, sigma_g=sg)
    singles = [search(index.graph, Q[i], sel, params, sigma_g=sg)
               for i in range(Q.shape[0])]
    for field in ("iters", "t_dc", "s_dc", "upper_dc", "picks"):
        np.testing.assert_array_equal(
            np.asarray(getattr(batched.stats, field)),
            np.stack([np.asarray(getattr(r.stats, field)) for r in singles]),
            err_msg=f"stats.{field} diverges")


def test_batched_matches_vmap_oracle(index, queries):
    Q = jnp.asarray(queries[:4])
    sel, sg = _sel_and_sigma(index, 0.3)
    params = _params(index)
    a = search_many(index.graph, Q, sel, params, sigma_g=sg)
    b = search_batch(index.graph, Q, sel, params, sigma_g=sg)
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
    np.testing.assert_array_equal(np.asarray(a.dists), np.asarray(b.dists))


def test_batched_empty_and_full_masks(index, queries):
    Q = jnp.asarray(queries[:3])
    empty = bitset.full_mask(index.graph.n, value=False)
    r = search_many(index.graph, Q, empty, _params(index, k=5), sigma_g=0.0)
    assert (np.asarray(r.ids) == -1).all()
    full = index.full_semimask()
    r = search_many(index.graph, Q, full, _params(index, k=5), sigma_g=1.0)
    assert (np.asarray(r.ids) >= 0).all()


def test_search_results_contain_no_duplicate_ids(index, queries):
    """Property: neither engine may return the same id twice in one
    result list (the visited bitset + beam merge must dedupe)."""
    for sigma in (1.0, 0.4, 0.08):
        sel, sg = _sel_and_sigma(index, sigma, seed=11)
        params = _params(index, k=20, efs=60)
        Q = jnp.asarray(queries[:6])
        batched = search_many(index.graph, Q, sel, params, sigma_g=sg)
        for row in np.asarray(batched.ids):
            real = row[row >= 0]
            assert len(set(real)) == len(real), f"dup ids at sigma={sigma}"
        single = search(index.graph, Q[0], sel, params, sigma_g=sg)
        real = np.asarray(single.ids)
        real = real[real >= 0]
        assert len(set(real)) == len(real)


def test_navix_search_many_engines_agree(index, queries):
    mask = np.random.default_rng(5).random(index.graph.n) < 0.35
    a = index.search_many(queries[:5], k=8, efs=40, semimask=mask,
                          engine="batched")
    b = index.search_many(queries[:5], k=8, efs=40, semimask=mask,
                          engine="vmap")
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
    np.testing.assert_array_equal(np.asarray(a.dists), np.asarray(b.dists))
    with pytest.raises(ValueError, match="engine"):
        index.search_many(queries[:2], k=4, engine="nope")


@pytest.mark.parametrize("heuristic", ["adaptive_local", "blind",
                                       "directed"])
def test_bit_test_kernel_leaves_the_search_unchanged(index, queries,
                                                     monkeypatch, heuristic):
    """With the Pallas kernels forced (interpret mode), the second hop's
    eligibility kernel gives the ids, dists and stats of the two XLA bit
    tests, for per-lane semimasks whose selectivities send lanes into the
    directed and the blind branch."""
    from repro.kernels import ops
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    rng = np.random.default_rng(17)
    sigmas = np.array([0.6, 0.2, 0.05, 0.02])
    sel = bitset.pack(jnp.asarray(
        rng.random((sigmas.size, index.graph.n)) < sigmas[:, None]))
    Q = jnp.asarray(queries[:sigmas.size])
    params = _params(index, heuristic=heuristic)
    calls = []
    kernel = ops.second_hop_eligible_pallas

    def spy(*args, **kw):
        calls.append(1)
        return kernel(*args, **kw)

    runs = []
    for max_rows in (ops.BIT_TEST_MAX_ROWS, 0):
        monkeypatch.setattr(ops, "BIT_TEST_MAX_ROWS", max_rows)
        monkeypatch.setattr(ops, "second_hop_eligible_pallas", spy)
        jax.clear_caches()        # the dispatch is baked in at trace time
        runs.append(search_many(index.graph, Q, sel, params))
    jax.clear_caches()
    assert len(calls) == 1        # the kernel ran in the first run only
    kern, xla = runs
    np.testing.assert_array_equal(np.asarray(kern.ids), np.asarray(xla.ids))
    np.testing.assert_array_equal(np.asarray(kern.dists),
                                  np.asarray(xla.dists))
    for field in ("iters", "t_dc", "s_dc", "upper_dc", "picks"):
        np.testing.assert_array_equal(np.asarray(getattr(kern.stats, field)),
                                      np.asarray(getattr(xla.stats, field)))
    picks = np.asarray(kern.stats.picks).sum(axis=0)
    if heuristic == "adaptive_local":
        assert picks[1] > 0 and picks[2] > 0, picks
