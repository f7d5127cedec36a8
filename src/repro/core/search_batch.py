"""Batched-frontier beam search: the native multi-query engine.

``jax.vmap(search)`` (kept in ``repro.core.search`` as the reference
oracle) is a correct throughput path but a wasteful one: vmap turns the
per-iteration ``lax.switch`` over the three expansion heuristics into a
select over the *branch union*, so every lane pays onehop + directed +
blind work every iteration -- exactly the per-predicate overhead the
paper's adaptive design avoids -- and the whole batch re-traces the
single-query program per lane.

This module is a dedicated engine that runs one ``lax.while_loop`` over a
``[B, efs]`` beam state:

* **per-query live mask** -- each lane carries the single-query
  convergence predicate; a converged lane's state is frozen and its
  candidate ids are masked to ``-1`` *before* the shared gathers, so it
  stops contributing distance computations (and dc accounting) while the
  rest of the batch finishes;
* **per-lane semimasks** -- ``sel_bits`` may be one shared packed bitset
  ``[W]`` or a per-lane ``[B, W]`` stack, so requests carrying *different*
  selection subqueries (each at its own selectivity) fuse into one device
  batch -- the paper's per-query ad-hoc S, batched. All selectivity
  machinery is lane-local: candidate masking, the sigma_l estimate, and
  (for adaptive-global) a per-lane ``sigma_g`` vector;
* **masked unified expansion** -- the three heuristics share one
  ``[B, M + K2]`` candidate layout: first-degree candidates are identical
  across branches (selected & unvisited, in neighbor order), so one
  shared ``[B, M]`` gather+distance serves onehop-s distances, blind
  distances, AND directed's ordering pass; branch differences reduce to
  cheap masks (which neighbors get marked visited, which parents seed the
  second hop, what the dc counters charge);
* **per-query adaptive-local branch selection** -- ``sigma_l`` and the
  paper's decision rule evaluate vectorized over lanes *against each
  lane's own S*, so different lanes take different branches in the same
  iteration at no extra cost;
* **data-dependent second-hop skip** -- when no live lane picked a
  two-hop branch this iteration, a ``lax.cond`` skips the entire
  ``[B, M, M]`` second-degree stage (exclusive under jit, something the
  vmap path structurally cannot do).

Lane-for-lane, the state transition is identical to the single-query
``search`` run with that lane's own semimask: the equivalence suite
asserts exactly equal (ids, dists) and stats.

The distance primitive is :func:`batch_gather_dist`, which routes through
``repro.kernels.ops.gather_distance_batch`` -- the batched Pallas
gather+distance kernel on TPU (interpret mode under
``REPRO_FORCE_PALLAS=1``), the XLA reference elsewhere. The second hop's
candidate test (:func:`batch_eligible`) routes the same way, through
``repro.kernels.ops.second_hop_eligible``. Set ``REPRO_ENGINE_GATHER=xla``
to pin the pure-jnp path for both; the choice is baked at trace time, so
set the env var before the first engine call.

The ``engine_*`` stepping API at the bottom decomposes the same loop into
resumable chunks (park / refill / step / finalize) for the serving tier's
continuous-batching scheduler: converged lanes are compacted out and
refilled from the request queue between device calls, LLM-serving style.
"""

from __future__ import annotations

import functools
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import bitset
from repro.core.distances import (gather_rows, gathered_dist_batch,
                                  point_dist)
from repro.core.graph import HnswGraph
from repro.core.heuristics import Heuristic, adaptive_rule
from repro.core.search import (SearchParams, SearchResult, SearchStats,
                               _dedupe_keep_first, search_batch)

class _BatchState(NamedTuple):
    d: jax.Array          # f32[B, efs]
    ids: jax.Array        # i32[B, efs]
    exp: jax.Array        # bool[B, efs]
    sel: jax.Array        # bool[B, efs]
    visited: jax.Array    # u32[B, W]
    it: jax.Array         # i32[B]
    t_dc: jax.Array       # i32[B]
    s_dc: jax.Array       # i32[B]
    picks: jax.Array      # i32[B, 3]


# ---------------------------------------------------------------------------
# distance primitive routing (ROADMAP: batched Pallas path in the engine)
# ---------------------------------------------------------------------------

GATHER_ENV = "REPRO_ENGINE_GATHER"
_GATHER_MODES = ("auto", "ops", "pallas", "xla")


def gather_backend() -> str:
    """The engine's gather+distance backend from ``REPRO_ENGINE_GATHER``:
    "auto"/"ops"/"pallas" route through ``repro.kernels.ops`` (Pallas on
    TPU, interpret-mode kernels under REPRO_FORCE_PALLAS=1, the XLA ref
    otherwise); "xla" pins the pure-jnp ``gathered_dist_batch``."""
    mode = os.environ.get(GATHER_ENV, "auto").lower()
    if mode not in _GATHER_MODES:
        raise ValueError(f"{GATHER_ENV}={mode!r}; valid: {_GATHER_MODES}")
    return mode


def batch_gather_dist(Q: jax.Array, vectors: jax.Array, ids: jax.Array,
                      metric: str) -> jax.Array:
    """The engine's distance primitive: dist(Q[b], vectors[ids[b]]).

    Routed through the kernels dispatch layer so the batched Pallas
    gather+distance kernel serves the engine when available; bitwise
    equal to :func:`repro.core.distances.gathered_dist_batch` on the
    fallback path. Backend choice is baked at trace time.

    ``vectors`` may be an int8-resident store (``QuantizedStore``,
    duck-typed on ``codes``): candidates then dequantize per gathered row
    (the quantized gather kernel on TPU, the jnp reference elsewhere) --
    bitwise what ``dequantize``-then-gather computes, with no ``[n, d]``
    f32 buffer live.
    """
    codes = getattr(vectors, "codes", None)
    if gather_backend() == "xla":
        return gathered_dist_batch(Q, vectors, ids, metric)
    from repro.kernels import ops
    if codes is not None:
        return ops.quantized_gather_distance_batch(Q, codes, vectors.scale,
                                                   ids, metric)
    return ops.gather_distance_batch(Q, vectors, ids, metric)


def batch_eligible(sel2: jax.Array, visited: jax.Array,
                   ids: jax.Array) -> jax.Array:
    """The second hop's candidate test, ``ids >= 0 & bit(sel2[b], ids) &
    ~bit(visited[b], ids)`` -> bool[B, L]: the Pallas kernel of
    ``repro.kernels.ops.second_hop_eligible`` where that layer takes it,
    the two lane-wise bit tests under ``REPRO_ENGINE_GATHER=xla`` (the
    same pin as :func:`batch_gather_dist`)."""
    from repro.kernels import ops, ref
    if gather_backend() == "xla":
        return ref.second_hop_eligible(sel2, visited, ids)
    return ops.second_hop_eligible(sel2, visited, ids)


def _take_first_batch(elig: jax.Array, values: jax.Array, width: int,
                      budget=None) -> jax.Array:
    """Lane-wise first-k compaction: ([B, L], [B, L]) -> int32[B, width].

    Bitwise-identical output to ``vmap(search._take_first)`` (the first
    up-to-``budget`` eligible values per lane, in order, -1 padded) but
    scatter- and sort-free: the j-th taken element of a lane sits at the
    first position whose running take-count reaches j+1, found with a
    vmapped binary search over the cumsum -- both per-lane scatters and
    a [B, L] top_k serialize badly on XLA CPU and each dominated the
    engine's second-degree stage.
    """
    cum = jnp.cumsum(elig.astype(jnp.int32), axis=1)
    if budget is not None:
        limit = jnp.minimum(budget, width)[:, None]
    else:
        limit = width
    # running count of TAKEN elements == eligible count clipped at the
    # take limit (an eligible element past the limit is never taken)
    cum_t = jnp.minimum(cum, limit)
    targets = jnp.arange(1, width + 1)
    idx = jax.vmap(
        lambda c: jnp.searchsorted(c, targets, side="left"))(cum_t)
    out = jnp.take_along_axis(
        values, jnp.minimum(idx, values.shape[1] - 1), axis=1)
    return jnp.where(targets[None, :] <= cum_t[:, -1:], out, -1)


def _frontier_min(st: _BatchState):
    d_un = jnp.where((~st.exp) & (st.ids >= 0), st.d, jnp.inf)
    j = jnp.argmin(d_un, axis=1)
    return j, jnp.take_along_axis(d_un, j[:, None], axis=1)[:, 0]


def _r_max(st: _BatchState, efs):
    """Per-lane result-set radius; ``efs`` is the static int cap or a
    per-lane ``int32[B]`` vector (the ragged-efs path -- each lane's
    radius closes once ITS OWN efs slots are selected)."""
    live = st.sel & (st.ids >= 0) & jnp.isfinite(st.d)
    n_sel = live.sum(axis=1)
    r = jnp.where(live, st.d, -jnp.inf).max(axis=1)
    return jnp.where(n_sel >= efs, r, jnp.inf)


def greedy_upper_batch(graph: HnswGraph, Q: jax.Array, metric: str):
    """Batched greedy walk on G_U with a per-lane improving mask.

    Returns (entry_ids[B], dc[B]); lane-for-lane identical to
    ``search.greedy_upper``.
    """
    upper, upper_ids, vectors = graph.upper, graph.upper_ids, graph.vectors
    bsz = Q.shape[0]
    b_idx = jnp.arange(bsz)

    def cond(c):
        return jnp.any(c[3])

    def body(c):
        pos, d, dc, act = c
        nbr_pos = upper[pos]                               # [B, M_U]
        valid = nbr_pos >= 0
        nbr_ids = jnp.where(valid, upper_ids[jnp.maximum(nbr_pos, 0)], -1)
        with jax.named_scope("navix.gather"):
            nd = batch_gather_dist(Q, vectors,
                                   jnp.where(act[:, None], nbr_ids, -1),
                                   metric)
        jj = jnp.argmin(nd, axis=1)
        best = jnp.take_along_axis(nd, jj[:, None], axis=1)[:, 0]
        upd = act & (best < d)
        return (jnp.where(upd, nbr_pos[b_idx, jj], pos),
                jnp.where(upd, best, d),
                dc + jnp.where(act, valid.sum(axis=1), 0).astype(jnp.int32),
                upd)

    pos0 = jnp.broadcast_to(graph.entry_pos, (bsz,))
    d0 = point_dist(Q, gather_rows(vectors, upper_ids[pos0]), metric)
    init = (pos0, d0, jnp.ones((bsz,), jnp.int32), jnp.ones((bsz,), bool))
    pos, _, dc, _ = lax.while_loop(cond, body, init)
    return upper_ids[pos], dc


# ---------------------------------------------------------------------------
# shared pieces of the lower-level loop (used by both the one-shot
# search_many path and the resumable engine_* stepping API, so the two
# stay in bitwise lockstep)
# ---------------------------------------------------------------------------


def _resolve_branching(sel2: jax.Array, params: SearchParams, sigma_g,
                       n: int, m_l: int, bsz: int):
    """Normalize (semimask, heuristic) to the loop's static/per-lane form.

    Returns ``(sel2, mode, global_branch[B])``: ONEHOP_A becomes ONEHOP_S
    over the full mask; ADAPTIVE_GLOBAL evaluates the paper's rule with a
    scalar or per-lane sigma_g (defaulting to each lane's own |S|/|V|).
    """
    mode = int(params.heuristic)
    if mode == int(Heuristic.ONEHOP_A):
        sel2 = jnp.broadcast_to(bitset.full_mask(n), sel2.shape)
        mode = int(Heuristic.ONEHOP_S)
    if mode == int(Heuristic.ADAPTIVE_GLOBAL):
        if sigma_g is None:
            sigma_g = bitset.count_batch(sel2) / n
        global_branch = adaptive_rule(sigma_g, m_l, params.ub, params.lf)
    else:
        global_branch = jnp.int32(mode if mode <= 2 else 0)
    return sel2, mode, jnp.broadcast_to(global_branch, (bsz,))


def _init_state(graph: HnswGraph, Q: jax.Array, sel2: jax.Array,
                seeds: jax.Array, params: SearchParams) -> _BatchState:
    """Fresh per-lane beams holding only each lane's seed entry point."""
    bsz, efs = Q.shape[0], params.efs
    seed_d = point_dist(Q, gather_rows(graph.vectors, seeds), params.metric)
    pad_d = jnp.full((bsz, efs - 1), jnp.inf, seed_d.dtype)
    return _BatchState(
        d=jnp.concatenate([seed_d[:, None], pad_d], axis=1),
        ids=jnp.concatenate(
            [seeds[:, None], jnp.full((bsz, efs - 1), -1, jnp.int32)], axis=1),
        exp=jnp.zeros((bsz, efs), bool),
        sel=jnp.concatenate(
            [bitset.test_batch(sel2, seeds[:, None])[:, 0:1],
             jnp.zeros((bsz, efs - 1), bool)], axis=1),
        visited=bitset.set_bits_batch(
            jnp.zeros((bsz, bitset.n_words(graph.n)), jnp.uint32),
            seeds[:, None]),
        it=jnp.zeros((bsz,), jnp.int32),
        t_dc=jnp.zeros((bsz,), jnp.int32),
        s_dc=jnp.zeros((bsz,), jnp.int32),
        picks=jnp.zeros((bsz, 3), jnp.int32),
    )


def _loop_fns(graph: HnswGraph, Q: jax.Array, sel2: jax.Array,
              params: SearchParams, mode: int, global_branch: jax.Array,
              efs_lanes=None):
    """Build the (lane_cond, body) closures of the batched lower-level
    loop. ``sel2`` is per-lane ``[B, W]``; ``mode`` is the static resolved
    heuristic; ``global_branch`` the per-lane fallback branch vector.

    ``efs_lanes`` (optional ``int32[B]``) makes the beam RAGGED: after
    every merge, slots at/past each lane's own efs are cleared (d=+inf,
    id=-1, sel=False, exp=True), so a lane admitted at a small efs is
    bit-identical to a lane whose beam was only ever that wide -- the
    convergence radius closes at the lane's own efs and the sorted-merge
    prefix property keeps its first ``efs_lanes[b]`` slots equal to the
    narrow beam's. Lanes at the full ``params.efs`` are untouched (the
    tail mask is empty for them), so a uniform-efs batch is bitwise
    unchanged."""
    efs = params.efs
    metric = params.metric
    m_l = graph.m_l
    k2 = params.two_hop_cap or m_l
    max_iters = params.max_iters or graph.n
    bsz = Q.shape[0]
    b_idx = jnp.arange(bsz)
    vectors, lower = graph.vectors, graph.lower

    dedupe = jax.vmap(_dedupe_keep_first)

    efs_eff = efs if efs_lanes is None else efs_lanes

    # the named scopes (navix.frontier / visited / semimask / gather /
    # merge) tag the step's ops for a device profile; they change op
    # metadata only, never the compiled program. lane_cond stays
    # unscoped: it is also the loop's condition, where a scope renames
    # instructions (some names derive from the name stack)
    def lane_cond(st: _BatchState):
        _, d_min = _frontier_min(st)
        keep = (d_min < jnp.inf) & (d_min <= _r_max(st, efs_eff))
        return keep & (st.it < max_iters)

    def body(st: _BatchState) -> _BatchState:
        with jax.named_scope("navix.frontier"):
            live = lane_cond(st)                           # [B]
            j, _ = _frontier_min(st)
            c_min = jnp.take_along_axis(st.ids, j[:, None], axis=1)[:, 0]
            # retired lanes contribute no candidates to the shared gathers
            nbrs = jnp.where(live[:, None],
                             lower[jnp.maximum(c_min, 0)], -1)  # [B, M_L]

            if mode == int(Heuristic.ADAPTIVE_LOCAL):
                deg = (nbrs >= 0).sum(axis=1)
                # each lane estimates sigma_l against its OWN selected set
                sigma_l = bitset.count_members_batch(sel2, nbrs) / \
                    jnp.maximum(deg, 1)
                branch = adaptive_rule(sigma_l, m_l, params.ub, params.lf)
            else:
                branch = global_branch
            is_dir = branch == int(Heuristic.DIRECTED)

        # shared first-degree pass: one gather serves every branch
        with jax.named_scope("navix.visited"):
            visited_t = bitset.test_batch(st.visited, nbrs)        # [B, M]
        new1 = (nbrs >= 0) & ~visited_t
        with jax.named_scope("navix.semimask"):
            sel1 = bitset.test_batch(sel2, nbrs) & ~visited_t  # == cand1
        cand1 = jnp.where(sel1, nbrs, -1)
        with jax.named_scope("navix.gather"):
            d_all = batch_gather_dist(Q, vectors, nbrs, metric)
        d1 = jnp.where(sel1, d_all, jnp.inf)
        n1 = sel1.sum(axis=1)
        # directed marks every neighbor it ordered; the others only the
        # selected candidates they actually inserted
        mark1 = jnp.where(is_dir[:, None], new1, sel1)
        with jax.named_scope("navix.visited"):
            visited1 = bitset.set_bits_batch(st.visited,
                                             jnp.where(mark1, nbrs, -1))

        # second-degree parents: distance-ordered for directed, scan order
        # for blind, none for onehop-s / retired lanes
        order1 = jnp.argsort(jnp.where(nbrs >= 0, d_all, jnp.inf), axis=1)
        parents = jnp.where(is_dir[:, None],
                            jnp.take_along_axis(nbrs, order1, axis=1), nbrs)
        two_hop = live & (branch != int(Heuristic.ONEHOP_S))
        parents = jnp.where(two_hop[:, None], parents, -1)
        budget = jnp.where(two_hop, jnp.maximum(k2 - n1, 0), 0)

        def do_second(args):
            visited1, parents, budget = args
            nb2 = lower[jnp.maximum(parents, 0)]           # [B, M, M]
            flat = jnp.where((parents >= 0)[:, :, None], nb2,
                             -1).reshape(bsz, -1)
            # one call tests S and the visited set (one kernel on the
            # TPU), so both count under one scope
            with jax.named_scope("navix.semimask"):
                elig = batch_eligible(sel2, visited1, flat)
            cand = _take_first_batch(elig, flat, 2 * k2)   # over-take ...
            cand = dedupe(cand)                            # ... dedupe ...
            cand2 = _take_first_batch(cand >= 0, cand, k2,
                                      budget=budget)       # ... then cap
            with jax.named_scope("navix.gather"):
                d2 = batch_gather_dist(Q, vectors, cand2, metric)
            with jax.named_scope("navix.visited"):
                visited2 = bitset.set_bits_batch(visited1, cand2)
            return cand2, d2, visited2, (cand2 >= 0).sum(axis=1)

        def skip_second(args):
            visited1, _, _ = args
            return (jnp.full((bsz, k2), -1, jnp.int32),
                    jnp.full((bsz, k2), jnp.inf, jnp.float32),
                    visited1,
                    jnp.zeros((bsz,), jnp.int32))

        cand2, d2, visited2, n2 = lax.cond(
            jnp.any(two_hop), do_second, skip_second,
            (visited1, parents, budget))

        t_add = jnp.where(is_dir, new1.sum(axis=1) + n2, n1 + n2)
        s_add = n1 + n2

        # retire the expanded slot and merge candidates (per lane);
        # one-hot mask arithmetic instead of batched .at[] scatters --
        # XLA CPU serializes per-lane scatters, these are the hot path
        with jax.named_scope("navix.merge"):
            slot = jnp.arange(efs)[None, :] == j[:, None]  # [B, efs]
            exp = st.exp | slot
            sel_j = jnp.take_along_axis(st.sel, j[:, None], axis=1)
            d = jnp.where(slot & ~sel_j, jnp.inf, st.d)

            cand_ids = jnp.concatenate([cand1, cand2], axis=1)
            cand_d = jnp.concatenate([d1, d2], axis=1)
            all_d = jnp.concatenate(
                [d, jnp.where(cand_ids >= 0, cand_d, jnp.inf)], axis=1)
            all_id = jnp.concatenate([st.ids, cand_ids], axis=1)
            all_exp = jnp.concatenate(
                [exp, jnp.zeros_like(cand_ids, dtype=bool)], axis=1)
            all_sel = jnp.concatenate([st.sel, cand_ids >= 0], axis=1)

            # navilint: op-ok the single fused beam-merge top_k PR 3 kept
            neg, order2 = lax.top_k(-all_d, efs)
            new_d = -neg
            new_id = jnp.take_along_axis(all_id, order2, axis=1)
            new_exp = jnp.take_along_axis(all_exp, order2, axis=1)
            new_sel = jnp.take_along_axis(all_sel, order2, axis=1)
            if efs_lanes is not None:
                # ragged beam tail: the merge is sorted ascending, so its
                # first efs_lanes[b] slots equal the top-efs_lanes[b]
                # merge of an efs_lanes[b]-wide beam; clearing the tail
                # keeps the induction exact and stops small-efs lanes
                # paying the full-cap radius (their r_max closes at their
                # own efs)
                tail = jnp.arange(efs)[None, :] >= efs_lanes[:, None]
                new_d = jnp.where(tail, jnp.inf, new_d)
                new_id = jnp.where(tail, -1, new_id)
                new_exp = new_exp | tail
                new_sel = new_sel & ~tail
        keep = live[:, None]
        return _BatchState(
            d=jnp.where(keep, new_d, st.d),
            ids=jnp.where(keep, new_id, st.ids),
            exp=jnp.where(keep, new_exp, st.exp),
            sel=jnp.where(keep, new_sel, st.sel),
            visited=jnp.where(keep, visited2, st.visited),
            it=st.it + live.astype(jnp.int32),
            t_dc=st.t_dc + jnp.where(live, t_add, 0).astype(jnp.int32),
            s_dc=st.s_dc + jnp.where(live, s_add, 0).astype(jnp.int32),
            picks=st.picks + ((jnp.arange(3)[None, :] == branch[:, None])
                              & live[:, None]).astype(jnp.int32),
        )

    return lane_cond, body


def _extract_results(st: _BatchState, efs: int):
    """Selected-slot top-k of the final beams: (dists[B, efs], ids[B, efs],
    per-lane stats with upper_dc left zero for the caller to fill)."""
    bsz = st.it.shape[0]
    with jax.named_scope("navix.merge"):
        res_d = jnp.where(st.sel & (st.ids >= 0), st.d, jnp.inf)
        # navilint: op-ok one top_k per search at extraction, not per step
        neg, order = lax.top_k(-res_d, efs)
        out_d = -neg
        out_id = jnp.where(jnp.isfinite(out_d),
                           jnp.take_along_axis(st.ids, order, axis=1), -1)
    stats = SearchStats(iters=st.it, t_dc=st.t_dc, s_dc=st.s_dc,
                        upper_dc=jnp.zeros((bsz,), jnp.int32),
                        picks=st.picks)
    return out_d, out_id, stats


def beam_search_lower_batch(
    graph: HnswGraph,
    Q: jax.Array,
    sel_bits: jax.Array,
    seeds: jax.Array,
    params: SearchParams,
    sigma_g=None,
    efs_lanes=None,
) -> tuple[jax.Array, jax.Array, SearchStats]:
    """Search G_L for B queries at once. Returns the full beams
    (dists[B, efs], ids[B, efs]) ascending, plus per-lane stats.

    ``seeds``: int32[B] entry node ids (one per lane).
    ``sel_bits``: one shared semimask ``[W]`` (the group's selection
    subquery) or a per-lane stack ``[B, W]`` (each lane its own S).
    ``sigma_g``: scalar or per-lane ``[B]`` (ADAPTIVE_GLOBAL only).
    ``efs_lanes``: optional per-lane ``int32[B]`` efs (ragged beams; each
    lane is bit-identical to a search at its own efs <= params.efs).
    """
    bsz = Q.shape[0]
    sel2 = bitset.broadcast_lanes(sel_bits, bsz)
    sel2, mode, global_branch = _resolve_branching(
        sel2, params, sigma_g, graph.n, graph.m_l, bsz)
    lane_cond, body = _loop_fns(graph, Q, sel2, params, mode, global_branch,
                                efs_lanes=efs_lanes)

    st = _init_state(graph, Q, sel2, seeds, params)
    st = lax.while_loop(lambda s: jnp.any(lane_cond(s)), body, st)
    return _extract_results(st, params.efs)


def search_lanes(graph: HnswGraph, Q: jax.Array, sel_bits: jax.Array,
                 params: SearchParams, sigma_g=None,
                 efs_lanes=None) -> SearchResult:
    """Unjitted body of :func:`search_many` -- the full 2-level filtered
    search for a [B, d] query batch. Exposed so callers embedding the
    engine in a larger traced program (``repro.core.distributed`` runs it
    per shard inside ``shard_map``) share one source of truth with the
    jitted entry point."""
    entry, upper_dc = greedy_upper_batch(graph, Q, params.metric)
    beam_d, beam_id, stats = beam_search_lower_batch(
        graph, Q, sel_bits, entry, params, sigma_g=sigma_g,
        efs_lanes=efs_lanes)
    k = params.k
    return SearchResult(
        dists=beam_d[:, :k],
        ids=beam_id[:, :k],
        # +1: the entry vector's own distance at the lower level
        stats=stats._replace(upper_dc=upper_dc.astype(jnp.int32) + 1),
    )


@functools.partial(jax.jit, static_argnames=("params",))
def search_many(graph: HnswGraph, Q: jax.Array, sel_bits: jax.Array,
                params: SearchParams, sigma_g=None,
                efs_lanes=None) -> SearchResult:
    """Full 2-level filtered search for a [B, d] query batch.

    Lane-for-lane equivalent to ``search.search`` per query with that
    lane's own semimask (same ids, dists, and stats), at a fraction of
    the vmap path's per-iteration cost. ``sel_bits`` is ``[W]`` (shared)
    or ``[B, W]`` (per-lane, the mixed-plan serving path); ``efs_lanes``
    (optional ``int32[B]``) runs each lane at its own efs.
    """
    return search_lanes(graph, Q, sel_bits, params, sigma_g=sigma_g,
                        efs_lanes=efs_lanes)


# ---------------------------------------------------------------------------
# resumable stepping API -- the continuous-batching scheduler's device side
# ---------------------------------------------------------------------------
# The serving tier holds a fixed [B, efs] beam state across device calls:
#   parked_state   -> all lanes empty (converged-by-construction)
#   engine_refill  -> reset a subset of lanes to fresh beams for new
#                     requests (their own query + their own semimask)
#   engine_steps   -> run at most n_steps loop iterations; returns the
#                     per-lane live mask so the host can spot convergence
#   engine_finalize-> extract per-lane (dists, ids, stats) at any point
# A lane stepped to convergence through any chunking of engine_steps calls
# passes through exactly the `search_many` state sequence (converged and
# parked lanes are frozen by the body's live mask), so per-lane results
# stay bitwise-identical to the single-query path.


def parked_state(n: int, bsz: int, params: SearchParams) -> _BatchState:
    """An all-parked batch state: every lane is empty and converged."""
    efs = params.efs
    return _BatchState(
        d=jnp.full((bsz, efs), jnp.inf, jnp.float32),
        ids=jnp.full((bsz, efs), -1, jnp.int32),
        exp=jnp.ones((bsz, efs), bool),
        sel=jnp.zeros((bsz, efs), bool),
        visited=jnp.zeros((bsz, bitset.n_words(n)), jnp.uint32),
        it=jnp.zeros((bsz,), jnp.int32),
        t_dc=jnp.zeros((bsz,), jnp.int32),
        s_dc=jnp.zeros((bsz,), jnp.int32),
        picks=jnp.zeros((bsz, 3), jnp.int32),
    )


def refill_lanes(graph: HnswGraph, Q: jax.Array, sel_bits: jax.Array,
                 st: _BatchState, upper_dc: jax.Array, refill: jax.Array,
                 params: SearchParams) -> tuple[_BatchState, jax.Array]:
    """Unjitted body of :func:`engine_refill` (shard_map-embeddable)."""
    bsz = Q.shape[0]
    sel2 = bitset.broadcast_lanes(sel_bits, bsz)
    sel2, _, _ = _resolve_branching(sel2, params, None, graph.n,
                                    graph.m_l, bsz)
    entry, dc = greedy_upper_batch(graph, Q, params.metric)
    fresh = _init_state(graph, Q, sel2, entry, params)

    def merge(new, old):
        sel_b = refill.reshape((bsz,) + (1,) * (new.ndim - 1))
        return jnp.where(sel_b, new, old)

    merged = jax.tree_util.tree_map(merge, fresh, st)
    return merged, jnp.where(refill, dc.astype(jnp.int32) + 1, upper_dc)


@functools.partial(jax.jit, static_argnames=("params",))
def engine_refill(graph: HnswGraph, Q: jax.Array, sel_bits: jax.Array,
                  st: _BatchState, upper_dc: jax.Array, refill: jax.Array,
                  params: SearchParams) -> tuple[_BatchState, jax.Array]:
    """Reset the lanes flagged in ``refill`` (bool[B]) to fresh beams.

    Refilled lanes run the greedy upper descent for their (new) query and
    start a fresh lower-level beam over their (new) per-lane semimask;
    all other lanes pass through bit-identically. Returns the merged
    state and the updated per-lane ``upper_dc`` accounting.
    """
    return refill_lanes(graph, Q, sel_bits, st, upper_dc, refill, params)


@functools.partial(jax.jit, static_argnames=("params",),
                   donate_argnums=(3, 4))
def engine_refill_overlap(graph: HnswGraph, Q: jax.Array,
                          sel_bits: jax.Array, st: _BatchState,
                          upper_dc: jax.Array, refill: jax.Array,
                          params: SearchParams
                          ) -> tuple[_BatchState, jax.Array]:
    """:func:`engine_refill` with ``st`` and ``upper_dc`` DONATED (the
    serving tier's overlapped path: refill dispatches in place and the
    next step chunk chains onto it without a host sync). The caller must
    replace its state references with the returned ones."""
    return refill_lanes(graph, Q, sel_bits, st, upper_dc, refill, params)


def step_lanes(graph: HnswGraph, Q: jax.Array, sel_bits: jax.Array,
               st: _BatchState, params: SearchParams, n_steps: int,
               sigma_g=None, efs_lanes=None) -> tuple[_BatchState, jax.Array]:
    """Unjitted body of :func:`engine_steps` (shard_map-embeddable)."""
    bsz = Q.shape[0]
    sel2 = bitset.broadcast_lanes(sel_bits, bsz)
    sel2, mode, global_branch = _resolve_branching(
        sel2, params, sigma_g, graph.n, graph.m_l, bsz)
    lane_cond, body = _loop_fns(graph, Q, sel2, params, mode, global_branch,
                                efs_lanes=efs_lanes)

    def cond(c):
        s, i = c
        keep = jnp.any(lane_cond(s))
        return keep & (i < n_steps) if n_steps else keep

    def chunk_body(c):
        s, i = c
        return body(s), i + 1

    st, _ = lax.while_loop(cond, chunk_body, (st, jnp.int32(0)))
    return st, lane_cond(st)


@functools.partial(jax.jit, static_argnames=("params", "n_steps"))
def engine_steps(graph: HnswGraph, Q: jax.Array, sel_bits: jax.Array,
                 st: _BatchState, params: SearchParams, n_steps: int,
                 sigma_g=None, efs_lanes=None) -> tuple[_BatchState, jax.Array]:
    """Advance the batch by at most ``n_steps`` loop iterations
    (``n_steps=0``: unbounded -- run to whole-batch convergence, the
    right call when the request queue is empty and there is nothing to
    refill between chunks).

    Returns ``(state, live[B])``; a lane with ``live == False`` has
    converged (or is parked) and is safe to finalize and refill.
    ``efs_lanes`` (optional ``int32[B]``) steps each lane at its own efs
    (see :func:`_loop_fns`); it must stay constant for a lane between
    refills.
    """
    return step_lanes(graph, Q, sel_bits, st, params, n_steps,
                      sigma_g=sigma_g, efs_lanes=efs_lanes)


@functools.partial(jax.jit, static_argnames=("params", "n_steps"),
                   donate_argnums=(3,))
def engine_steps_overlap(graph: HnswGraph, Q: jax.Array, sel_bits: jax.Array,
                         st: _BatchState, params: SearchParams, n_steps: int,
                         sigma_g=None, efs_lanes=None
                         ) -> tuple[_BatchState, jax.Array]:
    """:func:`engine_steps` with the state buffers DONATED: the input
    ``st`` is consumed (its buffers are reused for the output state), so
    the chunk dispatches without a copy and the host can keep working
    while it runs -- the serving tier's overlapped stepping path
    (:meth:`repro.serving.lanes.LaneBatch.step_async`). The caller must
    drop its reference to the input state: reading it after this call
    raises on a donated buffer."""
    return step_lanes(graph, Q, sel_bits, st, params, n_steps,
                      sigma_g=sigma_g, efs_lanes=efs_lanes)


def evict_lanes(st: _BatchState, upper_dc: jax.Array, evict: jax.Array
                ) -> tuple[_BatchState, jax.Array]:
    """Park the lanes flagged in ``evict`` (bool[B]): their beams become
    empty and converged (ids -1, sel False, d +inf), so they stop
    contributing work in ``engine_steps`` (their ``live`` predicate is
    False -- an un-evicted overdue lane would keep an ``n_steps=0`` call
    spinning forever), finalize to all ``-1`` ids, and are immediately
    refillable. The serving tier uses this for deadline eviction: it
    finalizes first (to salvage a partial beam), then parks the lane.

    Works on both state layouts: flat ``[B, ...]`` leaves and the
    shard-stacked ``[S, B, ...]`` leaves of :class:`ShardedNavix`
    (detected from ``st.it``'s rank -- the lane axis is the last leading
    axis), so one op serves ``engine_evict`` and the sharded
    ``evict_program`` without a ``shard_map`` round-trip: the merge is
    elementwise over lanes and preserves the state's sharding.
    """
    lead = st.it.ndim          # 1 = flat [B], 2 = shard-stacked [S, B]
    bsz = st.it.shape[-1]

    def merge(new, old):
        sel_b = evict.reshape((1,) * (lead - 1) + (bsz,)
                              + (1,) * (old.ndim - lead))
        return jnp.where(sel_b, new, old)

    parked = _BatchState(
        d=jnp.full_like(st.d, jnp.inf),
        ids=jnp.full_like(st.ids, -1),
        exp=jnp.ones_like(st.exp),
        sel=jnp.zeros_like(st.sel),
        visited=jnp.zeros_like(st.visited),
        it=jnp.zeros_like(st.it),
        t_dc=jnp.zeros_like(st.t_dc),
        s_dc=jnp.zeros_like(st.s_dc),
        picks=jnp.zeros_like(st.picks),
    )
    udc = merge(jnp.zeros_like(upper_dc), upper_dc)
    return jax.tree_util.tree_map(merge, parked, st), udc


@jax.jit
def engine_evict(st: _BatchState, upper_dc: jax.Array, evict: jax.Array
                 ) -> tuple[_BatchState, jax.Array]:
    """Jitted :func:`evict_lanes`: park the flagged lanes in place.

    No static arguments -- one compiled program per state shape serves
    every params/heuristic combination.
    """
    return evict_lanes(st, upper_dc, evict)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def engine_evict_overlap(st: _BatchState, upper_dc: jax.Array,
                         evict: jax.Array) -> tuple[_BatchState, jax.Array]:
    """:func:`engine_evict` with the state DONATED -- the serving tier's
    in-place eviction (parks lanes without copying the batch state; safe
    to dispatch while a donated step chunk is still in flight, the evict
    simply chains onto it). Shape-generic over flat ``[B, ...]`` and
    shard-stacked ``[S, B, ...]`` states like :func:`engine_evict`."""
    return evict_lanes(st, upper_dc, evict)


def finalize_lanes(st: _BatchState, upper_dc: jax.Array,
                   params: SearchParams) -> SearchResult:
    """Unjitted body of :func:`engine_finalize` (shard_map-embeddable)."""
    out_d, out_id, stats = _extract_results(st, params.efs)
    return SearchResult(
        dists=out_d, ids=out_id,
        stats=stats._replace(upper_dc=upper_dc.astype(jnp.int32)))


@functools.partial(jax.jit, static_argnames=("params",))
def engine_finalize(st: _BatchState, upper_dc: jax.Array,
                    params: SearchParams) -> SearchResult:
    """Extract per-lane results from a (possibly partially converged)
    batch state: full-efs beams, the host slices each lane to its own k."""
    return finalize_lanes(st, upper_dc, params)


#: the multi-row execution engines (name -> raw jitted entry point);
#: the single registry behind NavixIndex.search_many, NavixDB.execute,
#: and ProgramCache.batch
BATCH_ENGINES = {"batched": search_many, "vmap": search_batch}


def resolve_engine(engine: str):
    """Validate an engine name and return its raw entry point."""
    try:
        return BATCH_ENGINES[engine]
    except KeyError:
        raise ValueError(f"unknown engine {engine!r}; valid: "
                         f"{tuple(BATCH_ENGINES)}") from None
