"""The device-side lane core shared by every serving driver.

Both serving drivers -- the closed-queue ``SearchEngine.drain()`` and the
live :class:`~repro.serving.service.SearchService` loop -- run the same
machine: a fixed ``[B]``-lane batch over the resumable stepping API of
``repro.core.search_batch`` (``parked_state`` / ``engine_refill`` /
``engine_steps`` / ``engine_finalize`` / ``engine_evict``), shard-aware
through the mirrored ``*_program`` surface of
:class:`~repro.core.distributed.ShardedNavix`. This module holds that
machine so the two drivers stay in bitwise lockstep:

* ``_FlatLanes`` / ``_ShardLanes`` -- the backend split: identical lane
  operations over an unsharded :class:`NavixIndex` or a
  :class:`ShardedNavix` (whose buffers gain a leading shard dim and whose
  ``finalize`` merges per-shard beams under an ``alive`` quorum mask);
* :class:`LaneBatch` -- host-side buffer management + the device calls:
  ``admit`` (compact free lanes, refill them with new requests), ``step``
  (advance ``n_steps`` loop iterations, report per-lane liveness),
  ``finalize`` (extract every lane's current beam), ``evict`` (park
  overdue lanes so they stop burning device work and become refillable).

Scheduling policy -- what to admit, when to flush, which lanes are past
deadline -- stays in the drivers; ``LaneBatch`` owns no policy beyond
"fill free lanes in ascending order", which both drivers rely on.

Overlapped stepping: ``step_async`` dispatches the next device chunk on
DONATED state buffers (``engine_steps_overlap`` / the sharded
``steps_program(donate=True)``) and returns immediately -- the host then
runs finalize/expire/refill/response work concurrently with the in-flight
chunk, and ``step_wait`` synchronizes on the per-lane liveness exactly
once per chunk. ``step`` (dispatch + wait back-to-back) remains the
synchronous spelling. Because the state buffer is donated, callers must
never retain references to a pre-step ``st``; ``LaneBatch`` owns the only
reference and swaps it at dispatch. Finalize/evict/admit issued while a
chunk is in flight simply queue behind it on the device stream -- results
are bitwise identical to the synchronous order.
"""

from __future__ import annotations

import time
from typing import Any, Optional

import jax
import numpy as np

from repro.core.distributed import ShardedNavix
from repro.core.navix import NavixIndex
from repro.serving.spans import note_program, span
from repro.storage.columnar import GraphStore  # noqa: F401  (re-export site)


class _FlatLanes:
    """Device-side lane operations of the continuous scheduler over an
    unsharded :class:`NavixIndex` (the ``search_batch`` stepping API)."""

    n_shards = 0
    lane_multiple = 1
    exact = None

    def __init__(self, idx: NavixIndex, params):
        from repro.core import bitset

        self.idx, self.graph, self.params = idx, idx.graph, params
        self._words = bitset.n_words(idx.graph.n)
        # int8-resident indexes carry an exact f32 tier; LaneBatch
        # re-ranks finalized beams against it (the serving-side re-rank)
        self.exact = (idx.exact if getattr(idx, "is_quantized", False)
                      else None)

    def full_row(self) -> np.ndarray:
        return np.asarray(self.idx.full_semimask())            # [W]

    def pack_row(self, mask) -> np.ndarray:
        # host-side pack: one numpy pass per distinct plan instead of an
        # eager jnp dispatch chain (it dominated the drain wall)
        from repro.core import bitset

        m = np.asarray(mask)
        if m.dtype == np.uint32:
            return m                                           # [W]
        return bitset.pack_np(m)                               # [W]

    def sel_buffer(self, bsz: int) -> np.ndarray:
        return np.zeros((bsz, self._words), np.uint32)

    def set_lane(self, selh: np.ndarray, i: int, row: np.ndarray) -> None:
        selh[i] = row

    def place_lanes(self, arr):
        """Host [B, ...] lane buffer -> device array."""
        import jax.numpy as jnp
        return jnp.asarray(arr)

    place_sel = place_lanes

    def place_admit(self, Qh, selh, sigh, efsh, refill):
        """All five admit-time lane buffers in ONE device transfer."""
        import jax
        return jax.device_put((Qh, selh, sigh, efsh, refill))

    def parked(self, bsz: int):
        import jax.numpy as jnp

        from repro.core import search_batch as sb
        return (sb.parked_state(self.graph.n, bsz, self.params),
                jnp.zeros((bsz,), jnp.int32))

    def refill(self, Qj, selj, st, udc, refill):
        # donated st/udc: LaneBatch drops its references on return
        from repro.core import search_batch as sb
        return sb.engine_refill_overlap(self.graph, Qj, selj, st, udc,
                                        refill, self.params)

    def steps(self, Qj, selj, st, n_steps, sigj, efsj):
        # donated st; dispatch is async -- the caller syncs on `live`
        from repro.core import search_batch as sb
        return sb.engine_steps_overlap(self.graph, Qj, selj, st,
                                       self.params, n_steps, sigma_g=sigj,
                                       efs_lanes=efsj)

    def lower_steps(self, Qj, selj, st, n_steps, sigj, efsj):
        from repro.core import search_batch as sb
        return sb.engine_steps_overlap.lower(self.graph, Qj, selj, st,
                                             self.params, n_steps,
                                             sigma_g=sigj, efs_lanes=efsj)

    def note_steps(self, Qj, selj, st, n_steps, sigj, efsj) -> None:
        """Record the chunk program for a profile's op -> scope map
        (``spans.op_scopes``) by abstract shapes only: the record keeps
        no device buffer alive."""
        from repro.core import search_batch as sb
        args = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding),
            (self.graph, Qj, selj, st, sigj, efsj))
        graph, Q, sel, state, sig, efs = args
        params = self.params
        leaves, tree = jax.tree.flatten(args)
        note_program((params, n_steps, tree, tuple(leaves)),
                     lambda: sb.engine_steps_overlap.lower(
                         graph, Q, sel, state, params, n_steps,
                         sigma_g=sig, efs_lanes=efs))

    def finalize(self, st, udc, alive):
        from repro.core import search_batch as sb
        fin = sb.engine_finalize(st, udc, self.params)
        return fin.ids, fin.dists, fin.stats

    def evict(self, st, udc, evict):
        import jax.numpy as jnp

        from repro.core import search_batch as sb
        return sb.engine_evict_overlap(st, udc, jnp.asarray(evict))


class _ShardLanes:
    """The same lane operations over a :class:`ShardedNavix`: every
    buffer gains a leading shard dim ([S, B, W] semimasks, [S, B]
    upper_dc, shard-stacked beam state) and ``finalize`` merges the
    per-shard beams into global top-k under the current ``alive`` mask.
    Per-lane k/efs capping and lane refill are untouched."""

    exact = None    # sharded indexes stay f32-resident (no quantized tier)

    def __init__(self, sn: ShardedNavix, params):
        self.sn, self.params = sn, params
        self.n_shards = sn.n_shards
        self.lane_multiple = sn.lane_shards
        # donate=True throughout: LaneBatch owns the only reference to
        # st/udc and swaps it at every call, so the device writes in place
        self._refill = sn.refill_program(params, donate=True)
        self._steps = sn.steps_program(params, donate=True)
        # beams-only finalize: bitwise-identical merged ids/dists to
        # finalize_program, minus the stats reduction the drivers discard
        self._finalize = sn.finalize_beams_program(params)
        self._evict = sn.evict_program(params, donate=True)
        # cached NamedShardings: building one per place_* call shows up
        # in the admit path (mesh-shape lookups per transfer)
        self._lane_ns: dict = {}
        self._sel_ns = None

    def full_row(self) -> np.ndarray:
        return np.asarray(self.sn.full_semimask())             # [S, W]

    def pack_row(self, mask) -> np.ndarray:
        m = np.asarray(mask)
        if m.dtype == np.uint32:
            return m                                           # [S, W]
        return self.sn.shard_semimask_np(m)                    # [S, W]

    def sel_buffer(self, bsz: int) -> np.ndarray:
        return np.zeros((self.n_shards, bsz, self.sn.n_words_local),
                        np.uint32)

    def set_lane(self, selh: np.ndarray, i: int, row: np.ndarray) -> None:
        selh[:, i] = row

    def _lane_sharding(self, ndim: int):
        ns = self._lane_ns.get(ndim)
        if ns is None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            ns = NamedSharding(self.sn.mesh, P(
                self.sn.data_axis, *([None] * (ndim - 1))))
            self._lane_ns[ndim] = ns
        return ns

    def _sel_sharding(self):
        if self._sel_ns is None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            self._sel_ns = NamedSharding(self.sn.mesh, P(
                self.sn.model_axis, self.sn.data_axis, None))
        return self._sel_ns

    def place_lanes(self, arr):
        """Host [B, ...] lane buffer -> device array split over the data
        axis -- matching the program in_specs, so steady-state calls
        never reshard their operands."""
        import jax
        return jax.device_put(arr, self._lane_sharding(np.ndim(arr)))

    def place_sel(self, arr):
        """Host [S, B, W] semimask buffer -> device array on the
        (model, data) layout the programs expect."""
        import jax
        return jax.device_put(arr, self._sel_sharding())

    def place_admit(self, Qh, selh, sigh, efsh, refill):
        """All five admit-time lane buffers in ONE mesh transfer."""
        import jax
        lane1, lane2 = self._lane_sharding(1), self._lane_sharding(2)
        return jax.device_put(
            (Qh, selh, sigh, efsh, refill),
            (lane2, self._sel_sharding(), lane1, lane1, lane1))

    def parked(self, bsz: int):
        return self.sn.parked_state(bsz, self.params)

    def refill(self, Qj, selj, st, udc, refill):
        return self._refill(self.sn.graphs, Qj, selj, st, udc, refill)

    def steps(self, Qj, selj, st, n_steps, sigj, efsj):
        # sigj unused: each shard's lanes estimate selectivity against
        # their own slice of S (lane-local, shard-local)
        return self._steps(self.sn.graphs, Qj, selj, st, n_steps,
                           efs_lanes=efsj)

    def lower_steps(self, Qj, selj, st, n_steps, sigj, efsj):
        return self._steps.lower(self.sn.graphs, Qj, selj, st, n_steps,
                                 efs_lanes=efsj)

    def note_steps(self, Qj, selj, st, n_steps, sigj, efsj) -> None:
        # no op -> scope map for the sharded programs: a profile of a
        # sharded index reads no engine scope shares
        pass

    def finalize(self, st, udc, alive):
        # beams only: no per-lane stats (a response's work counters stay
        # None on a sharded index)
        import jax.numpy as jnp
        d, ids = self._finalize(st, udc, jnp.asarray(alive))
        return ids, d, None

    def evict(self, st, udc, evict):
        return self._evict(st, udc, self.place_lanes(np.asarray(evict)))


def make_backend(idx, params):
    """The backend split: ShardedNavix -> _ShardLanes, else _FlatLanes."""
    return (_ShardLanes(idx, params) if isinstance(idx, ShardedNavix)
            else _FlatLanes(idx, params))


class LaneBatch:
    """A resumable ``[B]``-lane device batch with host-side bookkeeping.

    Each lane is free (``meta[i] is None``) or carries one in-flight
    request's opaque driver payload. Device state (`st`, `udc`) and the
    host mirrors of the lane buffers (query rows, packed per-lane
    semimasks, per-lane sigma) live here; drivers decide *when* to call
    ``admit`` / ``step`` / ``finalize`` / ``evict`` and what the payloads
    mean. Admission fills free lanes in ascending index order.
    """

    def __init__(self, idx, heuristic: str, k_cap: int, efs_cap: int,
                 bsz: int):
        self.params = idx._params(k_cap, efs_cap, heuristic)
        self.backend = make_backend(idx, self.params)
        # data-axis backends split the lane dim over lane_multiple
        # devices; round the batch up so it divides evenly
        lm = self.backend.lane_multiple
        bsz = -(-bsz // lm) * lm
        self.bsz = bsz
        self.k_cap, self.efs_cap = k_cap, efs_cap
        dim = (idx.dim if isinstance(idx, ShardedNavix)
               else int(idx.graph.vectors.shape[-1]))
        self.Qh = np.zeros((bsz, dim), np.float32)
        self.selh = self.backend.sel_buffer(bsz)
        self.sigh = np.ones((bsz,), np.float32)
        # per-lane efs: free/uniform lanes sit at the cap (the masked
        # beam tail is then empty, bitwise-identical to no masking)
        self.efsh = np.full((bsz,), efs_cap, np.int32)
        self.meta: list[Optional[Any]] = [None] * bsz
        self.st, self.udc = self.backend.parked(bsz)
        self.Qj = self.backend.place_lanes(self.Qh)
        self.selj = self.backend.place_sel(self.selh)
        self.sigj = self.backend.place_lanes(self.sigh)
        self.efsj = self.backend.place_lanes(self.efsh)
        # the last finalize's per-lane work counters (host SearchStats;
        # None on a sharded backend)
        self.stats = None
        # overlapped-stepping bookkeeping (host-vs-device observability)
        self._live_pending = None          # in-flight chunk's live[B]
        self._noted: set = set()           # n_steps whose program is noted
        self._t_dispatched = 0.0
        self._t_wait_end = time.perf_counter()
        self._timing: dict = {}
        self.reset_timing()

    @property
    def n_shards(self) -> int:
        return self.backend.n_shards

    def occupied(self) -> list[int]:
        return [i for i in range(self.bsz) if self.meta[i] is not None]

    def occupied_count(self) -> int:
        return sum(1 for m in self.meta if m is not None)

    def free_count(self) -> int:
        return self.bsz - self.occupied_count()

    def release(self, i: int) -> None:
        """Free a lane host-side. Its frozen device state is inert (a
        converged/parked lane never advances) and the next ``admit``
        overwrites it."""
        self.meta[i] = None

    # -- device calls ---------------------------------------------------
    def admit(self, entries) -> list[int]:
        """Fill free lanes (ascending) from ``entries`` -- an iterable of
        ``(meta, qrow, sel_row, sigma, efs)`` -- and run ONE device refill
        for all of them (``efs`` is clamped to ``[1, efs_cap]``; lanes
        below the cap skip the cap-wide beam-tail maintenance). Returns
        the lane indices used; raises if more entries arrive than there
        are free lanes."""
        refill = np.zeros(self.bsz, bool)
        used: list[int] = []
        it = iter(entries)
        entry = next(it, None)
        for i in range(self.bsz):
            if entry is None:
                break
            if self.meta[i] is not None:
                continue
            meta, qrow, row, sigma, efs = entry
            self.Qh[i] = qrow
            self.backend.set_lane(self.selh, i, row)
            self.sigh[i] = sigma
            self.efsh[i] = min(max(int(efs), 1), self.efs_cap)
            self.meta[i] = meta
            refill[i] = True
            used.append(i)
            entry = next(it, None)
        if entry is not None:
            raise ValueError("more entries than free lanes; size the "
                             "admission to LaneBatch.free_count()")
        if not used:
            return used
        with span("navix.admit", lanes=len(used),
                  semimask_bytes=self.selh.nbytes):
            (self.Qj, self.selj, self.sigj, self.efsj,
             refill_j) = self.backend.place_admit(
                self.Qh, self.selh, self.sigh, self.efsh, refill)
            self.st, self.udc = self.backend.refill(
                self.Qj, self.selj, self.st, self.udc, refill_j)
        return used

    @property
    def step_pending(self) -> bool:
        """True while a dispatched device chunk has not been waited on."""
        return self._live_pending is not None

    def step_async(self, n_steps: int) -> None:
        """Dispatch the next device chunk (at most ``n_steps`` loop
        iterations; 0 = run to whole-batch convergence) WITHOUT blocking.
        The state buffers are donated to the chunk, so the pre-dispatch
        ``st`` is dead the moment this returns; host-side work between
        this call and :meth:`step_wait` overlaps the device."""
        if self._live_pending is not None:
            raise RuntimeError("a device chunk is already in flight; "
                               "step_wait() it first")
        t0 = time.perf_counter()
        self._timing["host_gap_ms"] += (t0 - self._t_wait_end) * 1e3
        if n_steps not in self._noted:
            self._noted.add(n_steps)
            self.backend.note_steps(self.Qj, self.selj, self.st, n_steps,
                                    self.sigj, self.efsj)
        self.st, self._live_pending = self.backend.steps(
            self.Qj, self.selj, self.st, n_steps, self.sigj, self.efsj)
        self._t_dispatched = time.perf_counter()

    def step_wait(self) -> np.ndarray:
        """Synchronize on the in-flight chunk; returns live bool[B].
        The ONE host sync per chunk lives here."""
        if self._live_pending is None:
            raise RuntimeError("no device chunk in flight; step_async() "
                               "first")
        t1 = time.perf_counter()
        self._timing["host_overlap_ms"] += (t1 - self._t_dispatched) * 1e3
        with span("navix.wait", self._timing, "device_wait_ms"):
            # navilint: sync-ok chunk boundary -- the host scheduler branches on liveness between device chunks (one sync per chunk by design)
            live = np.asarray(self._live_pending)
        self._live_pending = None
        self._t_wait_end = time.perf_counter()
        self._timing["n_chunks"] += 1
        return live

    def step(self, n_steps: int) -> np.ndarray:
        """Advance every lane by at most ``n_steps`` loop iterations
        (0 = run to whole-batch convergence); returns live bool[B]. The
        synchronous spelling of ``step_async`` + ``step_wait``."""
        self.step_async(n_steps)
        return self.step_wait()

    def lower_step(self, n_steps: int):
        """The ``jax`` Lowered form of the chunk program ``step_async``
        dispatches, over the current lane buffers (nothing runs or is
        donated): its text shows which kernels the served step calls."""
        return self.backend.lower_steps(self.Qj, self.selj, self.st,
                                        n_steps, self.sigj, self.efsj)

    def timing(self) -> dict:
        """Cumulative counters over every stepped chunk and finalize:
        the host-vs-device split (``host_gap_ms``: host work the device
        waited for, wait -> dispatch; ``host_overlap_ms``: host work
        hidden behind the chunk in flight, dispatch -> wait;
        ``device_wait_ms``: blocked on the device, the ``navix.wait``
        span), ``n_chunks``, and the exact-tier re-rank (``rerank_ms``
        over ``n_rerank`` finalizes, the ``navix.rerank`` span)."""
        return dict(self._timing)

    def reset_timing(self) -> None:
        """Zero the counters and re-anchor the gap clock. A reused
        batch (the closed-queue engine keeps LaneBatches across drains --
        parked-state allocation + mesh placement is the dominant per-drain
        setup cost on sharded backends) would otherwise charge the idle
        time between drains as host_gap."""
        self._timing.update(n_chunks=0, host_gap_ms=0.0, host_overlap_ms=0.0,
                            device_wait_ms=0.0, rerank_ms=0.0, n_rerank=0)
        self._t_wait_end = time.perf_counter()

    def finalize(self, alive) -> tuple[np.ndarray, np.ndarray]:
        """Extract every lane's current beam under ``alive`` (sharded
        backends merge across shards; a flat backend ignores it).
        Returns host ``(ids[B, efs], dists[B, efs])``.

        Quantized-resident backends finish here: the full-width beam
        (searched on int8 codes) is exactly re-ranked against the host
        f32 tier, lane-vectorized, so every driver's ``[:k]`` slice of a
        finalized lane is already exact-ordered. Parked/free lanes are
        all ``-1`` and stay all ``-1`` through the re-rank.

        The per-lane work counters (``SearchStats``: iterations, distance
        computations, branch picks) come back in the same transfer and
        are kept as :attr:`stats` (None on a sharded backend)."""
        fin = self.backend.finalize(self.st, self.udc, alive)
        # navilint: sync-ok THE declared finalize boundary -- ids, dists and stats cross to host in one transfer per finalize
        ids, dists, self.stats = jax.device_get(fin)
        exact = self.backend.exact
        if exact is not None:
            # exact-tier re-rank: host-side numpy at the same finalize
            # boundary (prepped queries already mirrored in Qh)
            with span("navix.rerank", self._timing, "rerank_ms"):
                dists, ids = exact.rerank_many(self.Qh, ids, ids.shape[1])
            self._timing["n_rerank"] += 1
        return ids, dists

    def work(self, i: int) -> dict:
        """Lane ``i``'s work counters from the last :meth:`finalize`, as
        the ``Response`` fields ``iters`` (loop iterations), ``dc``
        (distance computations, lower + upper level) and ``picks`` (times
        onehop-s / directed / blind was chosen); empty on a sharded
        backend, whose finalize returns beams only."""
        st = self.stats
        if st is None:
            return {}
        return {"iters": int(st.iters[i]),
                "dc": int(st.t_dc[i] + st.upper_dc[i]),
                "picks": tuple(int(x) for x in st.picks[i])}

    def evict(self, lane_ids) -> None:
        """Park the given lanes (one device call) and free them. Parked
        lanes report live=False and finalize to all ``-1`` ids until the
        next admit overwrites them -- finalize BEFORE evicting to salvage
        a partial beam."""
        lane_ids = list(lane_ids)
        if not lane_ids:
            return
        mask = np.zeros(self.bsz, bool)
        mask[lane_ids] = True
        self.st, self.udc = self.backend.evict(self.st, self.udc, mask)
        for i in lane_ids:
            self.meta[i] = None
