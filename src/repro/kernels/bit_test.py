"""Second-hop eligibility: two per-lane bit tests in one pass over VMEM.

The batched engine's second hop asks, for each of a lane's ``L`` (=
M_L²) two-hop candidate ids, whether the id is in the lane's selected
set S and not yet visited. Both sets are packed ``uint32[W]`` bitsets
(``repro.core.bitset``). As two XLA gathers that is ``2 * B * L``
single-word lookups, which the TPU runs one element at a time.

Here a lane's two bitsets come into VMEM as ``[R, 128]`` blocks (R =
W/128 rows of 128 words) and its ids as ``[L/128, 128]``. A word is
found by a lane gather (``take_along_axis`` over the 128 lanes, Mosaic's
``tpu.dynamic_gather``) of each table row in turn, kept where the id's
row ``word >> 7`` is that row. The two tables are combined row by row
first (``S & ~visited``), so one lookup serves both tests. The cost
grows with R, so the dispatch layer (``ops.second_hop_eligible``) takes
this kernel only up to a row count measured on the chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def _kernel(ids_ref, sel_ref, vis_ref, out_ref, *, rows: int):
    ids = ids_ref[...]                                   # [L/128, 128]
    safe = jnp.maximum(ids, 0)
    word = safe >> 5
    lane = word & (LANES - 1)
    row = word >> 7

    def body(r, acc):
        tbl = sel_ref[pl.ds(r, 1), :] & ~vis_ref[pl.ds(r, 1), :]   # [1, 128]
        got = jnp.take_along_axis(jnp.broadcast_to(tbl, ids.shape), lane,
                                  axis=1)
        return jnp.where(row == r, got, acc)

    words = lax.fori_loop(0, rows, body, jnp.zeros(ids.shape, jnp.uint32),
                          unroll=rows <= 16)
    hit = (words >> (safe & 31).astype(jnp.uint32)) & jnp.uint32(1)
    out_ref[...] = jnp.where(ids >= 0, hit, jnp.uint32(0)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def second_hop_eligible_pallas(sel: jax.Array, visited: jax.Array,
                               ids: jax.Array,
                               interpret: bool = False) -> jax.Array:
    """sel[B, W], visited[B, W] (uint32 bitsets), ids[B, L] (int32; < 0
    is padding) -> bool[B, L]: ``ids >= 0 & bit(sel) & ~bit(visited)``,
    each lane against its own two bitsets.

    W is zero-padded to a multiple of 128 (a padded word is never read:
    ids < 32 W) and L to a multiple of 128 with -1.
    """
    b, w = sel.shape
    l = ids.shape[1]
    wp, lp = -(-w // LANES) * LANES, -(-l // LANES) * LANES
    tables = [jnp.pad(t, ((0, 0), (0, wp - w))).reshape(b, wp // LANES, LANES)
              for t in (sel, visited)]
    idp = jnp.pad(ids, ((0, 0), (0, lp - l)), constant_values=-1)
    idp = idp.reshape(b, lp // LANES, LANES)
    rows = wp // LANES

    def lane_block(shape):
        return pl.BlockSpec((None,) + shape, lambda i: (i, 0, 0))

    out = pl.pallas_call(
        functools.partial(_kernel, rows=rows),
        grid=(b,),
        in_specs=[lane_block(idp.shape[1:]), lane_block((rows, LANES)),
                  lane_block((rows, LANES))],
        out_specs=lane_block(idp.shape[1:]),
        out_shape=jax.ShapeDtypeStruct(idp.shape, jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(idp, *tables)
    return out.reshape(b, lp)[:, :l] != 0
