"""The plain reference: exact filtered kNN, and the comparison that
decides ``correct``.

It imports nothing of the system under test and takes nothing it made.
Q_S comes to it as masks that the mix's ``mask`` evaluates in numpy
from the corpus's own columns (``traffic.mask``), not through the
system's plan evaluator, so a fault in the predicate layer shows.
Distances are float32 on the default device at ``Precision.HIGHEST``:
the top-k is chosen by a blocked matrix product, and every distance
that is compared is then recomputed elementwise from the rows (no
cancellation).

The numbers compared, each with a limit of its own
(``chipbench/limits/<cell>.json``):

* ``missing``: requests sent but not answered ``ok`` by the drain cap.
  Exact; limit 0.
* ``bad_answers``: answers holding an id outside their Q_S, an id twice,
  or distances that are not ascending. Exact; limit 0.
* ``dist_gap``: the widest gap between a distance the client received
  and the reference's distance of the same id, over every returned id,
  as a share of that request's exact k-th distance.
* ``miss_share``: 1 - the mean recall@k over the answers checked.
"""

from __future__ import annotations

import numpy as np

#: query rows per block of the exact search
BLOCK = 64


def _prep(X, metric: str):
    import jax.numpy as jnp
    X = jnp.asarray(X, jnp.float32)
    if metric == "cos":
        X = X / jnp.sqrt(jnp.sum(X * X, axis=-1, keepdims=True))
    return X


def _pairwise(Q, X, metric: str, dtype):
    """[b, n] distances (smaller is closer); the product in ``dtype``
    (float32 at HIGHEST, or a lower precision for the control)."""
    import jax
    import jax.numpy as jnp
    prec = (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    dot = jnp.dot(Q.astype(dtype), X.astype(dtype).T, precision=prec,
                  preferred_element_type=jnp.float32)
    if metric == "cos":
        return 1.0 - dot
    if metric == "l2":
        xx = jnp.sum(X.astype(dtype).astype(jnp.float32) ** 2, axis=1)
        qq = jnp.sum(Q.astype(dtype).astype(jnp.float32) ** 2, axis=1)
        return qq[:, None] + xx[None, :] - 2.0 * dot
    raise ValueError(metric)


def row_dists(Xp, Qp, ids: np.ndarray, metric: str) -> np.ndarray:
    """Elementwise f32 distances of query b to rows ``ids[b]`` ([b, k];
    -1 ids give +inf)."""
    import jax.numpy as jnp
    safe = jnp.asarray(np.maximum(ids, 0))
    rows = Xp[safe]                                  # [b, k, d]
    if metric == "cos":
        d = 1.0 - jnp.sum(rows * Qp[:, None, :], axis=-1)
    else:
        diff = rows - Qp[:, None, :]
        d = jnp.sum(diff * diff, axis=-1)
    return np.where(ids >= 0, np.asarray(d), np.inf)


def _block(Qb, Mb, Xp, *, k: int, metric: str, dtype):
    import jax
    import jax.numpy as jnp
    d = jnp.where(Mb, _pairwise(Qb, Xp, metric, dtype), jnp.inf)
    neg, ids = jax.lax.top_k(-d, k)
    return -neg, ids


def exact_topk(X, Q: np.ndarray, masks: np.ndarray, k: int, metric: str,
               dtype=None) -> tuple[np.ndarray, np.ndarray]:
    """Exact filtered top-k: ``(ids[b, k], dists[b, k])``, ascending,
    -1 / +inf where fewer than k rows are selected. ``dtype`` None is
    float32 at HIGHEST (the reference); ``jnp.bfloat16`` gives the
    control, whose distances are those of the lower precision."""
    import jax
    import jax.numpy as jnp
    dtype = jnp.float32 if dtype is None else dtype
    Xp = _prep(X, metric)
    ids_out = np.full((len(Q), k), -1, np.int64)
    d_out = np.full((len(Q), k), np.inf, np.float32)

    block = jax.jit(_block, static_argnames=("k", "metric", "dtype"))
    for s in range(0, len(Q), BLOCK):
        Qb = _prep(np.asarray(Q[s:s + BLOCK]), metric)
        Mb = np.asarray(masks[s:s + BLOCK], bool)
        pad = BLOCK - len(Mb)
        if pad:       # one block shape: one compile
            Qb = jnp.pad(Qb, ((0, pad), (0, 0)))
            Mb = np.pad(Mb, ((0, pad), (0, 0)))
        d, ids = block(Qb, jnp.asarray(Mb), Xp, k=k, metric=metric,
                       dtype=dtype)
        d, ids = np.asarray(d)[:len(Q) - s], np.asarray(ids)[:len(Q) - s]
        ok = np.isfinite(d)
        ids_out[s:s + BLOCK] = np.where(ok, ids, -1)
        d_out[s:s + BLOCK] = np.where(ok, d, np.inf)
    return ids_out, d_out


def compare(X, Q: np.ndarray, masks: np.ndarray, got_ids: np.ndarray,
            got_d: np.ndarray, k: int, metric: str) -> dict:
    """The compared numbers for answers ``got_ids``/``got_d`` ([b, k]) to
    queries ``Q`` under ``masks``, plus per-request recall."""
    true_ids, _ = exact_topk(X, Q, masks, k, metric)
    Xp = _prep(X, metric)
    gap, recall, bad = 0.0, np.zeros(len(Q)), 0
    for s in range(0, len(Q), BLOCK):
        sl = slice(s, s + BLOCK)
        Qp = _prep(np.asarray(Q[sl]), metric)
        d_true = row_dists(Xp, Qp, true_ids[sl], metric)
        d_got = row_dists(Xp, Qp, got_ids[sl], metric)
        for j in range(len(d_true)):
            i = s + j
            g = got_ids[i]
            valid = g >= 0
            n_true = int((true_ids[i] >= 0).sum())
            t = set(true_ids[i][true_ids[i] >= 0].tolist())
            recall[i] = (len(t & set(g[valid].tolist())) / n_true
                         if n_true else 1.0)
            gv = g[valid]
            rd = np.asarray(got_d[i], np.float64)[valid]
            if (not masks[i][gv].all() or len(set(gv.tolist())) < len(gv)
                    or np.any(np.diff(rd) < 0)):
                bad += 1
            fin = d_true[j][np.isfinite(d_true[j])]
            scale = max(float(np.abs(fin).max()) if len(fin) else 1.0,
                        1e-12)
            if valid.any():
                gap = max(gap, float(np.max(np.abs(
                    rd - d_got[j][valid].astype(np.float64)))) / scale)
    return {"bad_answers": bad, "dist_gap": gap,
            "miss_share": float(1.0 - recall.mean()) if len(Q) else 0.0,
            "recall": recall}


def control_numbers(X, Q: np.ndarray, masks: np.ndarray, k: int,
                    metric: str) -> dict:
    """The control: the reference put in the system's place, computed in
    bfloat16 (the nearest precision below the configuration's float32),
    compared as the system's answers are."""
    import jax.numpy as jnp
    ids, d = exact_topk(X, Q, masks, k, metric, dtype=jnp.bfloat16)
    return compare(X, Q, masks, ids, d, k, metric)
