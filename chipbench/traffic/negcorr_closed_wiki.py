"""Graph-RAG on the Wiki graph with a negatively correlated hop: every
request asks for the chunks of the persons born in a date range of its
own, and its query comes from a non-person component, so S (person
chunks, offset into a region of their own) lies away from the query.

How much of S the search finds differs between corpora by several
points of recall, so the mix serves one deployment (``data_seed``: the
corpus and the index come from it in every run) and draws its requests
from one sample, in an order the run's seed draws: every seed sends the
same requests to the same index, and the check set reads one recall.

* ``make_requests``: each query is drawn from a non-person component (as
  ``corpus.sample_queries`` draws ``global`` queries: the component of a
  random non-person chunk, here with the components' expected shares,
  which are equal), and each sigma (a share of all chunks) is as frequent
  as the others, within the check set (the first ``clients *
  recall_per_client`` requests) and within the rest. A request's Q_S is
  the persons born in ``[lo, hi)``, ``hi - lo = round(sigma /
  person_share * DAYS)`` days, with ``lo`` drawn uniformly per request:
  every Q_S is new, and the service's cache of prefiltered Q_S misses.
  The check set is one sample and the rest another, each in an order the
  seed draws, so every seed's check set is the same set of requests.
* ``plan``: ``HopJoin(Filter(NodeScan("Person"), "birth_date", "range",
  lo, hi), "PersonChunk")``, evaluated by the system's predicate layer.
* ``mask``: the same half-open interval, from the corpus's own
  ``birth_date`` and ``person_of_chunk`` in numpy (the reference's side;
  never the system's plan evaluator).
"""

from __future__ import annotations

import numpy as np

from corpus import DAYS, Corpus, rng_for
from traffic import Request

#: the stream of the request sample that every seed shares
REQUESTS_STREAM = 4


def make_requests(mix: dict, corpus: Corpus, cfg: dict, seed: int,
                  count: int) -> list[Request]:
    pred, m = mix["predicate"], cfg["mixture"]
    if pred["kind"] != "birth_hop" or mix["queries"] != "nonperson":
        raise ValueError(f"this mix serves birth_hop over nonperson "
                         f"queries, not {pred['kind']!r} over "
                         f"{mix['queries']!r}")
    shared = rng_for(int(mix["data_seed"]), REQUESTS_STREAM)
    order = rng_for(seed, 1)
    n_pc, n_rc = int(m["person_components"]), int(m["resource_components"])
    latent = corpus.latent_centers.shape[1]
    sigmas = np.asarray(pred["sigmas"], float)
    share = float(cfg["schema"]["person_share"])
    n_check = min(count, int(mix["clients"]) * int(mix["recall_per_client"]))
    out: list[Request] = []
    for size in (n_check, count - n_check):
        comp = n_pc + shared.integers(0, n_rc, size=size)
        Z = corpus.latent_centers[comp] + float(m["component_std"]) * \
            shared.standard_normal((size, latent), dtype=np.float32)
        Q = Z @ corpus.W
        Q += float(m["jitter"]) * shared.standard_normal(Q.shape,
                                                         dtype=np.float32)
        sig = shared.permutation(np.resize(sigmas, size))
        width = np.round(sig / share * DAYS).astype(np.int64)
        lo = shared.integers(0, DAYS - width + 1)
        for i in order.permutation(size):
            out.append(Request(idx=len(out), q=Q[i],
                               pred=("birth_hop", int(lo[i]),
                                     int(lo[i] + width[i])),
                               sigma=float(sig[i])))
    return out


def mask(c: Corpus, pred: tuple) -> np.ndarray:
    """The chunks of the persons born in ``[lo, hi)``, as bool[n]."""
    _, lo, hi = pred
    person = c.person_of_chunk
    born = c.birth_date[np.maximum(person, 0)]
    return (person >= 0) & (born >= lo) & (born < hi)


def plan(pred: tuple, k: int, efs: int):
    from repro.query.operators import (Filter, HopJoin, KnnSearch,
                                       NodeScan)
    _, lo, hi = pred
    people = Filter(NodeScan("Person"), "birth_date", "range", lo=lo, hi=hi)
    return KnnSearch(child=HopJoin(people, "PersonChunk"), k=k, efs=efs)
