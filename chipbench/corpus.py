"""Corpora made from a seed, in the shapes of a configuration file.

Two shapes, named by a configuration's ``corpus`` key:

* ``wiki`` -- the NaviX paper's Wiki graph (arXiv 2506.23397, Sec. 5.1,
  Fig. 7a schema): node tables Person, Resource and Chunk, relationships
  PersonChunk, ResourceChunk and WikiLink. Each person owns
  ``chunks_per_person`` chunks, each resource ``chunks_per_resource``
  (the last resource takes the remainder), and person chunks are
  ``person_share`` of all chunks. Person chunks come from mixture
  components of their own, offset into a region of their own, so that
  questions about persons correlate with person-chunk filters and other
  questions do not (the mechanism of the paper's correlated workloads).
* ``flat`` -- one Chunk table (the TEXMEX GIST1M shape: objects with an
  uncorrelated id column).

Embeddings are a Gaussian mixture in a ``latent``-wide space, projected
to ``d`` and jittered. The components' spread equals the centres' own,
so neighbouring components overlap as in text-embedding corpora instead
of forming islands that no graph edge crosses. The chunk order is
shuffled, so ``cID`` carries no information about the embedding.

This module imports nothing of the system under test: the reference
evaluates the same predicates from the arrays kept here.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

#: birth dates are whole days in [0, DAYS)
DAYS = 36_500


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream); any whole seed up
    to and beyond 2**32 is accepted."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


@dataclasses.dataclass
class Corpus:
    X: np.ndarray                     # f32[n, d] chunk embeddings
    cid: np.ndarray                   # int64[n] shuffled id column
    latent_centers: np.ndarray        # f32[c, latent] every component
    chunk_component: np.ndarray       # int32[n] component of each chunk
    W: np.ndarray                     # f32[latent, d] projection
    person_of_chunk: Optional[np.ndarray] = None   # int64[n], -1 = none
    birth_date: Optional[np.ndarray] = None        # int64[n_person]
    resource_of_chunk: Optional[np.ndarray] = None  # int64[n], -1 = none
    wikilink: Optional[tuple] = None               # (src, dst) int64

    @property
    def n(self) -> int:
        return self.X.shape[0]

    @property
    def is_person(self) -> np.ndarray:
        if self.person_of_chunk is None:
            return np.zeros(self.n, bool)
        return self.person_of_chunk >= 0

    @property
    def n_person(self) -> int:
        return 0 if self.birth_date is None else len(self.birth_date)


def _embed(Z: np.ndarray, W: np.ndarray, jitter: float,
           rng: np.random.Generator) -> np.ndarray:
    X = Z @ W
    X += jitter * rng.standard_normal(X.shape, dtype=np.float32)
    return X.astype(np.float32, copy=False)


def make_corpus(cfg: dict, seed: int) -> Corpus:
    """The corpus a configuration describes, from ``seed``."""
    mix = cfg["mixture"]
    n, d, latent = int(cfg["n"]), int(cfg["d"]), int(mix["latent"])
    std = float(mix["component_std"])
    rng = rng_for(seed, 0)
    W = (rng.standard_normal((latent, d), dtype=np.float32)
         / np.float32(np.sqrt(latent)))
    shape = cfg["corpus"]
    if shape == "flat":
        centers = rng.standard_normal((int(mix["components"]), latent),
                                      dtype=np.float32)
        comp = rng.integers(0, len(centers), size=n).astype(np.int32)
        Z = centers[comp] + std * rng.standard_normal((n, latent),
                                                      dtype=np.float32)
        X = _embed(Z, W, float(mix["jitter"]), rng)
        return Corpus(X=X, cid=rng.permutation(n).astype(np.int64),
                      latent_centers=centers, chunk_component=comp, W=W)
    if shape != "wiki":
        raise ValueError(f"unknown corpus shape {shape!r}")

    sch = cfg["schema"]
    cpp, cpr = int(sch["chunks_per_person"]), int(sch["chunks_per_resource"])
    n_person = int(round(n * float(sch["person_share"]) / cpp))
    n_pchunks = n_person * cpp
    n_rchunks = n - n_pchunks
    n_resource = -(-n_rchunks // cpr)
    n_pc, n_rc = int(mix["person_components"]), int(mix["resource_components"])
    offset = rng.standard_normal(latent, dtype=np.float32)
    offset *= np.float32(float(mix["person_offset"]) / np.linalg.norm(offset))
    centers = np.concatenate([
        rng.standard_normal((n_pc, latent), dtype=np.float32) + offset,
        rng.standard_normal((n_rc, latent), dtype=np.float32)])
    person_comp = rng.integers(0, n_pc, size=n_person)
    resource_comp = n_pc + rng.integers(0, n_rc, size=n_resource)
    # generation order: person chunks first, then resource chunks
    owner_p = np.repeat(np.arange(n_person), cpp)
    owner_r = np.repeat(np.arange(n_resource), cpr)[:n_rchunks]
    comp = np.concatenate([person_comp[owner_p],
                           resource_comp[owner_r]]).astype(np.int32)
    Z = centers[comp] + std * rng.standard_normal((n, latent),
                                                  dtype=np.float32)
    X = _embed(Z, W, float(mix["jitter"]), rng)
    # shuffle chunk ids so id-range filters are uncorrelated
    perm = rng.permutation(n)                 # generation index -> chunk id
    inv = np.argsort(perm)
    person_of = np.full(n, -1, np.int64)
    person_of[perm[:n_pchunks]] = owner_p
    resource_of = np.full(n, -1, np.int64)
    resource_of[perm[n_pchunks:]] = owner_r
    birth = rng.integers(0, DAYS, size=n_person).astype(np.int64)
    links = int(sch["links_per_person"])
    wl = (np.repeat(np.arange(n_person, dtype=np.int64), links),
          rng.integers(0, n_resource, size=n_person * links).astype(np.int64))
    return Corpus(X=X[inv], cid=np.arange(n, dtype=np.int64),
                  latent_centers=centers, chunk_component=comp[inv], W=W,
                  person_of_chunk=person_of, birth_date=birth,
                  resource_of_chunk=resource_of, wikilink=wl)


def graph_store_tables(c: Corpus) -> dict:
    """The corpus as node tables and relationships, in plain arrays:
    ``{"nodes": {table: (n, {column: array})}, "rels": {name: (src_table,
    dst_table, src, dst)}}``. The harness hands these to the system's
    store; the reference never reads them."""
    nodes = {"Chunk": (c.n, {"cID": c.cid})}
    rels = {}
    if c.person_of_chunk is not None:
        n_res = int(c.resource_of_chunk.max()) + 1
        nodes["Chunk"][1]["is_person"] = c.is_person
        nodes["Person"] = (c.n_person, {
            "pID": np.arange(c.n_person, dtype=np.int64),
            "birth_date": c.birth_date})
        nodes["Resource"] = (n_res, {"rID": np.arange(n_res,
                                                      dtype=np.int64)})
        pc = np.flatnonzero(c.person_of_chunk >= 0)
        rc = np.flatnonzero(c.resource_of_chunk >= 0)
        rels["PersonChunk"] = ("Person", "Chunk", c.person_of_chunk[pc], pc)
        rels["ResourceChunk"] = ("Resource", "Chunk",
                                 c.resource_of_chunk[rc], rc)
        rels["WikiLink"] = ("Person", "Resource", *c.wikilink)
    return {"nodes": nodes, "rels": rels}


def sample_queries(c: Corpus, cfg: dict, mode: str, count: int,
                   rng: np.random.Generator) -> np.ndarray:
    """``count`` query vectors drawn from the corpus's own mixture
    (``global``: each from the component of a random chunk)."""
    if mode != "global":
        raise ValueError(f"unknown query mode {mode!r}")
    mix = cfg["mixture"]
    comp = c.chunk_component[rng.choice(c.n, size=count)]
    latent = c.latent_centers.shape[1]
    Z = c.latent_centers[comp] + float(mix["component_std"]) * \
        rng.standard_normal((count, latent), dtype=np.float32)
    return _embed(Z, c.W, float(mix["jitter"]), rng)
