"""Seeded corpus at a configuration's shapes.

Vectors are a Gaussian mixture whose within-cluster spread lies in a
low-dimensional random subspace per cluster (the low intrinsic dimension of
real embeddings); tags are Zipf-distributed over a fixed vocabulary; each
record carries the configuration's numeric fields. The same seed gives the
same corpus. Copied in spirit from the program's ``repro.data.synth`` so
that no program change can move the yardstick, and vectorised so that a
100K-record corpus takes seconds.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Corpus:
    vectors: np.ndarray        # (N, d) float32
    queries: np.ndarray        # (Q, d) float32, drawn from the same mixture
    tag_offsets: np.ndarray    # (N + 1,) int64 CSR over records
    tag_flat: np.ndarray       # (nnz,) int32, sorted within each record
    values: dict               # numeric field -> (N,) float32
    n_tags: int

    @property
    def n(self) -> int:
        return self.vectors.shape[0]

    def tag_cover(self) -> np.ndarray:
        """Number of records holding each tag id."""
        return np.bincount(self.tag_flat, minlength=self.n_tags)

    def metadata(self) -> list:
        """One plain dict per record, as a user hands them to the index."""
        fields = list(self.values)
        cols = [self.values[f].tolist() for f in fields]
        off = self.tag_offsets.tolist()
        flat = self.tag_flat.tolist()
        return [dict({"tag": flat[off[i]:off[i + 1]]},
                     **{f: c[i] for f, c in zip(fields, cols)})
                for i in range(self.n)]


def _mixture(rng, centers, basis, assign, sigma):
    out = centers[assign].copy()
    z = rng.normal(0.0, sigma, (assign.size, basis.shape[1])).astype(
        np.float32)
    for c in range(centers.shape[0]):
        rows = np.flatnonzero(assign == c)
        out[rows] += z[rows] @ basis[c]
    return out


def make_corpus(cfg: dict, seed: int, n_queries: int) -> Corpus:
    """``cfg`` is a configuration file's ``corpus`` block."""
    n, d = int(cfg["n"]), int(cfg["dim"])
    rng = np.random.default_rng(seed)
    k, idim = int(cfg["n_clusters"]), int(cfg["intrinsic_dim"])
    sigma = float(cfg["cluster_spread"])
    centers = rng.normal(0.0, 1.0, (k, d)).astype(np.float32)
    basis = rng.normal(0.0, idim ** -0.5, (k, idim, d)).astype(np.float32)
    vectors = _mixture(rng, centers, basis, rng.integers(0, k, n), sigma)
    queries = _mixture(rng, centers, basis, rng.integers(0, k, n_queries),
                       sigma)

    n_tags = int(cfg["n_tags"])
    pop = 1.0 / np.arange(1, n_tags + 1, dtype=np.float64) ** float(
        cfg["zipf_a"])
    counts = rng.poisson(float(cfg["avg_tags"]), n).clip(
        1, int(cfg["max_tags"]))
    draws = rng.choice(n_tags, size=int(counts.sum()), p=pop / pop.sum())
    rec = np.repeat(np.arange(n, dtype=np.int64), counts)
    pairs = np.unique(rec * n_tags + draws)        # dedupe within a record
    tag_flat = (pairs % n_tags).astype(np.int32)
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(pairs // n_tags, minlength=n), out=offsets[1:])

    values = {}
    for f in cfg["numeric_fields"]:
        if f["dist"] == "lognormal":
            v = rng.lognormal(f["mean"], f["sigma"], n)
        elif f["dist"] == "uniform":
            v = rng.uniform(f["lo"], f["hi"], n)
        else:
            raise ValueError(f"unknown distribution {f['dist']!r}")
        values[f["name"]] = v.astype(np.float32)
    return Corpus(vectors, queries, offsets, tag_flat, values, n_tags)
