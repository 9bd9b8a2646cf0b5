"""The plain reference: exact filtered top-k by brute force.

For each query, every record that satisfies its filter (evaluated on the
raw corpus, independent of the index) is ranked by squared L2 distance.
Candidates are picked on the device in blocks of queries, with the matrix
product at ``Precision.HIGHEST``, and re-ranked on the host with float64
differences, so the top-k and its distances are exact.
``precision="bfloat16"`` is the control: the same computation on vectors
and queries rounded to bfloat16, the nearest precision below the
configuration's float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from lib.corpus import Corpus
from lib.traffic import Pool

BLOCK = 256          # queries per device block
SLACK = 32           # device candidates re-ranked on the host per query


def _round(x: np.ndarray, precision: str) -> np.ndarray:
    if precision == "float32":
        return x
    if precision == "bfloat16":
        import ml_dtypes
        return x.astype(ml_dtypes.bfloat16).astype(np.float32)
    raise ValueError(f"unknown precision {precision!r}")


def filter_arrays(c: Corpus, pool: Pool, rows: np.ndarray):
    """Per request: the tag (-1: none) and [lo, hi) per numeric field of
    the corpus (-inf, inf where the filter has no range on it)."""
    fields = list(c.values)
    tag = np.full(rows.size, -1, np.int32)
    lo = np.full((rows.size, len(fields)), -np.inf, np.float32)
    hi = np.full((rows.size, len(fields)), np.inf, np.float32)
    for j, r in enumerate(rows):
        f = pool.filters[r]
        if f["tag"] is not None:
            tag[j] = f["tag"]
        for field, a, b in f["ranges"]:
            i = fields.index(field)
            lo[j, i] = max(lo[j, i], np.float32(a))
            hi[j, i] = min(hi[j, i], np.float32(b))
    return tag, lo, hi


def padded_tags(c: Corpus) -> np.ndarray:
    """(N, T) int32 tags of each record, -1 padded."""
    counts = np.diff(c.tag_offsets)
    out = np.full((c.n, max(1, int(counts.max()))), -1, np.int32)
    col = np.arange(c.tag_flat.size) - np.repeat(c.tag_offsets[:-1], counts)
    out[np.repeat(np.arange(c.n), counts), col] = c.tag_flat
    return out


@functools.partial(jax.jit, static_argnames=("take", "low"))
def _candidates(x, xn, rec_tags, vals, q, tag, lo, hi, take: int,
                low: bool):
    if low:
        dots = jnp.dot(q.astype(jnp.bfloat16), x.astype(jnp.bfloat16).T,
                       preferred_element_type=jnp.float32)
    else:
        dots = jnp.dot(q, x.T, precision=jax.lax.Precision.HIGHEST)
    d = xn[None, :] - 2.0 * dots
    ok = tag[:, None] < 0
    for t in range(rec_tags.shape[1]):
        ok = ok | (rec_tags[None, :, t] == tag[:, None])
    for f in range(vals.shape[1]):
        v = vals[None, :, f]
        ok = ok & (v >= lo[:, None, f]) & (v < hi[:, None, f])
    d = jnp.where(ok, d, jnp.inf)
    neg, idx = jax.lax.top_k(-d, take)
    return idx, jnp.isfinite(neg)


def exact_topk(c: Corpus, pool: Pool, rows: np.ndarray, k: int,
               precision: str = "float32"):
    """(ids (len(rows), k) int64 padded -1, dists (len(rows), k) float64
    padded inf) of pool requests ``rows``."""
    low = precision != "float32"
    x = _round(c.vectors, precision)
    x64 = x.astype(np.float64)
    xn = np.einsum("ij,ij->i", x64, x64).astype(np.float32)
    dev = (jnp.asarray(x), jnp.asarray(xn), jnp.asarray(padded_tags(c)),
           jnp.asarray(np.stack(list(c.values.values()), axis=1)))
    tag, lo, hi = filter_arrays(c, pool, rows)
    q_all = _round(c.queries[rows], precision)
    take = min(SLACK, c.n)
    ids = np.full((rows.size, k), -1, np.int64)
    dists = np.full((rows.size, k), np.inf)
    for s in range(0, rows.size, BLOCK):
        e = min(s + BLOCK, rows.size)
        pad = BLOCK - (e - s)
        blk = [np.pad(a[s:e], [(0, pad)] + [(0, 0)] * (a.ndim - 1),
                      mode="edge") for a in (q_all, tag, lo, hi)]
        cand, live = _candidates(*dev, *map(jnp.asarray, blk), take=take,
                                 low=low)
        cand, live = np.asarray(cand), np.asarray(live)
        for j in range(e - s):
            cj = cand[j][live[j]]
            diff = x64[cj] - q_all[s + j].astype(np.float64)[None, :]
            dd = np.einsum("ij,ij->i", diff, diff)
            o = np.argsort(dd, kind="stable")[:k]
            ids[s + j, :o.size] = cj[o]
            dists[s + j, :o.size] = dd[o]
    return ids, dists
