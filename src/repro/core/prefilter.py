"""Speculative pre-filtering (paper §3 Fig. 3a): attribute-index scan →
in-memory PQ brute force over the superset → exact re-rank + verification.

The superset comes from ``Selector.pre_filter_approx`` (host side, pages
accounted): exact posting merges for labels, sequential sorted-index scans
for ranges, heavy-branch pruning for ANDs. The PQ scan runs on device in
fixed-size chunks (a ``lax.scan`` carrying a running top-(L+δ)) so any
selectivity fits a static shape.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pq as pq_mod
from repro.core.records import RecordStore
from repro.core.selectors import (InMemory, QueryFilter, Selector, is_member,
                                  is_member_approx)
from repro.utils.spans import span

BIG = jnp.float32(1e30)
INVALID_PENALTY = jnp.float32(1e12)
SCAN_CHUNK = 4096        # full-corpus gated-scan chunk (scan_all_gated)


@dataclasses.dataclass(frozen=True)
class PrefilterParams:
    l_rerank: int            # L + δ: vectors fetched from SSD for re-ranking
    k: int = 10
    chunk: int = 8192        # PQ-scan chunk size (static)
    max_candidates: int = 1 << 20   # superset hard cap


class PrefilterResult(NamedTuple):
    ids: jax.Array           # (B, k) verified-valid top-k (-1 pad)
    dists: jax.Array         # (B, k)
    io_pages: np.ndarray     # (B,) scan + re-rank pages (counted on the host)
    dist_comps: np.ndarray   # (B,)
    n_valid: np.ndarray      # (B,)


@functools.partial(jax.jit, static_argnames=("l_rerank", "chunk", "distance_fn"))
def _pq_topl(codes, codebook, query, cand_ids, cand_len, l_rerank: int,
             chunk: int, distance_fn: Callable = pq_mod.adc_lookup):
    """Running top-l over a padded candidate id array, chunked scan.

    cand_ids: (C,) int32 padded with -1 (C divisible by chunk).
    Returns (top_ids (l,), top_dists (l,)).
    """
    table = pq_mod.distance_table(codebook, query)
    n_chunks = cand_ids.shape[0] // chunk

    def step(carry, ids_chunk):
        top_ids, top_d = carry
        live = ids_chunk >= 0
        d = distance_fn(codes[jnp.where(live, ids_chunk, 0)], table)
        d = jnp.where(live, d, BIG)
        all_ids = jnp.concatenate([top_ids, ids_chunk])
        all_d = jnp.concatenate([top_d, d])
        neg_d, idx = jax.lax.top_k(-all_d, l_rerank)
        return (all_ids[idx], -neg_d), None

    init = (jnp.full((l_rerank,), -1, jnp.int32),
            jnp.full((l_rerank,), BIG, jnp.float32))
    (top_ids, top_d), _ = jax.lax.scan(
        step, init, cand_ids.reshape(n_chunks, chunk))
    return top_ids, top_d


def _verify_core(qf: QueryFilter, query, top_ids, vecs, rl, rv, k: int,
                 pages_std):
    """Exact distance + exact verification over already-fetched record
    fields (dead rows carry arbitrary data — fully masked by ``live``)."""
    live = top_ids >= 0
    d = vecs - query[None, :]
    ex_d = jnp.where(live, jnp.sum(d * d, axis=-1), BIG)
    ok = is_member(qf, rl, rv) & live
    key = jnp.where(ok, ex_d, BIG)
    order = jnp.argsort(key)[:k]
    ids = jnp.where(ok[order], top_ids[order], -1)
    dists = jnp.where(ok[order], ex_d[order], jnp.inf)
    io = jnp.sum(live) * pages_std
    return ids, dists, io, jnp.sum(ok)


@functools.partial(jax.jit, static_argnames=("params", "pages_std"))
def _verify_fetched(qf: QueryFilter, query, top_ids, vecs, rl, rv,
                    params: PrefilterParams, pages_std: int):
    """Verification over records fetched outside the trace (disk tier)."""
    return _verify_core(qf, query, top_ids, vecs, rl, rv, params.k,
                        pages_std)


@functools.partial(jax.jit, static_argnames=("params",))
def _rerank_verify(store: RecordStore, qf: QueryFilter, query,
                   top_ids, params: PrefilterParams):
    """Fetch top-(L+δ) records, exact distance + exact verification."""
    safe = jnp.where(top_ids >= 0, top_ids, 0)
    return _verify_core(qf, query, top_ids, store.vectors[safe],
                        store.rec_labels[safe], store.rec_values[safe],
                        params.k, store.pages_std)


@functools.partial(jax.jit,
                   static_argnames=("l_rerank", "chunk", "distance_fn"))
def scan_all_gated(codes, codebook, mem: InMemory, qf: QueryFilter, query,
                   l_rerank: int, chunk: int,
                   distance_fn: Callable = pq_mod.adc_lookup):
    """Gated full-corpus ADC scan: the serve tier's last degrade rung.

    Every id is a candidate (no posting scan, no graph traversal — one
    fused pass over the in-memory code tier), ranked by ADC distance plus
    ``INVALID_PENALTY`` where the *approximate* membership gate rejects.
    The gate is a superset test (bloom / bucket words only over-admit),
    so no truly-valid record is ever pushed behind an invalid one — the
    no-false-negative contract holds structurally; exactness comes from
    the caller's fetch + exact verify of the returned top-``l_rerank``.

    Returns ``(top_ids (l_rerank,), top_keys)``; ids whose key carries
    the penalty are approx-invalid fill (the verifier drops them).
    """
    table = pq_mod.distance_table(codebook, query)
    n = codes.shape[0]
    n_chunks = -(-n // chunk)
    pad_n = n_chunks * chunk
    ids_all = jnp.arange(pad_n, dtype=jnp.int32)

    def step(carry, ids_chunk):
        top_ids, top_d = carry
        live = ids_chunk < n
        safe = jnp.where(live, ids_chunk, 0)
        d = distance_fn(codes[safe], table)
        ok = is_member_approx(qf, safe, mem)
        d = d + jnp.where(ok, 0.0, INVALID_PENALTY)
        d = jnp.where(live, d, BIG)
        all_ids = jnp.concatenate([top_ids, ids_chunk])
        all_d = jnp.concatenate([top_d, d])
        neg_d, idx = jax.lax.top_k(-all_d, l_rerank)
        return (all_ids[idx], -neg_d), None

    init = (jnp.full((l_rerank,), -1, jnp.int32),
            jnp.full((l_rerank,), BIG, jnp.float32))
    (top_ids, top_d), _ = jax.lax.scan(
        step, init, ids_all.reshape(n_chunks, chunk))
    return top_ids, top_d


def prefilter_search(store: RecordStore, codes, codebook, selectors, qfilters,
                     queries, params: PrefilterParams,
                     distance_fn: Callable = pq_mod.adc_lookup,
                     speculative: bool = True,
                     host_fetch: Callable | None = None) -> PrefilterResult:
    """Host-driven pre-filtering for a query batch.

    ``speculative=True`` uses Selector.pre_filter_approx (partial scans,
    heavy-branch pruning); ``False`` forces exact full-constraint scans
    (the strict baseline — implemented as evaluating every branch).

    ``host_fetch`` (disk backend: ``DiskRecordStore.fetch_host``) replaces
    the device-array record gather for the re-rank: the top-(L+δ) records
    are read from slab files instead — same fields, same verification,
    bit-identical output, but through the real page cache.
    """
    B = queries.shape[0]
    out_ids, out_d = [], []
    io_pages = np.zeros(B, np.int64)
    dist_comps = np.zeros(B, np.int64)
    n_valid = np.zeros(B, np.int64)

    for b in range(B):
        sel: Selector = selectors[b]
        with span("prefilter.superset", query=b):
            if speculative:
                cand, pages = sel.pre_filter_approx()
            else:
                cand, pages = _strict_scan(sel)
            cand = cand[:params.max_candidates]
            pad = -(-max(cand.size, 1) // params.chunk) * params.chunk
            cand_padded = np.full(pad, -1, np.int32)
            cand_padded[:cand.size] = cand
        with span("prefilter.dispatch", query=b, cand=int(cand.size)):
            # index on the host: a device-side row gather is shape-keyed on
            # the raw batch width and would compile per distinct group
            # composition
            qf = jax.tree_util.tree_map(lambda x: np.asarray(x)[b], qfilters)
            top_ids, _ = _pq_topl(codes, codebook, queries[b],
                                  jnp.asarray(cand_padded), cand.size,
                                  params.l_rerank, params.chunk, distance_fn)
            if host_fetch is None:
                ids, dists, io, nv = _rerank_verify(store, qf, queries[b],
                                                    top_ids, params)
            else:
                tid = np.asarray(top_ids)
                rec = host_fetch(np.where(tid >= 0, tid, 0))
                ids, dists, io, nv = _verify_fetched(
                    qf, queries[b], top_ids, jnp.asarray(rec["vectors"]),
                    jnp.asarray(rec["rec_labels"]),
                    jnp.asarray(rec["rec_values"]), params, store.pages_std)
        out_ids.append(ids)
        out_d.append(dists)
        with span("prefilter.readback", query=b):
            io_pages[b] = pages + int(io)
            n_valid[b] = int(nv)
        dist_comps[b] = cand.size

    return PrefilterResult(
        ids=jnp.stack(out_ids), dists=jnp.stack(out_d), io_pages=io_pages,
        dist_comps=dist_comps, n_valid=n_valid)


def _strict_scan(sel: Selector) -> tuple[np.ndarray, int]:
    """Exact pre-filter: evaluate every branch (no pruning/speculation)."""
    from repro.core.selectors import (AndSelector, LabelAndSelector,
                                      LabelOrSelector, OrSelector,
                                      RangeSelector)
    if isinstance(sel, LabelAndSelector):
        merged, pages = sel._fetch_merged(sel.labels, "and")
        return merged.astype(np.int32), pages
    if isinstance(sel, LabelOrSelector):
        merged, pages = sel._fetch_merged(sel.labels, "or")
        return merged.astype(np.int32), pages
    if isinstance(sel, RangeSelector):
        ids, pages = sel._fs.scan(sel.lo, sel.hi)
        return ids.astype(np.int32), pages
    if isinstance(sel, AndSelector):
        # every branch (optional label + all range predicates), intersected
        ids, pages = _strict_scan(sel.children[0])
        for c in sel.children[1:]:
            more, p = _strict_scan(c)
            ids = np.intersect1d(ids, more)
            pages += p
        return ids.astype(np.int32), pages
    if isinstance(sel, OrSelector):
        a, pa = _strict_scan(sel.label_sel)
        b, pb = _strict_scan(sel.range_sel)
        return np.union1d(a, b).astype(np.int32), pa + pb
    return sel.pre_filter_approx()
