"""Batched best-first graph search with speculative / strict / post filtering.

This is the paper's §3–§4 search engine expressed as a shape-static JAX
program: a ``lax.while_loop`` advances every query's beam one hop per step,
so the record fetches of a whole query batch coalesce into one gather — the
TPU-native analogue of PipeANN's pipelined SSD reads (DESIGN.md §2).

Modes
-----
* ``post``      — plain traversal, dummy approx filter (always true); validity
                  checked only at verification (the loose extreme of §3).
* ``spec_in``   — speculative in-filtering: neighbors (direct + 2-hop) are
                  screened by ``is_member_approx`` against in-memory Bloom
                  words / bucket codes; up to R approx-valid neighbors are
                  kept per hop, back-filled with invalid *direct* neighbors
                  (bridge nodes). Exploration prefers possibly-valid nodes
                  even when invalid ones are geometrically closer.
* ``strict_in`` — the strict baseline (Filtered-DiskANN-like): every neighbor's
                  exact attributes are read from the record store before it may
                  enter the pool (+1 page per neighbor — the I/O bottleneck the
                  paper eliminates).

Exact verification piggybacks on the re-rank fetch: every explored record's
full vector *and* attributes arrive in the same (already-counted) pages.

Hop pipeline (docs/perf.md has the diagram)
-------------------------------------------
The hot loop is built from shape-static, near-linear primitives:

* **Probabilistic visited set** — a per-query hashed slot table (the
  device analogue of the paper's Bloom superset) replaces the pairwise
  dedup broadcasts against the pool and the explored buffer. Candidates
  are marked when *admitted* to the pool merge (entries at init); a slot
  collision only skips re-exploration of a node, it can never admit an
  invalid result (verification is exact). Below ``VISITED_SLOTS_MAX`` ids
  the table covers the id space and the set is exact.
* **Sorted-pool invariant** — the pool stays key-ascending, so the merge
  is a fixed-size concatenate + one ``top_k`` instead of a full argsort,
  and the early-termination bound (the l_valid-th verified distance) is
  tracked incrementally in a small sorted buffer instead of re-sorting
  the whole explored buffer every iteration.
* **Fused candidate pass** — PQ ADC distance + approximate membership +
  invalid-penalty key for the whole ``(B, W·(R+R_d))`` candidate slab in
  one kernel (``kernels/ops.hop_fused``); the loop itself runs genuinely
  batched (no ``vmap``) so the kernel amortizes across queries.

Pipelined execution (PR 5; docs/perf.md has the timeline)
----------------------------------------------------------
Two mechanisms restructure the loop into the paper's genuine pipeline:

* **Cross-hop prefetch (double-buffering)** — after the sorted-pool merge
  the next hop's best-W frontier is fully determined, so the loop selects
  it and issues its record fetch at the *end* of the body, carrying the
  fetched slab in loop state: hop t+1's gather overlaps hop t's fused
  candidate pass instead of heading the critical path. The fetch *set*
  and every counter are unchanged — only the issue time moves — so the
  oracle parity below still holds bit-exactly. ``SearchParams.
  prefetch_depth`` records the in-flight slab count for the modeled SSD
  latency (``io_sim.IOModel.latency_us``).
* **Straggler compaction** — :func:`run_hops` advances a batch by up to
  ``n_hops`` hops over an explicit :class:`HopState`; the host driver
  :func:`filtered_search_pipelined` re-checks the active set every chunk
  and compacts surviving queries into power-of-two buckets (B → B/2 → …,
  padded with inert rows), so late hops run at the active-set width
  instead of full B. No hop-loop op mixes query rows, so compaction is
  pure re-indexing: the driver's results are bit-identical to the
  single-shot :func:`filtered_search`.

Implementations sharing the semantics:

* :func:`filtered_search` — the fused batched pipeline in one jit
  (single-shot; also the distributed/shard_map entry).
* :func:`filtered_search_pipelined` — the bucketed host driver over
  :func:`init_search` / :func:`run_hops` / :func:`finalize_search`
  (the engine's production path).
* :func:`filtered_search_ref` — the jnp oracle: same dedup/admission
  semantics, naive primitives (``vmap`` over queries, full argsorts,
  unfused gathers). A/B parity: identical ``io_pages``/``explored``.
* :func:`filtered_search_legacy` — the pre-fused-pipeline implementation
  (pairwise dedup broadcasts, per-iteration result re-sort), kept as the
  baseline that ``benchmarks/bench_search.py`` measures speedups against.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import faults as faults_mod
from repro.core import io_sim
from repro.core import pq as pq_mod
from repro.core.faults import FaultPlan
from repro.core.records import RecordStore
from repro.core.selectors import (InMemory, QueryFilter, is_member,
                                  is_member_approx, kernel_filter_params,
                                  kernel_view, merged_table_words)
from repro.kernels import ops as kops
from repro.kernels.ref import INVALID_PENALTY   # single source (1e12)
from repro.utils.spans import span
from repro.utils.tree import tree_put_rows, tree_take_rows

BIG = jnp.float32(1e30)
VISITED_SLOTS_MAX = 1 << 20   # beyond this the visited set hashes (approx.)

DEFAULT_HOP_CHUNK = 32    # hops between the driver's compaction checks (K)
MIN_COMPACT_BUCKET = 8    # narrowest bucket worth a dedicated compile


@dataclasses.dataclass(frozen=True)
class SearchParams:
    l_search: int           # candidate pool length L
    k: int = 10
    beam_width: int = 1     # W records fetched per hop (pipelined I/O analogue)
    max_hops: int = 256
    mode: str = "spec_in"   # 'post' | 'spec_in' | 'strict_in'
    l_valid: int = 0        # early-exit once this many verified-valid found
                            # (0 -> defaults to l_search)
    prefetch_depth: int = 2  # record slabs in flight per query: 1 models
                            # the serial issue order, 2 the double-buffered
                            # loop (next hop's fetch issued behind the
                            # current hop's compute), >2 widens the disk
                            # tier's real read-ahead window
                            # (storage/disk.py). The executed fetch set is
                            # identical at any depth — the knob feeds
                            # io_sim.IOModel.latency_us and the cache
                            # warmer, never results.
    fault_plan: FaultPlan | None = None
                            # seeded fault injection on the frontier slab
                            # reads (core/faults.py): failed/corrupted
                            # reads walk the retry→hedge→degrade ladder.
                            # None (or an all-zero-rate plan) traces the
                            # unmodified hot path — the plan is static, so
                            # the clean compile carries zero fault ops.

    def __post_init__(self):
        assert self.mode in ("post", "spec_in", "strict_in")
        # depth is bounded by the modeled device queue depth: more slabs
        # in flight than the device sustains would claim overlap the
        # latency model (and the real read-ahead) cannot deliver
        assert 1 <= self.prefetch_depth <= io_sim.IOModel.parallelism, (
            f"prefetch_depth={self.prefetch_depth} outside "
            f"[1, IOModel.parallelism={io_sim.IOModel.parallelism}]")


class SearchResult(NamedTuple):
    ids: jax.Array          # (B, k) int32 — verified-valid top-k (-1 pad)
    dists: jax.Array        # (B, k) float32 exact distances
    io_pages: jax.Array     # (B,) int32 pages fetched
    hops: jax.Array         # (B,) int32 beam-loop iterations
    dist_comps: jax.Array   # (B,) int32 PQ distance computations
    approx_checks: jax.Array  # (B,) int32 is_member_approx evaluations
    n_valid: jax.Array      # (B,) int32 verified-valid results found
    fp_explored: jax.Array  # (B,) int32 explored records that verified invalid
    explored: jax.Array     # (B,) int32 records fetched & exact-verified
    faults: jax.Array       # (B,) int32 injected fault events encountered
                            # (failed/corrupted attempts + latency spikes)
    retries: jax.Array      # (B,) int32 extra read attempts issued
                            # (retries + hedged reads)
    degraded: jax.Array     # (B,) int32 rows that exhausted the ladder and
                            # fell back to PQ-approximate distance/validity


def _exact_sq_dist(vecs, q):
    d = vecs - q[None, :]
    return jnp.sum(d * d, axis=-1)


def local_fetch(store: RecordStore, ids: jax.Array) -> dict:
    """Single-host record fetch: plain gathers.

    ``ids`` may be any shape — the batched hop loop passes one flat
    ``(B·W,)`` vector per hop so the whole batch's reads coalesce. The
    distributed engine (core/distributed.py) swaps in a psum-combined
    sharded fetch honouring the same contract, ``cand_first`` included
    (stores without the precompute omit the key and the search falls
    back to the on-the-fly dedup). Unused keys cost nothing: XLA dead-code
    eliminates gathers whose results a mode never consumes."""
    rec = {
        "vectors": store.vectors[ids],
        "neighbors": store.neighbors[ids],
        "dense_neighbors": store.dense_neighbors[ids],
        "rec_labels": store.rec_labels[ids],
        "rec_values": store.rec_values[ids],
    }
    if store.cand_first is not None:
        rec["cand_first"] = store.cand_first[ids]
    return rec


# ---------------------------------------------------------------------------
# Hop-pipeline primitives
# ---------------------------------------------------------------------------

def _visited_spec(n_ids: int) -> tuple[int, int]:
    """(n_slots, shift) for the visited slot table over ``n_ids`` ids.

    Exact (identity-indexed) while the id space fits in VISITED_SLOTS_MAX
    slots; hashed (multiply-shift) beyond — false positives then skip
    re-exploration of the colliding node (Bloom-superset semantics), never
    break result validity."""
    bits = max(8, int(max(n_ids - 1, 1)).bit_length())
    bits = min(bits, VISITED_SLOTS_MAX.bit_length() - 1)
    return 1 << bits, 32 - bits


def _visited_slot(ids: jax.Array, n_ids: int) -> jax.Array:
    n_slots, shift = _visited_spec(n_ids)
    if n_slots >= n_ids:
        return ids
    h = ids.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
    return (h >> shift).astype(jnp.int32)


def _first_occurrence(cand: jax.Array, live: jax.Array,
                      n_ids: int) -> jax.Array:
    """True at the first slab-order occurrence of each id (last axis).

    Exact intra-slab dedup in O(C log C) — the 2-hop sample repeats ids
    and W beams collide; the legacy path paid an O(C²) pairwise tril
    broadcast for the same mask. ``(id, position)`` pairs pack into one
    int32 so a single-key sort + binary search replaces the variadic
    sort + argsort + invert dance (XLA's CPU variadic sort is a scalar
    loop — the packed form is ~7× faster there, and no worse on TPU);
    past ~2^31/C ids the packing would overflow and the exact two-key
    sort takes over (static branch)."""
    c = cand.shape[-1]
    key = jnp.where(live, cand, n_ids)
    pos = jnp.broadcast_to(jnp.arange(c, dtype=jnp.int32), key.shape)
    if (n_ids + 1) * c >= 2 ** 31:
        # packed key would overflow int32 (and int64 silently truncates
        # without jax_enable_x64): fall back to the exact two-key sort.
        # Slower per hop, but only reachable past ~2^31/C ids.
        skey, spos = jax.lax.sort((key, pos), num_keys=2)
        prev = jnp.concatenate(
            [jnp.full(skey.shape[:-1] + (1,), -2, skey.dtype),
             skey[..., :-1]], axis=-1)
        first_sorted = skey != prev
        inv = jnp.argsort(spos, axis=-1)
        return jnp.take_along_axis(first_sorted, inv, axis=-1)
    packed = key * c + pos
    sp = jnp.sort(packed, axis=-1)
    # leftmost occurrence of each key: unrolled binary search over the
    # packed keys (cheaper than vmapped searchsorted on CPU)
    tgt = key * c
    lo = jnp.zeros_like(tgt)
    hi = jnp.full_like(tgt, c)
    # c.bit_length() halvings collapse the [lo, hi) range of width c to
    # empty; one fewer leaves a 1-wide range when c is a power of two
    for _ in range(c.bit_length()):
        mid = (lo + hi) >> 1
        v = jnp.take_along_axis(sp, mid, axis=-1)
        right = v < tgt
        lo = jnp.where(right, mid + 1, lo)
        hi = jnp.where(right, hi, mid)
    firstpos = jnp.take_along_axis(sp, jnp.minimum(lo, c - 1), axis=-1) % c
    return firstpos == pos


def _slab_pq(codes: jax.Array, ids: jax.Array, tables: jax.Array) -> jax.Array:
    """Batched ADC distances for a gathered candidate slab.

    codes (N, M); ids (B, S); tables (B, M, K) -> (B, S) float32.
    Delegates to the single bitwise-pinned gather+reduce in
    ``kernels.ref.adc_slab_ref`` (== ``pq.adc_lookup`` values)."""
    from repro.kernels.ref import adc_slab_ref
    return adc_slab_ref(codes[ids], tables)


# ---------------------------------------------------------------------------
# Pipelined search state
# ---------------------------------------------------------------------------

class QueryCtx(NamedTuple):
    """Per-query constants of one search call (leading dim B).

    Built once by :func:`init_search`. The bucketed driver gathers query
    rows out of it when compacting stragglers, so every per-query input
    the hop loop reads must live here rather than be re-derived inside
    the loop."""
    queries: jax.Array        # (B, D) float32
    tables: jax.Array         # (B, M, ksub) ADC distance tables
    qf: QueryFilter           # batched filter pytree
    merged_tbl: jax.Array     # (B, ceil((n_ids+1)/32)) int32 word-packed
                              # rare-list bitmap ((B, 1) dummy outside
                              # spec_in) — see selectors.merged_table_words


class HopState(NamedTuple):
    """Per-query mutable search state carried across hops (leading dim B).

    ``cur_ids``/``cur_live`` hold the *already-selected* next frontier
    whose record fetch is in flight (cross-hop prefetch): the loop body
    consumes the carried slab, merges, selects the following frontier and
    issues its fetch at the END of the body. No hop-loop operation mixes
    query rows, so gathering/scattering rows of this pytree (straggler
    compaction) leaves each query's trajectory bit-identical."""
    pool_ids: jax.Array       # (B, P) int32
    pool_key: jax.Array       # (B, P) float32, key-ascending
    pool_exp: jax.Array       # (B, P) bool
    visited: jax.Array        # (B, n_slots // 32) int32 bit-words
                              # (kernels/or_scatter.py sets, shift+mask
                              # reads — 8× smaller than the former
                              # byte-per-slot bool table)
    res_ids: jax.Array        # (B, res_cap) int32
    res_d: jax.Array          # (B, res_cap) float32
    res_valid: jax.Array      # (B, res_cap) bool
    vtop: jax.Array           # (B, l_valid) float32 sorted valid top-l
    n_okc: jax.Array          # (B,) int32
    counters: jax.Array       # (B, 7) int32: io, dist, approx, hops,
                              #               faults, retries, degraded
    active: jax.Array         # (B,) bool
    cur_ids: jax.Array        # (B, W) int32 — prefetched frontier
    cur_live: jax.Array       # (B, W) bool


def _select_frontier(pool_ids, pool_key, pool_exp, active, W: int, P: int):
    """Best-W unexplored pool rows (sorted pool ⇒ one top_k), marked
    explored — gated by ``active`` exactly like the pre-pipelined loop
    head. Returns (cur_ids, cur_live, pool_exp')."""
    B = pool_ids.shape[0]
    bW = jnp.arange(B, dtype=jnp.int32)[:, None]
    masked = jnp.where(pool_exp, BIG, pool_key)
    negk, sel = jax.lax.top_k(-masked, W)                  # (B, W)
    cur_ids = jnp.take_along_axis(pool_ids, sel, 1)
    cur_live = (-negk < BIG) & active[:, None]
    pool_exp = pool_exp.at[
        bW, jnp.where(active[:, None], sel, P)].set(True, mode="drop")
    return cur_ids, cur_live, pool_exp


def _init(store, codes, codebook, mem, qfilters, queries, entry, params,
          distance_fn, entries):
    """Seed the pool/visited/result state and select the first frontier."""
    p = params
    l_valid = p.l_valid or p.l_search
    P, W = p.l_search, p.beam_width
    res_cap = p.max_hops * W                     # explored-record buffer
    B, D = queries.shape
    n_ids = codes.shape[0]
    n_slots, _ = _visited_spec(n_ids)
    if entries is None:
        entries = jnp.full((B, 1), entry, jnp.int32)
    E = entries.shape[1]
    assert E <= P, "entry seeds exceed the pool length"

    tables = jax.vmap(lambda q: pq_mod.distance_table(codebook, q))(queries)
    if p.mode == "spec_in":
        # rare-list membership as a per-query word-packed bitmap, built
        # once: one OR-scatter replaces a (B, W·C)-wide binary search
        # over the CAP-length merged list every hop
        # (selectors.merged_table_words)
        merged_tbl = merged_table_words(qfilters, n_ids)
    else:
        merged_tbl = jnp.zeros((B, 1), jnp.int32)

    # ---- entry seeding (pool kept key-ascending from the start) ----
    ent_valid = entries >= 0
    safe_ent = jnp.where(ent_valid, entries, 0)
    entry_d = jax.vmap(distance_fn)(codes[safe_ent], tables)       # (B, E)
    entry_ok = jax.vmap(is_member_approx, in_axes=(0, 0, None))(
        qfilters, safe_ent, mem) & ent_valid
    entry_key = jnp.where(
        ent_valid, entry_d + jnp.where(entry_ok, 0.0, INVALID_PENALTY), BIG)
    order0 = jnp.argsort(entry_key, axis=1)
    pool_ids = jnp.full((B, P), -1, jnp.int32).at[:, :E].set(
        jnp.take_along_axis(jnp.where(ent_valid, entries, -1), order0, 1))
    pool_key = jnp.full((B, P), BIG, jnp.float32).at[:, :E].set(
        jnp.take_along_axis(entry_key, order0, 1))
    pool_exp = jnp.ones((B, P), jnp.bool_).at[:, :E].set(
        jnp.take_along_axis(~ent_valid, order0, 1))

    # n_slots is 2^bits with bits >= 8, so the word table divides evenly;
    # the n_slots sentinel is out of range and drops in the OR-scatter
    visited = kops.or_scatter(
        jnp.zeros((B, n_slots // 32), jnp.int32),
        jnp.where(ent_valid, _visited_slot(safe_ent, n_ids), n_slots))

    res_ids = jnp.full((B, res_cap), -1, jnp.int32)
    res_d = jnp.full((B, res_cap), BIG, jnp.float32)
    res_valid = jnp.zeros((B, res_cap), jnp.bool_)
    vtop = jnp.full((B, l_valid), BIG, jnp.float32)   # sorted valid top-l
    n_okc = jnp.zeros((B,), jnp.int32)
    # io, dist_comps, approx, hops, faults, retries, degraded
    counters = jnp.zeros((B, 7), jnp.int32)
    active = jnp.any(~pool_exp & (pool_key < BIG), axis=1)

    cur_ids, cur_live, pool_exp = _select_frontier(
        pool_ids, pool_key, pool_exp, active, W, P)
    st = HopState(pool_ids, pool_key, pool_exp, visited, res_ids, res_d,
                  res_valid, vtop, n_okc, counters, active, cur_ids,
                  cur_live)
    return QueryCtx(queries, tables, qfilters, merged_tbl), st


def _hop_step(store, codes, mem, params, distance_fn, fetch_fn, ctx, mc,
              st, rec) -> "HopState":
    """Consume the in-flight record slab for one hop, merge, and select
    the next frontier. Steps keep the pre-pipelined numbering (the fetch
    that used to be step 2 now happens at the end of the previous
    iteration — same records, same counters, earlier issue). Each stage
    runs under a ``pipeann.hop.<stage>`` name scope, so device ops carry
    their stage in the profiler's trace."""
    p = params
    l_valid = p.l_valid or p.l_search
    P, W = p.l_search, p.beam_width
    R = store.degree
    Rd = store.dense_degree if p.mode == "spec_in" else 0
    C = R + Rd                                   # candidates per beam row
    res_cap = p.max_hops * W
    rec_pages = store.pages_dense if p.mode == "spec_in" else store.pages_std
    n_ids = codes.shape[0]
    n_slots, _ = _visited_spec(n_ids)
    (pool_ids, pool_key, pool_exp, visited, res_ids, res_d, res_valid,
     vtop, n_okc, counters, active, cur_ids, cur_live) = st
    queries, tables, qfilters, merged_tbl = ctx
    B, D = queries.shape
    bW = jnp.arange(B, dtype=jnp.int32)[:, None]
    w_iota = jnp.arange(W, dtype=jnp.int32)[None, :]
    is_direct = jnp.concatenate(
        [jnp.ones((R,), bool), jnp.zeros((Rd,), bool)])
    hops = counters[:, 3]

    with jax.named_scope("pipeann.hop.fetch"):
        # ---- 2'. the carried slab (fetched at the end of the previous
        # iteration / by the loop prologue) ----
        vecs = rec["vectors"].reshape(B, W, D)
        nbrs = rec["neighbors"].reshape(B, W, R)
        rl = rec["rec_labels"].reshape(B, W, -1)
        rv = rec["rec_values"].reshape(B, W, -1)
        io = counters[:, 0] + jnp.sum(cur_live, axis=1) * rec_pages

        # the fused kernel computes the ADC distance itself (bitwise equal
        # to pq.adc_lookup); a non-default distance_fn routes every slab
        # through the caller's function instead, keeping A/B parity with
        # the oracle — resolved statically, no cost on the default path
        default_dist = distance_fn is pq_mod.adc_lookup

        def slab_dist(ids_slab):
            if default_dist:
                return _slab_pq(codes, ids_slab, tables)
            return jax.vmap(distance_fn)(codes[ids_slab], tables)

        # ---- 2''. fault ladder on the slab read (core/faults.py) ----
        # Retry → hedge → degrade. Every decision is a stateless hash of
        # (record id, that query's own hop counter, attempt), so the
        # bucketed compaction driver can gather rows into any order and no
        # draw changes — pipelined stays bit-identical to single-shot under
        # the same plan. Rows whose every attempt drew bad are "degraded".
        plan = p.fault_plan
        faults_c = counters[:, 4]
        retries_c = counters[:, 5]
        degraded_c = counters[:, 6]
        if plan is not None and plan.reads_faulty:
            ids_safe = jnp.where(cur_live, cur_ids, 0)
            hcol = hops[:, None]
            pending = (faults_mod.read_attempt_bad(ids_safe, hcol, 0, plan)
                       & cur_live)
            n_faults = jnp.sum(pending, axis=1)
            n_retries = jnp.zeros_like(n_faults)
            for a in range(1, plan.attempts):
                n_retries = n_retries + jnp.sum(pending, axis=1)
                pending = pending & faults_mod.read_attempt_bad(
                    ids_safe, hcol, a, plan)
                n_faults = n_faults + jnp.sum(pending, axis=1)
            degraded_rows = pending
            spikes = faults_mod.read_spike(ids_safe, hcol, plan) & cur_live
            faults_c = faults_c + n_faults + jnp.sum(spikes, axis=1)
            retries_c = retries_c + n_retries
            degraded_c = degraded_c + jnp.sum(degraded_rows, axis=1)
            io = io + n_retries * rec_pages        # each retry re-reads pages
        else:
            degraded_rows = None

    with jax.named_scope("pipeann.hop.rerank_verify"):
        # ---- 3. re-rank + piggybacked exact verification ----
        diff = vecs - queries[:, None, :]
        ex_d = jnp.where(cur_live, jnp.sum(diff * diff, axis=-1), BIG)
        ex_ok = jax.vmap(is_member)(qfilters, rl, rv) & cur_live
        if degraded_rows is not None:
            # a degraded row never saw its record: fall back to the
            # in-memory tier — ADC distance and approx membership, a
            # no-false-negative superset, so a valid result is approximated
            # rather than dropped (verification stays post-hoc per paper)
            deg_d = jnp.where(cur_live, slab_dist(ids_safe), BIG)
            deg_ok = jax.vmap(is_member_approx, in_axes=(0, 0, None))(
                qfilters, ids_safe, mem) & cur_live
            ex_d = jnp.where(degraded_rows, deg_d, ex_d)
            ex_ok = jnp.where(degraded_rows, deg_ok, ex_ok)
        pos = jnp.where(active[:, None], hops[:, None] * W + w_iota, res_cap)
        res_ids = res_ids.at[bW, pos].set(
            jnp.where(cur_live, cur_ids, -1), mode="drop")
        res_d = res_d.at[bW, pos].set(ex_d, mode="drop")
        res_valid = res_valid.at[bW, pos].set(ex_ok, mode="drop")
        # incremental early-termination bound: merge the W new verified
        # distances into the sorted top-l_valid buffer (no res re-sort)
        vtop = -jax.lax.top_k(
            -jnp.concatenate([vtop, jnp.where(ex_ok, ex_d, BIG)], axis=1),
            l_valid)[0]
        n_okc = n_okc + jnp.sum(ex_ok, axis=1)

    with jax.named_scope("pipeann.hop.dedup"):
        # ---- 4. candidate slab + visited-set dedup ----
        if p.mode == "spec_in":
            dn = rec["dense_neighbors"].reshape(B, W, Rd)
            cand = jnp.concatenate([nbrs, dn], axis=2)     # (B, W, C)
        else:
            cand = nbrs
        expand_live = (cur_live if degraded_rows is None
                       else cur_live & ~degraded_rows)
        cand = jnp.where(expand_live[:, :, None], cand, -1).reshape(B, W * C)
        live = cand >= 0
        safe_cand = jnp.where(live, cand, 0)
        slots = _visited_slot(safe_cand, n_ids)
        seen = ((jnp.take_along_axis(visited, slots >> 5, axis=1)
                 >> (slots & 31)) & 1).astype(jnp.bool_)
        if W == 1 and "cand_first" in rec:
            # W=1: the slab is exactly one record's candidate list, whose
            # intra-slab duplicate structure is query-independent — read the
            # precomputed mask off the record (records.candidate_first_mask)
            # instead of paying the packed-sort dedup per hop. Bit-identical
            # to _first_occurrence on the one-row slab; the first C columns of
            # the [nbrs ++ dense] mask are the nbrs-only mask (prefix
            # property), so post/strict slice cleanly.
            first = rec["cand_first"].reshape(B, -1)[:, :C]
        else:
            first = _first_occurrence(cand, live, n_ids)
        fresh = live & ~seen & first

    with jax.named_scope("pipeann.hop.fused_pass"):
        # ---- 5. fused candidate pass (distance + membership + key) ----
        if p.mode == "post":
            ok = fresh
            key_slab = slab_dist(safe_cand)
            approx_c = counters[:, 2]
        elif p.mode == "spec_in":
            if default_dist:
                bl_i32, bc_i32, (f_scal, f_om, f_rf, f_blo, f_bhi) = mc
                in_merged = ((jnp.take_along_axis(merged_tbl, safe_cand >> 5,
                                                  axis=1)
                              >> (safe_cand & 31)) & 1).astype(jnp.bool_)
                key_slab, ok_approx = kops.hop_fused(
                    codes[safe_cand], bl_i32[safe_cand], bc_i32[safe_cand],
                    in_merged, tables, f_scal, f_om, f_rf, f_blo, f_bhi)
            else:
                ok_approx = jax.vmap(is_member_approx, in_axes=(0, 0, None))(
                    qfilters, safe_cand, mem)
                key_slab = slab_dist(safe_cand) + jnp.where(
                    ok_approx, 0.0, INVALID_PENALTY)
            ok = ok_approx & fresh
            approx_c = counters[:, 2] + jnp.sum(live, axis=1)
        else:  # strict_in: read every fresh neighbor's attrs from "SSD"
            if getattr(fetch_fn, "wants_ctx", False):
                # disk tier: consult the device-resident bloom/bucket words
                # BEFORE any attribute page is read (paper's gated I/O). The
                # gate is a no-false-negative superset, so a gated-out row's
                # poisoned attributes (labels −1, values NaN) fail exact
                # membership exactly where the real attributes would —
                # bit-identical results, measurably fewer page reads
                # (snapshot counters: gated_skips / attr_probes)
                gate = jax.vmap(is_member_approx, in_axes=(0, 0, None))(
                    qfilters, safe_cand, mem)
                nrec = fetch_fn(store, safe_cand.reshape(-1),
                                need=fresh.reshape(-1),
                                gate=gate.reshape(-1), attrs_only=True)
            else:
                nrec = fetch_fn(store, safe_cand.reshape(-1))
            n_rl = nrec["rec_labels"].reshape(B, W * C, -1)
            n_rv = nrec["rec_values"].reshape(B, W * C, store.n_fields)
            ok = jax.vmap(is_member)(qfilters, n_rl, n_rv) & fresh
            io = io + jnp.sum(fresh, axis=1)               # 1 page / neighbor
            key_slab = slab_dist(safe_cand)
            approx_c = counters[:, 2]

    with jax.named_scope("pipeann.hop.slot_select"):
        # ---- 6. slot selection: up to R approx-valid, bridge back-fill ----
        if p.mode == "spec_in":
            okr = ok.reshape(B, W, C)
            fill = (fresh.reshape(B, W, C) & ~okr
                    & is_direct[None, None, :])
            rank_ok = jnp.cumsum(okr.astype(jnp.int32), axis=2) - 1
            rank_fill = jnp.cumsum(fill.astype(jnp.int32), axis=2) - 1
            n_ok_row = jnp.sum(okr, axis=2, keepdims=True)
            order_key = jnp.where(
                okr, rank_ok.astype(jnp.float32),
                jnp.where(fill, (n_ok_row + rank_fill).astype(jnp.float32),
                          BIG))
            _, take = jax.lax.top_k(-order_key, R)         # (B, W, R)
            sel_ok = jnp.take_along_axis(okr, take, 2).reshape(B, W * R)
            sel_fill = jnp.take_along_axis(fill, take, 2).reshape(B, W * R)
            sel_live = sel_ok | sel_fill
            sel_ids = jnp.take_along_axis(
                cand.reshape(B, W, C), take, 2).reshape(B, W * R)
            sel_key = jnp.take_along_axis(
                key_slab.reshape(B, W, C), take, 2).reshape(B, W * R)
            new_ids = jnp.where(sel_live, sel_ids, -1)
            new_key = jnp.where(sel_live, sel_key, BIG)
        else:
            sel_live = ok
            new_ids = jnp.where(ok, cand, -1)
            new_key = jnp.where(ok, key_slab, BIG)
        dist_c = counters[:, 1] + jnp.sum(sel_live, axis=1)
        # mark *admitted* candidates visited (pool entries are marked from
        # init, explored ones were admitted earlier): a fresh candidate
        # that loses slot selection stays unmarked and may be re-proposed
        # through another parent — the legacy pool/explored-membership
        # dedup behaves the same way
        visited = kops.or_scatter(
            visited,
            jnp.where(sel_live,
                      _visited_slot(jnp.where(sel_live, new_ids, 0), n_ids),
                      n_slots))

    with jax.named_scope("pipeann.hop.merge"):
        # ---- 7. sorted-pool merge: concatenate + one top_k ----
        all_key = jnp.concatenate([pool_key, new_key], axis=1)
        negm, midx = jax.lax.top_k(-all_key, P)
        pool_key = -negm
        pool_ids = jnp.take_along_axis(
            jnp.concatenate([pool_ids, new_ids], axis=1), midx, 1)
        pool_exp = jnp.take_along_axis(
            jnp.concatenate(
                [pool_exp, jnp.zeros(new_ids.shape, jnp.bool_)], axis=1),
            midx, 1)

    with jax.named_scope("pipeann.hop.terminate"):
        # ---- 8. per-query termination ----
        hops_new = hops + active.astype(jnp.int32)
        frontier = jnp.any(~pool_exp & (pool_key < BIG), axis=1)
        best_unexp = jnp.min(jnp.where(pool_exp, BIG, pool_key), axis=1)
        settled = (n_okc >= l_valid) & (best_unexp > vtop[:, l_valid - 1])
        active = active & (hops_new < p.max_hops) & frontier & ~settled
        counters = jnp.stack([io, dist_c, approx_c, hops_new, faults_c,
                              retries_c, degraded_c], axis=1)

    with jax.named_scope("pipeann.hop.frontier"):
        # ---- 1'. select the NEXT frontier (its fetch is issued right after
        # this step returns — the cross-hop prefetch) ----
        cur_ids, cur_live, pool_exp = _select_frontier(
            pool_ids, pool_key, pool_exp, active, W, P)
    return HopState(pool_ids, pool_key, pool_exp, visited, res_ids, res_d,
                    res_valid, vtop, n_okc, counters, active, cur_ids,
                    cur_live)


def _hop_loop(store, codes, mem, params, distance_fn, fetch_fn, ctx, st,
              n_hops, active_any=jnp.any) -> "HopState":
    """Run up to ``n_hops`` double-buffered hops over ``st``.

    The body consumes the carried slab, then issues the next frontier's
    fetch as its last action — the slab rides the loop carry, so hop
    t+1's gather sits behind hop t's candidate pass in program order
    (``prefetch_depth`` = 2 slabs in flight).

    ``active_any`` reduces the per-row active mask to the loop-level
    "keep hopping" scalar. It is evaluated in the loop *body* and carried
    (identical value to re-deriving it in the condition — the state is
    unchanged between body end and condition), because the sharded runner
    substitutes a psum-based global any and collectives are not legal in
    a ``while_loop`` condition: under ``shard_map`` every shard must take
    the same number of iterations, with settled shards hopping inertly
    (inactive rows are exact fixed points of the hop step) until the
    *global* active set drains."""
    p = params
    if p.mode == "spec_in" and distance_fn is pq_mod.adc_lookup:
        bl_i32, bc_i32 = kernel_view(mem)
        mc = (bl_i32, bc_i32, kernel_filter_params(ctx.qf))
    else:
        mc = None

    # extended fetch protocol (storage/disk.py): a fetch_fn marked
    # ``wants_ctx`` receives per-row hop counters (the disk tier's fault
    # draws must key on the same (id, hop) pairs as the traced ladder),
    # row liveness (dead rows skip real I/O), and the record flavor —
    # resolved statically, so the default local/distributed fetch traces
    # exactly as before
    ctx_fetch = getattr(fetch_fn, "wants_ctx", False)

    def issue(st):
        with jax.named_scope("pipeann.hop.fetch"):
            ids = jnp.where(st.cur_live, st.cur_ids, 0).reshape(-1)
            if ctx_fetch:
                return fetch_fn(store, ids,
                                hops=jnp.repeat(st.counters[:, 3],
                                                p.beam_width),
                                live=st.cur_live.reshape(-1),
                                dense=(p.mode == "spec_in"))
            return fetch_fn(store, ids)

    def cond(carry):
        st, _, i, g = carry
        return g & (i < n_hops)

    def body(carry):
        st, rec, i, _ = carry
        st = _hop_step(store, codes, mem, p, distance_fn, fetch_fn, ctx,
                       mc, st, rec)
        return st, issue(st), i + 1, active_any(st.active)

    st, _, _, _ = jax.lax.while_loop(
        cond, body, (st, issue(st), jnp.int32(0), active_any(st.active)))
    return st


def _finalize(st: "HopState", params: SearchParams) -> SearchResult:
    """Top-k verified-valid by exact distance (once, outside the loop)."""
    p = params
    final_key = jnp.where(st.res_valid, st.res_d, BIG)
    _, order = jax.lax.top_k(-final_key, p.k)
    top_valid = jnp.take_along_axis(st.res_valid, order, 1)
    out_ids = jnp.where(top_valid,
                        jnp.take_along_axis(st.res_ids, order, 1), -1)
    out_d = jnp.where(top_valid, jnp.take_along_axis(st.res_d, order, 1),
                      jnp.inf)
    n_valid = jnp.sum(st.res_valid, axis=1)
    n_explored = jnp.sum(st.res_ids >= 0, axis=1)
    fp = jnp.sum((st.res_ids >= 0) & ~st.res_valid, axis=1)
    c = st.counters
    return SearchResult(out_ids, out_d, c[:, 0], c[:, 3], c[:, 1], c[:, 2],
                        n_valid, fp, n_explored, c[:, 4], c[:, 5], c[:, 6])


# ---------------------------------------------------------------------------
# Fused batched pipeline (single-shot jit)
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("params", "distance_fn", "fetch_fn"))
def filtered_search(store: RecordStore, codes: jax.Array,
                    codebook: pq_mod.PQCodebook, mem: InMemory,
                    qfilters: QueryFilter, queries: jax.Array, entry: int,
                    params: SearchParams,
                    distance_fn: Callable = pq_mod.adc_lookup,
                    fetch_fn: Callable = local_fetch,
                    entries: jax.Array | None = None) -> SearchResult:
    """Run the filtered beam search for a batch of queries (one jit).

    codes: (N, M) uint8 PQ codes (the replicated in-memory tier — its
    leading dim, not the possibly-sharded record store's, defines the
    global id space).
    qfilters: batched QueryFilter (leading dim B).
    entries: optional (B, E) int32 per-query entry seeds (-1 pad; each row
    must hold distinct ids). Defaults to the shared ``entry`` (medoid).
    Strict in-filtering passes exactly-valid seeds here — the query-time
    analogue of Filtered-DiskANN's precomputed per-label entry points —
    because its valid-only pool dies immediately when the medoid's
    neighborhood contains no valid record.

    ``filtered_search_pipelined`` runs the same init/hop/finalize code
    through the chunked runner with straggler compaction (bit-identical
    results); this single-shot form stays the distributed/shard_map entry
    and the compaction-parity oracle.
    """
    ctx, st = _init(store, codes, codebook, mem, qfilters, queries, entry,
                    params, distance_fn, entries)
    st = _hop_loop(store, codes, mem, params, distance_fn, fetch_fn, ctx,
                   st, params.max_hops)
    return _finalize(st, params)


# ---------------------------------------------------------------------------
# Chunked runner + bucketed straggler-compaction driver
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("params", "distance_fn"))
def init_search(store: RecordStore, codes: jax.Array,
                codebook: pq_mod.PQCodebook, mem: InMemory,
                qfilters: QueryFilter, queries: jax.Array, entry: int,
                params: SearchParams,
                distance_fn: Callable = pq_mod.adc_lookup,
                entries: jax.Array | None = None):
    """Build ``(QueryCtx, HopState)`` for a batch — the seeding half of
    :func:`filtered_search`, exposed so the bucketed driver owns the hop
    loop. Compiles once per (shapes, params)."""
    return _init(store, codes, codebook, mem, qfilters, queries, entry,
                 params, distance_fn, entries)


@functools.partial(jax.jit,
                   static_argnames=("params", "distance_fn", "fetch_fn"),
                   donate_argnames=("st",))
def run_hops(store: RecordStore, codes: jax.Array, mem: InMemory,
             ctx: QueryCtx, st: HopState, n_hops, params: SearchParams,
             distance_fn: Callable = pq_mod.adc_lookup,
             fetch_fn: Callable = local_fetch):
    """Advance every active query by up to ``n_hops`` hops.

    ``n_hops`` is traced, so one compile covers every chunk length at a
    given batch width: the bucket jit cache is keyed only by (bucket
    shapes, params) — asserted by the compile-count test. ``st`` is
    donated: chunk t's state buffers are reused in place by chunk t+1.

    Returns ``(state, active_mask)``. The mask is an int8 *copy* of
    ``state.active`` in its own output buffer (the dtype change forbids
    any aliasing with the donated state), so the driver can dispatch the
    next chunk — consuming ``state`` — and only then read the mask back,
    overlapping the host sync with device work (the async readback)."""
    st = _hop_loop(store, codes, mem, params, distance_fn, fetch_fn, ctx,
                   st, n_hops)
    return st, st.active.astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("params",))
def finalize_search(st: HopState, params: SearchParams) -> SearchResult:
    """Extract the SearchResult from a settled (or hop-capped) state."""
    return _finalize(st, params)


def _pow2_at_least(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length() if n > 1 else 1


def filtered_search_pipelined(store: RecordStore, codes: jax.Array,
                              codebook: pq_mod.PQCodebook, mem: InMemory,
                              qfilters: QueryFilter, queries: jax.Array,
                              entry: int, params: SearchParams,
                              distance_fn: Callable = pq_mod.adc_lookup,
                              fetch_fn: Callable = local_fetch,
                              entries: jax.Array | None = None,
                              hop_chunk: int = DEFAULT_HOP_CHUNK,
                              min_bucket: int = MIN_COMPACT_BUCKET,
                              async_readback: bool = True,
                              runner=None):
    """Bucketed host driver: chunked hops + straggler compaction.

    Runs :func:`run_hops` ``hop_chunk`` hops at a time; after every chunk
    the still-active queries are counted on the host and, when they fit a
    smaller power-of-two bucket (≥ ``min_bucket``), compacted into it —
    settled rows fold back into the full-width state, pads (repeats of a
    live row, forced inactive) fill the bucket. Late hops therefore run
    at the straggler-set width instead of full B, while every query's
    trajectory stays bit-identical to single-shot
    :func:`filtered_search` (no hop-loop op mixes rows). Each bucket
    width compiles once and is reused across calls/chunks (the Session
    repeat-search path).

    ``async_readback`` (the default) overlaps the per-chunk host sync
    with device work: the driver dispatches the *next* chunk before
    reading the previous chunk's active mask (``copy_to_host_async``),
    so settle/shrink decisions run one chunk late on a stale mask. This
    is safe bit-wise: ``active`` only ever shrinks, so the stale mask is
    a superset of the truly-active rows, and inactive rows are exact
    fixed points of the hop step — a speculative chunk over a partially
    settled bucket does identical work for live rows and none for
    settled ones. ``async_readback=False`` keeps the synchronous
    reference driver (one blocking readback per chunk).

    ``hop_chunk=0`` falls back to the single-shot jit. The host stages
    run under ``pipeann.hops.*`` spans (``utils/spans.py``): ``init``,
    ``dispatch`` per chunk (with the observed ``active`` count, the
    ``bucket`` width and the ``hop`` reached; in async mode the
    observation lags dispatch by one chunk), ``readback`` of a mask,
    ``compact`` and ``finalize``.

    ``runner`` (a ``distributed.ShardedSearchRunner``) swaps the hop
    kernel for its shard_map'd equivalent over the mesh-sharded record
    store: init/finalize and this driver's whole compaction/bucket logic
    run unchanged on the replicated query state, only the chunked hop
    call crosses the mesh (``fetch_fn`` is then owned by the runner and
    ignored here). Bucket widths stay divisible by the shard count —
    both are powers of two and ``min_bucket`` is raised to ``n_shards``
    — so every bucket row-shards evenly. Results remain bit-identical to
    the single-device driver.
    """
    if runner is not None:
        min_bucket = max(min_bucket, runner.n_shards)
        if hop_chunk <= 0:
            # single-shot through the sharded runner: one max_hops chunk
            # of the same driver (bit-identical; the runner owns the only
            # sharded hop entry)
            hop_chunk = params.max_hops
    if hop_chunk <= 0:
        return filtered_search(store, codes, codebook, mem, qfilters,
                               queries, entry, params,
                               distance_fn=distance_fn, fetch_fn=fetch_fn,
                               entries=entries)
    orig_b = int(queries.shape[0])
    # Quantize the top-level width to the same power-of-two bucket
    # ladder the compaction loop uses: compile keys stay bounded to the
    # widths ``Session.warmup`` tiles, so an arbitrary group size never
    # hits a fresh multi-second jit mid-serve. Pads duplicate row 0 but
    # start inactive — exact fixed points of the hop step, zero extra
    # hops — and their rows are sliced off the result. The padding runs
    # in numpy: eager device ops at the raw width would compile one tiny
    # executable per distinct composition, defeating the quantization.
    B = max(min_bucket, _pow2_at_least(orig_b))
    n_pad = B - orig_b
    with span("hops.init", width=orig_b, bucket=B):
        if n_pad:
            def _pad(a):
                a = np.asarray(a)
                return np.concatenate(
                    [a, np.broadcast_to(a[:1], (n_pad,) + a.shape[1:])],
                    axis=0)
            queries = _pad(queries)
            qfilters = jax.tree_util.tree_map(_pad, qfilters)
            if entries is not None:
                entries = _pad(entries)
        full_ctx, full_st = init_search(store, codes, codebook, mem,
                                        qfilters, queries, entry, params,
                                        distance_fn=distance_fn,
                                        entries=entries)
        if n_pad:
            full_st = full_st._replace(
                active=full_st.active.at[orig_b:].set(False))
    work_ctx, work_st = full_ctx, full_st
    work_map: np.ndarray | None = None   # None ⇒ identity (full width)
    work_valid: np.ndarray | None = None  # non-pad rows of the bucket
    width = B
    hops_done = 0

    def hop(ctx, st, n_act):
        with span("hops.dispatch", active=n_act, bucket=width,
                  hop=hops_done):
            if runner is not None:
                st, mask = runner.run(ctx, st, hop_chunk, params,
                                      distance_fn)
            else:
                st, mask = run_hops(store, codes, mem, ctx, st, hop_chunk,
                                    params, distance_fn=distance_fn,
                                    fetch_fn=fetch_fn)
            if async_readback:
                mask.copy_to_host_async()
        return st, mask

    def readback(mask):
        with span("hops.readback"):
            return np.asarray(mask)

    # act: host copy of an active mask; in async mode it may lag work_st
    # by one chunk (a superset of the truly-active rows — see docstring)
    act = readback(work_st.active)       # init-state snapshot, pre-donation
    inflight = None                      # device mask of the newest chunk
    while True:
        n_act = int(act.sum())               # pads are inert (forced off)
        bucket = min(B, max(min_bucket, _pow2_at_least(max(n_act, 1))))
        if n_act and bucket >= width:
            # active set still fills the current bucket: keep hopping
            work_st, mask = hop(work_ctx, work_st, n_act)
            hops_done += hop_chunk
            if not async_readback:
                act = readback(mask)
                continue
            if inflight is None:
                # prime the one-chunk pipeline: dispatch a second chunk so
                # there is device work to hide the first mask's readback
                work_st, inflight = hop(work_ctx, work_st, n_act)
                hops_done += hop_chunk
                act = readback(mask)
            else:
                # read the older in-flight mask while this chunk runs
                act, inflight = readback(inflight), mask
            continue
        # settle or shrink: fold the working rows into the full state
        # (work_st may be one speculative chunk past the observed mask —
        # settled rows are bitwise unchanged by it)
        with span("hops.compact", active=n_act, bucket=bucket):
            if work_map is None:
                full_st = work_st
            else:
                sidx = jnp.asarray(
                    np.where(work_valid, work_map, B).astype(np.int32))
                full_st = tree_put_rows(full_st, work_st, sidx)
            if n_act:
                # compact the survivors into the next power-of-two
                # bucket; the stale mask over-admits at worst (rows that
                # settled during the speculative chunk ride along as
                # inert valid rows)
                surv = np.flatnonzero(act)
                idx = (work_map[surv] if work_map is not None else surv) \
                    .astype(np.int32)
                pads = np.full(bucket - idx.size, idx[0], np.int32)
                work_map = np.concatenate([idx, pads])
                work_valid = np.arange(bucket) < idx.size
                gidx = jnp.asarray(work_map)
                work_ctx = tree_take_rows(full_ctx, gidx)
                work_st = tree_take_rows(full_st, gidx)
                work_st = work_st._replace(
                    active=work_st.active & jnp.asarray(work_valid))
        if n_act == 0:
            break
        width = bucket
        inflight = None
        if async_readback:
            # don't block on the compacted state's mask: every carried
            # row was stale-active, so assume all live and let the next
            # iteration dispatch at this width (an all-settled carry makes
            # that chunk an immediate-exit no-op)
            act = work_valid.copy()
            continue
        work_st, mask = hop(work_ctx, work_st, n_act)
        hops_done += hop_chunk
        act = readback(mask)
    with span("hops.finalize", width=orig_b):
        res = finalize_search(full_st, params)
        if n_pad:
            # slice on the host: a device-side slice at the raw width
            # would compile per composition (same reason the padding is
            # numpy)
            res = SearchResult(*(np.asarray(a)[:orig_b] for a in res))
    return res


# ---------------------------------------------------------------------------
# jnp reference oracle (same semantics, naive primitives)
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("params", "distance_fn", "fetch_fn"))
def filtered_search_ref(store: RecordStore, codes: jax.Array,
                        codebook: pq_mod.PQCodebook, mem: InMemory,
                        qfilters: QueryFilter, queries: jax.Array, entry: int,
                        params: SearchParams,
                        distance_fn: Callable = pq_mod.adc_lookup,
                        fetch_fn: Callable = local_fetch,
                        entries: jax.Array | None = None) -> SearchResult:
    """The A/B oracle for :func:`filtered_search`.

    Same hop semantics — an *exact* ever-proposed visited set, the same
    admission keys and early termination — expressed with the naive
    primitives the fused path replaces: ``vmap`` over queries, a full
    argsort pool merge, a full re-sort of the explored buffer in the loop
    condition, and separate unfused distance/membership gathers. Parity
    bar: identical ``io_pages``/``explored`` counters, recall within 1%.
    """
    p = params
    l_valid = p.l_valid or p.l_search
    P, W = p.l_search, p.beam_width
    R = store.degree
    Rd = store.dense_degree if p.mode == "spec_in" else 0
    res_cap = p.max_hops * W
    rec_pages = store.pages_dense if p.mode == "spec_in" else store.pages_std
    n_ids = codes.shape[0]
    if entries is None:
        entries = jnp.full((queries.shape[0], 1), entry, jnp.int32)

    def one(q, qf, ent):
        table = pq_mod.distance_table(codebook, q)            # (M, ksub)

        e_n = ent.shape[0]
        ent_valid = ent >= 0
        safe_ent = jnp.where(ent_valid, ent, 0)
        entry_d = distance_fn(codes[safe_ent], table)         # (E,)
        entry_ok = is_member_approx(qf, safe_ent, mem) & ent_valid
        entry_key = jnp.where(
            ent_valid, entry_d + jnp.where(entry_ok, 0.0, INVALID_PENALTY),
            BIG)

        pool_ids = jnp.full((P,), -1, jnp.int32).at[:e_n].set(
            jnp.where(ent_valid, ent, -1))
        pool_key = jnp.full((P,), BIG, jnp.float32).at[:e_n].set(entry_key)
        explored = jnp.ones((P,), jnp.bool_).at[:e_n].set(~ent_valid)
        seen = jnp.zeros((n_ids,), jnp.bool_).at[
            jnp.where(ent_valid, safe_ent, n_ids)].set(True, mode="drop")

        res_ids = jnp.full((res_cap,), -1, jnp.int32)
        res_d = jnp.full((res_cap,), BIG, jnp.float32)
        res_valid = jnp.zeros((res_cap,), jnp.bool_)

        counters = jnp.zeros((4,), jnp.int32)    # io, dist_comps, approx, hops

        def cond(state):
            (pool_ids, pool_key, explored, seen, res_ids, res_d, res_valid,
             counters) = state
            hops = counters[3]
            frontier = jnp.any(~explored[:P] & (pool_key[:P] < BIG))
            # paper early termination: top-l_valid verified & no closer
            # frontier (full re-sort every iteration — the oracle keeps the
            # naive form the fused path's incremental bound replaces)
            n_ok = jnp.sum(res_valid)
            kth = jnp.sort(jnp.where(res_valid, res_d, BIG))[
                jnp.minimum(l_valid, res_cap) - 1]
            best_unexp = jnp.min(jnp.where(explored, BIG, pool_key))
            settled = (n_ok >= l_valid) & (best_unexp > kth)
            return (hops < p.max_hops) & frontier & ~settled

        def body(state):
            (pool_ids, pool_key, explored, seen, res_ids, res_d, res_valid,
             counters) = state
            # ---- 1. pick best-W unexplored (by priority key) ----
            masked = jnp.where(explored, BIG, pool_key)
            _, sel = jax.lax.top_k(-masked, W)
            cur_ids = pool_ids[sel]                            # (W,)
            cur_live = masked[sel] < BIG
            explored = explored.at[sel].set(True)
            safe_cur = jnp.where(cur_live, cur_ids, 0)

            # ---- 2. fetch records (vector + neighbors + attrs: one I/O) ----
            rec = fetch_fn(store, safe_cur)
            vecs = rec["vectors"]                              # (W, D)
            nbrs = rec["neighbors"]                            # (W, R)
            rl = rec["rec_labels"]                             # (W, ML)
            rv = rec["rec_values"]                             # (W, F)
            io = counters[0] + jnp.sum(cur_live) * rec_pages

            # ---- 3. re-rank + piggybacked exact verification ----
            ex_d = jnp.where(cur_live, _exact_sq_dist(vecs, q), BIG)
            ex_ok = is_member(qf, rl, rv) & cur_live
            hops = counters[3]
            start = hops * W
            res_ids = jax.lax.dynamic_update_slice(
                res_ids, jnp.where(cur_live, cur_ids, -1), (start,))
            res_d = jax.lax.dynamic_update_slice(res_d, ex_d, (start,))
            res_valid = jax.lax.dynamic_update_slice(res_valid, ex_ok,
                                                     (start,))

            # ---- 4. candidate generation per mode ----
            if p.mode == "spec_in":
                dn = rec["dense_neighbors"]                    # (W, Rd)
                cand = jnp.concatenate([nbrs, dn], axis=1)     # (W, R+Rd)
                is_direct = jnp.concatenate(
                    [jnp.ones((W, R), bool), jnp.zeros((W, Rd), bool)],
                    axis=1)
            else:
                cand = nbrs
                is_direct = jnp.ones((W, R), bool)
            cand = jnp.where(cur_live[:, None], cand, -1)
            live = cand >= 0
            safe_cand = jnp.where(live, cand, 0)

            # exact visited set (ever-admitted ∪ entries) + intra-slab
            # first-occurrence — the O(N)-memory oracle form of the fused
            # path's hashed slot table
            c = cand.shape[1]
            first = _first_occurrence(
                cand.reshape(-1), live.reshape(-1), n_ids).reshape(W, c)
            fresh = live & ~seen[safe_cand] & first

            approx_n = jnp.sum(live)
            if p.mode == "post":
                ok = fresh
                counters_approx = counters[2]
            elif p.mode == "spec_in":
                ok = is_member_approx(qf, safe_cand, mem) & fresh
                counters_approx = counters[2] + approx_n
            else:  # strict_in: read every fresh neighbor's attrs from "SSD"
                nrec = fetch_fn(store, safe_cand.reshape(-1))
                n_rl = nrec["rec_labels"].reshape(W, R, -1)    # (W, R, ML)
                n_rv = nrec["rec_values"].reshape(W, R, store.n_fields)
                ok = is_member(qf, n_rl, n_rv) & fresh
                io = io + jnp.sum(fresh)                       # 1 page / nbr
                counters_approx = counters[2]

            # ---- 5. slot selection: up to R approx-valid, bridge fill ----
            if p.mode == "spec_in":
                # first-come order (cheap, matches Table-1 compute accounting)
                rank_ok = jnp.cumsum(ok.astype(jnp.int32), axis=1) - 1
                fill = fresh & ~ok & is_direct
                rank_fill = jnp.cumsum(fill.astype(jnp.int32), axis=1) - 1
                n_ok_row = jnp.sum(ok, axis=1, keepdims=True)
                order_key = jnp.where(
                    ok, rank_ok.astype(jnp.float32),
                    jnp.where(fill,
                              (n_ok_row + rank_fill).astype(jnp.float32),
                              BIG))
                _, take = jax.lax.top_k(-order_key, R)          # (W, R)
                sel_ids = jnp.take_along_axis(cand, take, axis=1)
                sel_ok = jnp.take_along_axis(ok, take, axis=1)
                sel_fill = jnp.take_along_axis(fill, take, axis=1)
                sel_live = sel_ok | sel_fill
            else:
                sel_ids, sel_ok, sel_live = cand, ok, ok

            # ---- 6. PQ distances for selected candidates (unfused) ----
            flat_ids = sel_ids.reshape(-1)
            flat_live = sel_live.reshape(-1)
            flat_ok = sel_ok.reshape(-1)
            pq_d = distance_fn(codes[jnp.where(flat_live, flat_ids, 0)],
                               table)
            key = pq_d + jnp.where(flat_ok, 0.0, INVALID_PENALTY)
            key = jnp.where(flat_live, key, BIG)
            dist_comps = counters[1] + jnp.sum(flat_live)
            seen = seen.at[jnp.where(flat_live, flat_ids, n_ids)].set(
                True, mode="drop")

            # ---- 7. merge into pool (full argsort — the naive form) ----
            all_ids = jnp.concatenate(
                [pool_ids, jnp.where(flat_live, flat_ids, -1)])
            all_key = jnp.concatenate([pool_key, key])
            all_exp = jnp.concatenate([explored,
                                       jnp.zeros_like(flat_live)])
            order = jnp.argsort(all_key)[:P]
            new_counters = jnp.stack([io, dist_comps, counters_approx,
                                      hops + 1])
            return (all_ids[order], all_key[order], all_exp[order], seen,
                    res_ids, res_d, res_valid, new_counters)

        state = (pool_ids, pool_key, explored, seen, res_ids, res_d,
                 res_valid, counters)
        state = jax.lax.while_loop(cond, body, state)
        (pool_ids, pool_key, explored, seen, res_ids, res_d, res_valid,
         counters) = state

        # ---- final: top-k verified-valid by exact distance ----
        final_key = jnp.where(res_valid, res_d, BIG)
        order = jnp.argsort(final_key)[:p.k]
        out_ids = jnp.where(res_valid[order], res_ids[order], -1)
        out_d = jnp.where(res_valid[order], res_d[order], jnp.inf)
        n_valid = jnp.sum(res_valid)
        n_explored = jnp.sum(res_ids >= 0)
        fp = jnp.sum((res_ids >= 0) & ~res_valid)
        zero = jnp.int32(0)     # oracle has no fault plan: clean counters
        return (out_ids, out_d, counters[0], counters[3], counters[1],
                counters[2], n_valid, fp, n_explored, zero, zero, zero)

    outs = jax.vmap(one)(queries, qfilters, entries)
    return SearchResult(*outs)


# ---------------------------------------------------------------------------
# Pre-fused-pipeline implementation (benchmark baseline)
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("params", "distance_fn", "fetch_fn"))
def filtered_search_legacy(store: RecordStore, codes: jax.Array,
                           codebook: pq_mod.PQCodebook, mem: InMemory,
                           qfilters: QueryFilter, queries: jax.Array,
                           entry: int, params: SearchParams,
                           distance_fn: Callable = pq_mod.adc_lookup,
                           fetch_fn: Callable = local_fetch,
                           entries: jax.Array | None = None) -> SearchResult:
    """The pre-fused-pipeline search, kept verbatim as the benchmark
    baseline (``benchmarks/bench_search.py`` asserts the fused path's
    speedup against it). Its hop loop does quadratic work: pairwise dedup
    broadcasts against the pool and the whole explored buffer, a full
    argsort merge, and a full explored-buffer re-sort in the loop
    condition. Dedup semantics differ slightly from the fused path (a
    candidate dropped from the pool may be re-proposed), so counters are
    not comparable — use :func:`filtered_search_ref` for A/B parity.
    """
    p = params
    l_valid = p.l_valid or p.l_search
    P, W = p.l_search, p.beam_width
    R = store.degree
    Rd = store.dense_degree if p.mode == "spec_in" else 0
    res_cap = p.max_hops * W                     # explored-record buffer
    rec_pages = store.pages_dense if p.mode == "spec_in" else store.pages_std
    if entries is None:
        entries = jnp.full((queries.shape[0], 1), entry, jnp.int32)

    def one(q, qf, ent):
        table = pq_mod.distance_table(codebook, q)            # (M, ksub)

        e_n = ent.shape[0]
        ent_valid = ent >= 0
        safe_ent = jnp.where(ent_valid, ent, 0)
        entry_d = distance_fn(codes[safe_ent], table)         # (E,)
        entry_ok = is_member_approx(qf, safe_ent, mem) & ent_valid
        entry_key = jnp.where(
            ent_valid, entry_d + jnp.where(entry_ok, 0.0, INVALID_PENALTY),
            BIG)

        pool_ids = jnp.full((P,), -1, jnp.int32).at[:e_n].set(
            jnp.where(ent_valid, ent, -1))
        pool_key = jnp.full((P,), BIG, jnp.float32).at[:e_n].set(entry_key)
        explored = jnp.ones((P,), jnp.bool_).at[:e_n].set(~ent_valid)

        res_ids = jnp.full((res_cap,), -1, jnp.int32)
        res_d = jnp.full((res_cap,), BIG, jnp.float32)
        res_valid = jnp.zeros((res_cap,), jnp.bool_)

        counters = jnp.zeros((4,), jnp.int32)    # io, dist_comps, approx, hops

        def cond(state):
            pool_ids, pool_key, explored, res_ids, res_d, res_valid, counters = state
            hops = counters[3]
            frontier = jnp.any(~explored[:P] & (pool_key[:P] < BIG))
            # paper early termination: top-l_valid verified & no closer frontier
            n_ok = jnp.sum(res_valid)
            kth = jnp.sort(jnp.where(res_valid, res_d, BIG))[
                jnp.minimum(l_valid, res_cap) - 1]
            best_unexp = jnp.min(jnp.where(explored, BIG, pool_key))
            settled = (n_ok >= l_valid) & (best_unexp > kth)
            return (hops < p.max_hops) & frontier & ~settled

        def body(state):
            pool_ids, pool_key, explored, res_ids, res_d, res_valid, counters = state
            # ---- 1. pick best-W unexplored (by priority key) ----
            masked = jnp.where(explored, BIG, pool_key)
            _, sel = jax.lax.top_k(-masked, W)
            cur_ids = pool_ids[sel]                            # (W,)
            cur_live = masked[sel] < BIG
            explored = explored.at[sel].set(True)
            safe_cur = jnp.where(cur_live, cur_ids, 0)

            # ---- 2. fetch records (vector + neighbors + attrs: one I/O) ----
            rec = fetch_fn(store, safe_cur)
            vecs = rec["vectors"]                              # (W, D)
            nbrs = rec["neighbors"]                            # (W, R)
            rl = rec["rec_labels"]                             # (W, ML)
            rv = rec["rec_values"]                             # (W, F)
            io = counters[0] + jnp.sum(cur_live) * rec_pages

            # ---- 3. re-rank + piggybacked exact verification ----
            ex_d = jnp.where(cur_live, _exact_sq_dist(vecs, q), BIG)
            ex_ok = is_member(qf, rl, rv) & cur_live
            hops = counters[3]
            start = hops * W
            res_ids = jax.lax.dynamic_update_slice(
                res_ids, jnp.where(cur_live, cur_ids, -1), (start,))
            res_d = jax.lax.dynamic_update_slice(res_d, ex_d, (start,))
            res_valid = jax.lax.dynamic_update_slice(res_valid, ex_ok, (start,))

            # ---- 4. candidate generation per mode ----
            if p.mode == "spec_in":
                dn = rec["dense_neighbors"]                    # (W, Rd)
                cand = jnp.concatenate([nbrs, dn], axis=1)     # (W, R+Rd)
                is_direct = jnp.concatenate(
                    [jnp.ones((W, R), bool), jnp.zeros((W, Rd), bool)], axis=1)
            else:
                cand = nbrs
                is_direct = jnp.ones((W, R), bool)
            cand = jnp.where(cur_live[:, None], cand, -1)
            live = cand >= 0
            safe_cand = jnp.where(live, cand, 0)

            # dedup vs pool, explored buffer, and within the row (the 2-hop
            # sample may repeat ids)
            dup_pool = jnp.any(cand[:, :, None] == pool_ids[None, None, :], -1)
            dup_res = jnp.any(cand[:, :, None] == res_ids[None, None, :], -1)
            c = cand.shape[1]
            tri = jnp.tril(jnp.ones((c, c), bool), -1)
            dup_row = jnp.any((cand[:, :, None] == cand[:, None, :]) & tri, -1)
            fresh = live & ~dup_pool & ~dup_res & ~dup_row

            approx_n = jnp.sum(live)
            if p.mode == "post":
                ok = fresh
                counters_approx = counters[2]
            elif p.mode == "spec_in":
                ok = is_member_approx(qf, safe_cand, mem) & fresh
                counters_approx = counters[2] + approx_n
            else:  # strict_in: read every fresh neighbor's attrs from "SSD"
                nrec = fetch_fn(store, safe_cand.reshape(-1))
                n_rl = nrec["rec_labels"].reshape(W, R, -1)    # (W, R, ML)
                n_rv = nrec["rec_values"].reshape(W, R, store.n_fields)
                ok = is_member(qf, n_rl, n_rv) & fresh
                io = io + jnp.sum(fresh)                       # 1 page / neighbor
                counters_approx = counters[2]

            # ---- 5. slot selection: up to R approx-valid, bridge back-fill ----
            if p.mode == "spec_in":
                # first-come order (cheap, matches Table-1 compute accounting)
                rank_ok = jnp.cumsum(ok.astype(jnp.int32), axis=1) - 1
                fill = fresh & ~ok & is_direct
                rank_fill = jnp.cumsum(fill.astype(jnp.int32), axis=1) - 1
                n_ok_row = jnp.sum(ok, axis=1, keepdims=True)
                order_key = jnp.where(
                    ok, rank_ok.astype(jnp.float32),
                    jnp.where(fill, (n_ok_row + rank_fill).astype(jnp.float32),
                              BIG))
                _, take = jax.lax.top_k(-order_key, R)          # (W, R)
                sel_ids = jnp.take_along_axis(cand, take, axis=1)
                sel_ok = jnp.take_along_axis(ok, take, axis=1)
                sel_fill = jnp.take_along_axis(fill, take, axis=1)
                sel_live = sel_ok | sel_fill
            else:
                sel_ids, sel_ok, sel_live = cand, ok, ok

            # ---- 6. PQ distances for selected candidates only ----
            flat_ids = sel_ids.reshape(-1)
            flat_live = sel_live.reshape(-1)
            flat_ok = sel_ok.reshape(-1)
            # cross-row dedup of the selected set (W > 1 beams may collide)
            nf = flat_ids.shape[0]
            trif = jnp.tril(jnp.ones((nf, nf), bool), -1)
            dupf = jnp.any((flat_ids[:, None] == flat_ids[None, :]) & trif, -1)
            flat_live = flat_live & ~dupf
            flat_ok = flat_ok & ~dupf
            pq_d = distance_fn(codes[jnp.where(flat_live, flat_ids, 0)], table)
            key = pq_d + jnp.where(flat_ok, 0.0, INVALID_PENALTY)
            key = jnp.where(flat_live, key, BIG)
            dist_comps = counters[1] + jnp.sum(flat_live)

            # ---- 7. merge into pool (sorted ascending by key) ----
            all_ids = jnp.concatenate([pool_ids, jnp.where(flat_live, flat_ids, -1)])
            all_key = jnp.concatenate([pool_key, key])
            all_exp = jnp.concatenate([explored,
                                       jnp.zeros_like(flat_live)])
            order = jnp.argsort(all_key)[:P]
            new_counters = jnp.stack([io, dist_comps, counters_approx, hops + 1])
            return (all_ids[order], all_key[order], all_exp[order],
                    res_ids, res_d, res_valid, new_counters)

        state = (pool_ids, pool_key, explored, res_ids, res_d, res_valid, counters)
        state = jax.lax.while_loop(cond, body, state)
        pool_ids, pool_key, explored, res_ids, res_d, res_valid, counters = state

        # ---- final: top-k verified-valid by exact distance ----
        final_key = jnp.where(res_valid, res_d, BIG)
        order = jnp.argsort(final_key)[:p.k]
        out_ids = jnp.where(res_valid[order], res_ids[order], -1)
        out_d = jnp.where(res_valid[order], res_d[order], jnp.inf)
        n_valid = jnp.sum(res_valid)
        n_explored = jnp.sum(res_ids >= 0)
        fp = jnp.sum((res_ids >= 0) & ~res_valid)
        zero = jnp.int32(0)     # baseline has no fault plan: clean counters
        return (out_ids, out_d, counters[0], counters[3], counters[1],
                counters[2], n_valid, fp, n_explored, zero, zero, zero)

    outs = jax.vmap(one)(queries, qfilters, entries)
    return SearchResult(*outs)
