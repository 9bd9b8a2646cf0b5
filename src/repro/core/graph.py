"""Vamana graph construction (paper §5.1) + ACORN-style 2-hop densification
(paper §4.1).

Two builders share the same batched on-device greedy search:

* ``build_vamana`` — the sequential numpy reference (robust pruning and
  reverse-edge insertion in Python loops). Kept as the correctness oracle.
* ``build_vamana_batched`` / ``IncrementalBuilder`` — the device-resident
  batched pipeline used by the engine.

DESIGN — batched prune/scatter formulation
------------------------------------------
The batched builder processes an insertion batch of B nodes per jitted step:

1. **Vectorized RobustPrune** (``robust_prune_batch``): each node's candidate
   set (search pool ∪ old out-edges, deduped and id-sorted like the numpy
   ``np.unique`` path) is stable-sorted by distance to the insert point; a
   masked domination scan (``kernels.ops.prune_scan`` — a fori_loop on CPU,
   a Pallas kernel on TPU) walks the sorted candidates keeping ≤ R survivors,
   where survivor i prunes every j with α²·d(i, j) ≤ d(p, j). Per node this
   is the *identical* keep sequence as the sequential reference (same stable
   tie-breaking, same α²-domination test); the only deviation is float
   associativity in the distance computations.
2. **Scatter reverse edges** (``_scatter_pairs``): the whole batch's
   (target, source) reverse edges are resolved at once — pairs are
   segment-sorted by target (stable in batch order), ranked within each
   target run, and the first ``free_slots(target)`` ranks are written with a
   single scatter into the rank-th free slot. Conflicts between sources of
   one target are therefore resolved in the same first-come order as the
   sequential loop.
3. **Overflow rows** re-enter the same batched prune: targets whose free
   slots are exhausted are pruned once over (old row ∪ pending sources)
   instead of once per incoming edge. This is the one *semantic* deviation
   from sequential Vamana — overflow sources are grouped per target rather
   than interleaved — and it is recall-neutral (the α²-domination objective
   is order-independent over the same candidate set; equivalence is enforced
   by test against the reference builder). Sources beyond the per-round cap
   are carried into another scatter/prune round, so no edge is dropped.

Within a batch all nodes see the adjacency snapshot from the batch start
(the reference updates it node by node); with two passes this stays within
the ±1% recall-parity budget the equivalence test enforces.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops


# ---------------------------------------------------------------------------
# Batched greedy (beam) search over an adjacency array — build-time navigator.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("ell", "max_hops"))
def greedy_search(data, adj, entry: int, queries, ell: int, max_hops: int):
    """Best-first search with a size-`ell` pool; exact (full-precision) dists.

    data: (N, D) f32; adj: (N, R) i32 (-1 pad); queries: (B, D).
    ``adj`` may carry extra scratch rows beyond N (the batched builder's
    dump row) — they are never reachable because no stored edge points at
    them. Returns (pool_ids, pool_dists): (B, ell) each, sorted ascending.
    """
    r = adj.shape[1]

    def one(q):
        d0 = jnp.sum((data[entry] - q) ** 2)
        pool_ids = jnp.full((ell,), -1, jnp.int32).at[0].set(entry)
        pool_d = jnp.full((ell,), jnp.inf, jnp.float32).at[0].set(d0)
        explored = jnp.zeros((ell,), jnp.bool_)

        def cond(state):
            _, pool_d, explored, hops = state
            has_frontier = jnp.any(~explored & jnp.isfinite(pool_d))
            return has_frontier & (hops < max_hops)

        def body(state):
            pool_ids, pool_d, explored, hops = state
            # pick best unexplored
            masked = jnp.where(explored, jnp.inf, pool_d)
            i = jnp.argmin(masked)
            explored = explored.at[i].set(True)
            cur = pool_ids[i]
            nbrs = adj[cur]                                    # (R,)
            valid = nbrs >= 0
            nv = jnp.where(valid, nbrs, 0)
            nd = jnp.sum((data[nv] - q[None, :]) ** 2, axis=1)
            nd = jnp.where(valid, nd, jnp.inf)
            # dedup against pool
            dup = jnp.any(nbrs[:, None] == pool_ids[None, :], axis=1)
            nd = jnp.where(dup, jnp.inf, nd)
            # merge: keep ell best of pool ∪ neighbors
            all_ids = jnp.concatenate([pool_ids, nbrs])
            all_d = jnp.concatenate([pool_d, nd])
            all_exp = jnp.concatenate([explored, jnp.zeros((r,), jnp.bool_)])
            order = jnp.argsort(all_d)[:ell]
            return (all_ids[order], all_d[order], all_exp[order], hops + 1)

        pool_ids, pool_d, explored, _ = jax.lax.while_loop(
            cond, body, (pool_ids, pool_d, explored, jnp.int32(0)))
        return pool_ids, pool_d

    return jax.vmap(one)(queries)


def _beam_pool(adj, entry: int, ell: int, max_hops: int, width: int,
               dist_fn):
    """One query's beam-pool navigation over ``adj`` with a pluggable
    distance: ``dist_fn(ids (C,) int32) -> (C,) float32`` (ids are safe,
    i.e. already clamped non-negative; invalid lanes are masked to +inf by
    this navigator). Shared by :func:`greedy_search_beam` (exact
    full-precision distances) and the sharded builder's PQ-approximate
    navigation (core/distributed.py — ADC distances steer the pool, the
    RobustPrune re-rank stays exact). Returns (pool_ids, pool_d), each
    (ell,) ascending."""
    r = adj.shape[1]
    w = width
    d0 = dist_fn(jnp.full((1,), entry, jnp.int32))[0]
    pool_ids0 = jnp.full((ell,), -1, jnp.int32).at[0].set(entry)
    pool_d0 = jnp.full((ell,), jnp.inf, jnp.float32).at[0].set(d0)
    explored0 = jnp.zeros((ell,), jnp.bool_)

    def cond(state):
        _, pool_d, explored, hops = state
        has_frontier = jnp.any(~explored & jnp.isfinite(pool_d))
        return has_frontier & (hops < max_hops)

    def body(state):
        pool_ids, pool_d, explored, hops = state
        masked = jnp.where(explored, jnp.inf, pool_d)
        _, sel = jax.lax.top_k(-masked, w)
        cur_live = jnp.isfinite(masked[sel])
        explored = explored.at[sel].set(True)
        cur = jnp.where(cur_live, pool_ids[sel], 0)
        nbrs = adj[cur]                                  # (W, R)
        nbrs = jnp.where(cur_live[:, None], nbrs, -1).reshape(-1)
        valid = nbrs >= 0
        nv = jnp.where(valid, nbrs, 0)
        nd = dist_fn(nv)
        nd = jnp.where(valid, nd, jnp.inf)
        # dedup against pool and across the W beams' rows
        dup = jnp.any(nbrs[:, None] == pool_ids[None, :], axis=1)
        c = nbrs.shape[0]
        tri = jnp.tril(jnp.ones((c, c), jnp.bool_), -1)
        dup |= jnp.any((nbrs[:, None] == nbrs[None, :]) & tri, axis=1)
        nd = jnp.where(dup, jnp.inf, nd)
        all_ids = jnp.concatenate([pool_ids, nbrs])
        all_d = jnp.concatenate([pool_d, nd])
        all_exp = jnp.concatenate([explored, jnp.zeros((c,), jnp.bool_)])
        # top_k merge: ~4x cheaper than a full argsort on CPU/TPU
        neg_d, order = jax.lax.top_k(-all_d, ell)
        return (all_ids[order], -neg_d, all_exp[order], hops + 1)

    pool_ids, pool_d, _, _ = jax.lax.while_loop(
        cond, body, (pool_ids0, pool_d0, explored0, jnp.int32(0)))
    return pool_ids, pool_d


@functools.partial(jax.jit, static_argnames=("ell", "max_hops", "width"))
def greedy_search_beam(data, adj, entry: int, queries, ell: int,
                       max_hops: int, width: int = 4):
    """Beam variant of :func:`greedy_search`: explores the ``width`` best
    unexplored pool entries per iteration, so the sequential hop count drops
    ~width× while each step stays one coalesced gather. Used by the batched
    builder as its candidate generator (same pool semantics, coarser
    exploration order). Returns (pool_ids, pool_dists): (B, ell) ascending.
    """
    def one(q):
        return _beam_pool(
            adj, entry, ell, max_hops, width,
            lambda ids: jnp.sum((data[ids] - q[None, :]) ** 2, axis=1))

    return jax.vmap(one)(queries)


# ---------------------------------------------------------------------------
# Robust prune (numpy reference, squared distances -> alpha^2 domination)
# ---------------------------------------------------------------------------

def robust_prune(p_vec: np.ndarray, cand_ids: np.ndarray,
                 cand_vecs: np.ndarray, r: int, alpha: float) -> np.ndarray:
    """Vamana RobustPrune: keep ≤ r diverse candidates."""
    if cand_ids.size == 0:
        return cand_ids
    d_p = np.sum((cand_vecs - p_vec[None, :]) ** 2, axis=1)
    order = np.argsort(d_p, kind="stable")
    a2 = alpha * alpha
    pruned = np.zeros(cand_ids.size, dtype=bool)
    keep: list[int] = []
    for idx in order:
        if pruned[idx]:
            continue
        keep.append(idx)
        if len(keep) >= r:
            break
        d_kc = np.sum((cand_vecs - cand_vecs[idx][None, :]) ** 2, axis=1)
        pruned |= a2 * d_kc <= d_p
        pruned[idx] = True
    return cand_ids[np.array(keep, dtype=np.int64)]


def build_vamana(data: np.ndarray, r: int = 32, ell: int = 64,
                 alpha: float = 1.2, batch: int = 1024,
                 seed: int = 0) -> tuple[np.ndarray, int]:
    """Sequential reference build. Returns (adjacency (N, r) int32, medoid).

    Robust pruning and reverse-edge insertion run in numpy Python loops;
    use :func:`build_vamana_batched` for the fast device-resident path.
    """
    rng = np.random.default_rng(seed)
    data = np.asarray(data, dtype=np.float32)
    n = data.shape[0]
    medoid = int(np.argmin(np.sum((data - data.mean(0, keepdims=True)) ** 2, 1)))

    # random initial graph
    adj = rng.integers(0, n, size=(n, r), dtype=np.int64).astype(np.int32)
    adj[adj == np.arange(n, dtype=np.int32)[:, None]] = medoid

    data_dev = jnp.asarray(data)

    for alpha_pass in (1.0, alpha):
        order = rng.permutation(n)
        for start in range(0, n, batch):
            ids = order[start:start + batch]
            adj_dev = jnp.asarray(adj)
            pool_ids, _ = greedy_search(data_dev, adj_dev, medoid,
                                        data_dev[ids], ell, max_hops=ell)
            pool_ids = np.asarray(pool_ids)
            for k, p in enumerate(ids):
                cands = np.concatenate([pool_ids[k], adj[p]])
                cands = np.unique(cands[(cands >= 0) & (cands != p)])
                kept = robust_prune(data[p], cands, data[cands], r, alpha_pass)
                row = np.full(r, -1, np.int32)
                row[:kept.size] = kept
                adj[p] = row
                # reverse edges
                for q in kept:
                    qrow = adj[q]
                    if p in qrow:
                        continue
                    slot = np.where(qrow < 0)[0]
                    if slot.size:
                        adj[q, slot[0]] = p
                    else:
                        rc = np.unique(np.concatenate([qrow, [p]]))
                        rc = rc[(rc >= 0) & (rc != q)]
                        kept_q = robust_prune(data[q], rc, data[rc], r, alpha_pass)
                        qnew = np.full(r, -1, np.int32)
                        qnew[:kept_q.size] = kept_q
                        adj[q] = qnew
    return adj, medoid


# ---------------------------------------------------------------------------
# Batched device-resident build (see DESIGN note in the module docstring)
# ---------------------------------------------------------------------------

_INT_MAX = np.iinfo(np.int32).max


def _dedup_ascending(cands: jax.Array, self_ids: jax.Array) -> jax.Array:
    """Row-wise unique ascending ids; drops negatives and the row's own id.

    cands (B, C) int32 -> (B, C) int32 with valid ids ascending and -1
    right-padding — the device analogue of the reference's ``np.unique``.
    """
    big = jnp.int32(_INT_MAX)
    x = jnp.where((cands < 0) | (cands == self_ids[:, None]), big, cands)
    x = jnp.sort(x, axis=1)
    dup = jnp.concatenate(
        [jnp.zeros_like(x[:, :1], jnp.bool_), x[:, 1:] == x[:, :-1]], axis=1)
    x = jnp.sort(jnp.where(dup, big, x), axis=1)
    return jnp.where(x == big, -1, x)


def prune_distances(data: jax.Array, p_ids: jax.Array,
                    cand_ids: jax.Array):
    """The domination scan's inputs for a batch of prune rows.

    Returns ``(dp_s, dcc, ids_s)``: candidate→point distances stable-sorted
    ascending per row (+inf for the -1 pads), the (B, C, C) pairwise
    candidate distances in that sorted order, and the ids in that order —
    what :func:`kernels.ops.prune_scan` consumes."""
    valid = cand_ids >= 0
    cv = data[jnp.where(valid, cand_ids, 0)]                 # (B, C, D)
    pv = data[p_ids]                                         # (B, D)
    d_p = jnp.sum((cv - pv[:, None, :]) ** 2, axis=-1)
    d_p = jnp.where(valid, d_p, jnp.inf)
    order = jnp.argsort(d_p, axis=1)                         # stable
    dp_s = jnp.take_along_axis(d_p, order, axis=1)
    ids_s = jnp.take_along_axis(cand_ids, order, axis=1)
    cv_s = jnp.take_along_axis(cv, order[:, :, None], axis=1)
    # pairwise candidate distances in sorted space (norm expansion, with
    # float32 products: the TPU's default matmul rounds to bfloat16)
    sq = jnp.sum(cv_s * cv_s, axis=-1)                       # (B, C)
    dcc = sq[:, :, None] + sq[:, None, :] \
        - 2.0 * jnp.einsum("bcd,bed->bce", cv_s, cv_s,
                           precision=jax.lax.Precision.HIGHEST)
    return dp_s, jnp.maximum(dcc, 0.0), ids_s


@functools.partial(jax.jit, static_argnames=("r", "alpha"))
def robust_prune_batch(data: jax.Array, p_ids: jax.Array, cand_ids: jax.Array,
                       r: int, alpha: float) -> jax.Array:
    """Vectorized RobustPrune for a whole insertion batch.

    data (N, D); p_ids (B,) int32; cand_ids (B, C) int32 — unique ascending
    with -1 right-padding, self id excluded (see ``_dedup_ascending``).
    Returns (B, r) int32 rows, survivors in keep (distance) order, -1 pad —
    matching the sequential reference's output row layout.
    """
    a2 = float(alpha) * float(alpha)
    b = cand_ids.shape[0]
    dp_s, dcc, ids_s = prune_distances(data, p_ids, cand_ids)
    keep_s = ops.prune_scan(dp_s, dcc, a2, r)                # (B, C) bool
    rank = jnp.cumsum(keep_s.astype(jnp.int32), axis=1) - 1
    rows = jnp.full((b, r), -1, jnp.int32)
    rows = rows.at[jnp.arange(b)[:, None],
                   jnp.where(keep_s, rank, r)].set(
        jnp.where(keep_s, ids_s, -1), mode="drop")
    return rows


@jax.jit
def _scatter_pairs(adj_ext: jax.Array, tgt: jax.Array, src: jax.Array):
    """Batched reverse-edge insertion: one scatter for all (tgt, src) pairs.

    adj_ext: (N+1, R) int32 — row N is an all(-1) dump row for masked
    writes (the invariant "dump row stays -1" is preserved by every caller).
    Pairs are segment-sorted by target (stable in pair order) and ranked;
    rank k lands in the target's k-th free slot. Returns
    (adj_ext, sorted_tgt, sorted_src, overflow_mask) where overflow pairs
    are valid pairs whose target had no free slot left.
    """
    n1, r = adj_ext.shape
    dump = n1 - 1
    p = tgt.shape[0]
    valid = (tgt >= 0) & (src >= 0) & (tgt != src)
    safe_t = jnp.where(valid, tgt, dump)
    # skip pairs whose edge already exists
    valid &= ~jnp.any(adj_ext[safe_t] == src[:, None], axis=1)
    pos = jnp.arange(p)
    # stable sort by target keeps pairs of one target in batch order;
    # invalid pairs sort to the dump-row run at the end (ids < dump)
    order = jnp.argsort(jnp.where(valid, safe_t, dump))
    st, ss, sv = safe_t[order], src[order], valid[order]
    # rank within each target run (runs are contiguous after the sort)
    is_first = jnp.concatenate([jnp.ones((1,), jnp.bool_), st[1:] != st[:-1]])
    seg_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(is_first, pos, -1))
    rank = pos - seg_start
    rowq = adj_ext[st]                                       # (P, R)
    free = rowq < 0
    n_free = jnp.sum(free, axis=1)
    colpos = jnp.broadcast_to(jnp.arange(r)[None, :], rowq.shape)
    slot_order = jnp.argsort(jnp.where(free, colpos, r + colpos), axis=1)
    slot = jnp.take_along_axis(
        slot_order, jnp.minimum(rank, r - 1)[:, None], axis=1)[:, 0]
    do = sv & (rank < n_free)
    adj_ext = adj_ext.at[jnp.where(do, st, dump),
                         jnp.where(do, slot, 0)].set(jnp.where(do, ss, -1))
    overflow = sv & (rank >= n_free)
    return adj_ext, st, ss, overflow


@functools.partial(jax.jit, donate_argnums=(0,))
def write_rows(buf: jax.Array, rows: jax.Array, start) -> jax.Array:
    """Donated in-place row write: ``buf[start:start+len(rows)] = rows``.

    The capacity-padded insert path funnels every device-array row write
    through this jitted helper so XLA reuses the input buffer
    (``donate_argnums``) instead of materializing the O(capacity)
    functional-update copy a bare ``.at[...].set`` outside jit pays.
    ``start`` is traced (dynamic), so steady-state inserts of one batch
    shape compile exactly once. The donated input is DELETED — callers
    must own ``buf`` exclusively and rebind the result.
    """
    idx = (jnp.asarray(start, jnp.int32),) + (0,) * (buf.ndim - 1)
    return jax.lax.dynamic_update_slice(buf, rows, idx)


@functools.partial(jax.jit, static_argnames=("r", "alpha"),
                   donate_argnums=(1,))
def _link_batch(data: jax.Array, adj_ext: jax.Array, ids: jax.Array,
                live: jax.Array, pool_ids: jax.Array, r: int, alpha: float):
    """Prune an insertion batch's rows and scatter their reverse edges.
    ``adj_ext`` is donated: the row set + reverse scatter reuse its buffer
    (callers rebind the returned array)."""
    dump = adj_ext.shape[0] - 1
    cand = jnp.concatenate([pool_ids, adj_ext[ids]], axis=1)
    cand = _dedup_ascending(cand, ids)
    rows = robust_prune_batch(data, ids, cand, r=r, alpha=alpha)
    rows = jnp.where(live[:, None], rows, -1)
    adj_ext = adj_ext.at[jnp.where(live, ids, dump)].set(rows)
    tgt = rows.reshape(-1)
    src = jnp.repeat(ids, r)
    return _scatter_pairs(adj_ext, tgt, src)


def _pow2_pad(m: int, lo: int = 32) -> int:
    return max(lo, 1 << (max(m, 1) - 1).bit_length())


def _pad_batch(ids: np.ndarray, width: int) -> tuple[np.ndarray, np.ndarray]:
    """Right-pad an insertion-id batch to ``width``, repeating the last id;
    the returned live mask marks pads dead so ``_link_batch`` routes their
    rows and reverse edges to the dump row."""
    live = np.ones(width, bool)
    if ids.size < width:
        live[ids.size:] = False
        ids = np.concatenate(
            [ids, np.full(width - ids.size, ids[-1], np.int32)])
    return ids.astype(np.int32), live


def _prune_rows(data_dev, adj_ext, targets: np.ndarray, srcs: np.ndarray,
                r: int, alpha: float, chunk: int = 4096, prune=None):
    """Re-prune overflowing rows over (old row ∪ pending sources).
    ``prune(data, ids, cand) -> rows`` replaces :func:`robust_prune_batch`
    (the sharded build passes its mesh-replicated form)."""
    if prune is None:
        prune = functools.partial(robust_prune_batch, r=r, alpha=alpha)
    dump = adj_ext.shape[0] - 1
    for s in range(0, targets.shape[0], chunk):
        t = targets[s:s + chunk]
        sc = srcs[s:s + chunk]
        pad = _pow2_pad(t.shape[0]) - t.shape[0]
        if pad:
            # padded targets resolve to the dump row: their candidate set is
            # empty, so the prune writes an all(-1) row back into it,
            # preserving the dump invariant.
            t = np.concatenate([t, np.full(pad, dump, t.dtype)])
            sc = np.concatenate(
                [sc, np.full((pad, sc.shape[1]), -1, sc.dtype)])
        t_dev = jnp.asarray(t)
        cand = jnp.concatenate([adj_ext[t_dev], jnp.asarray(sc)], axis=1)
        cand = _dedup_ascending(cand, t_dev)
        rows = prune(data_dev, t_dev, cand)
        adj_ext = adj_ext.at[t_dev].set(rows)
    return adj_ext


def _group_overflow(st, ss, overflow, ov_cap: int):
    """Host-side: group overflow pairs by target (already target-sorted).

    Returns (targets (T,), srcs (T, ov_cap) -1-padded, leftover (tgt, src))
    where leftover holds each target's sources beyond ``ov_cap`` for the
    next scatter/prune round.
    """
    ov = np.asarray(overflow)
    t = np.asarray(st)[ov]
    s = np.asarray(ss)[ov]
    if t.size == 0:
        return None
    uniq, start, cnt = np.unique(t, return_index=True, return_counts=True)
    gidx = np.repeat(np.arange(uniq.size), cnt)
    posg = np.arange(t.size) - np.repeat(start, cnt)
    take = posg < ov_cap
    srcs = np.full((uniq.size, ov_cap), -1, np.int32)
    srcs[gidx[take], posg[take]] = s[take]
    return uniq.astype(np.int32), srcs, (t[~take], s[~take])


@functools.partial(jax.jit, donate_argnums=(0,))
def apply_pruned_rows(adj_ext: jax.Array, ids: jax.Array, live: jax.Array,
                      rows: jax.Array):
    """Row set + reverse-edge scatter for externally pruned rows — the
    replicated host half of the sharded build's link step
    (core/distributed.py): navigation + RobustPrune run per shard under
    shard_map and the all-gathered (B, R) rows land here. Identical to
    the back half of :func:`_link_batch` (which fuses the prune in)."""
    dump = adj_ext.shape[0] - 1
    rows = jnp.where(live[:, None], rows, -1)
    adj_ext = adj_ext.at[jnp.where(live, ids, dump)].set(rows)
    tgt = rows.reshape(-1)
    src = jnp.repeat(ids, rows.shape[1])
    return _scatter_pairs(adj_ext, tgt, src)


def _drain_overflow(data_dev, adj_ext, st, ss, overflow, n_rows: int,
                    r: int, alpha: float, prune=None):
    """Drain a batch's pending reverse-edge overflow rounds (``prune`` as
    in :func:`_prune_rows`)."""
    # small per-round source cap: overflow counts are heavy-tailed (most
    # targets receive a handful of pending edges), so a narrow candidate
    # width r+8 keeps the O(C²·D) prune cheap; rare hot targets just take
    # extra rounds, each consuming another 8 sources
    ov_cap = 8
    # every round consumes ≥ ov_cap pending sources per remaining target
    # (or scatters them into freed slots), so ceil(B/ov_cap) rounds is a
    # hard upper bound — a target receives at most one edge per batch node.
    # Exceeding it means a logic bug: fail loudly, never drop edges.
    max_rounds = -(-n_rows // ov_cap) + 2
    for _ in range(max_rounds):
        grouped = _group_overflow(st, ss, overflow, ov_cap=ov_cap)
        if grouped is None:
            break
        targets, srcs, (lt, ls) = grouped
        adj_ext = _prune_rows(data_dev, adj_ext, targets, srcs, r, alpha,
                              prune=prune)
        if lt.size == 0:
            break
        pad = _pow2_pad(lt.size) - lt.size
        tgt = np.concatenate([lt, np.full(pad, -1, lt.dtype)]).astype(np.int32)
        src = np.concatenate([ls, np.full(pad, -1, ls.dtype)]).astype(np.int32)
        adj_ext, st, ss, overflow = _scatter_pairs(
            adj_ext, jnp.asarray(tgt), jnp.asarray(src))
    else:
        raise RuntimeError(
            "reverse-edge overflow failed to drain within the round bound; "
            "this indicates a bug in the scatter/overflow bookkeeping")
    return adj_ext


def _apply_batch(data_dev, adj_ext, ids: np.ndarray, live: np.ndarray,
                 pool_ids, r: int, alpha: float):
    """One insertion batch: prune + row set + reverse scatter + overflow."""
    adj_ext, st, ss, overflow = _link_batch(
        data_dev, adj_ext, jnp.asarray(ids), jnp.asarray(live), pool_ids,
        r=r, alpha=alpha)
    return _drain_overflow(data_dev, adj_ext, st, ss, overflow,
                           ids.shape[0], r, alpha)


def build_vamana_batched(data: np.ndarray, r: int = 32, ell: int = 64,
                         alpha: float = 1.2, batch: int = 1024,
                         seed: int = 0) -> tuple[np.ndarray, int]:
    """Device-resident batched Vamana build (same signature/RNG stream as
    the reference). Returns (adjacency (N, r) int32 padded -1, medoid)."""
    rng = np.random.default_rng(seed)
    data = np.asarray(data, dtype=np.float32)
    n = data.shape[0]
    medoid = int(np.argmin(np.sum((data - data.mean(0, keepdims=True)) ** 2, 1)))

    adj0 = rng.integers(0, n, size=(n, r), dtype=np.int64).astype(np.int32)
    adj0[adj0 == np.arange(n, dtype=np.int32)[:, None]] = medoid

    data_dev = jnp.asarray(data)
    adj_ext = jnp.concatenate(
        [jnp.asarray(adj0), jnp.full((1, r), -1, jnp.int32)])
    batch = min(batch, _pow2_pad(n))

    for pass_i, alpha_pass in enumerate((1.0, alpha)):
        # the α=1 bootstrap pass only seeds the final α-pass with a usable
        # graph; a ⅔-width pool there cuts ~40% of navigation time with no
        # measurable recall cost (the equivalence test gates the result)
        pell = ell if pass_i else max(16, (2 * ell) // 3)
        order = rng.permutation(n)
        for start in range(0, n, batch):
            ids, live = _pad_batch(order[start:start + batch].astype(
                np.int32), batch)
            pool_ids, _ = greedy_search_beam(data_dev, adj_ext, medoid,
                                             data_dev[jnp.asarray(ids)],
                                             pell, max_hops=pell)
            adj_ext = _apply_batch(data_dev, adj_ext, ids, live, pool_ids,
                                   r=r, alpha=float(alpha_pass))
    return np.asarray(adj_ext[:-1]), medoid


class IncrementalBuilder:
    """Appends batches of new nodes to a live Vamana graph on device.

    Wraps (data, adjacency, medoid) with geometric capacity growth so the
    jitted search/prune/scatter steps recompile only on capacity changes,
    not on every insert. ``add_batch`` links each new node with a single
    final-α pass (greedy search from the medoid → batched RobustPrune →
    batched reverse-edge scatter) — the streaming-insert half of the
    batched pipeline. Unreached capacity rows hold zero vectors and empty (-1)
    adjacency — no stored edge ever points at them, so searches cannot
    reach them.
    """

    def __init__(self, data: np.ndarray, adj: np.ndarray, medoid: int,
                 ell: int = 64, alpha: float = 1.2, batch: int = 1024):
        data = np.asarray(data, np.float32)
        adj = np.asarray(adj, np.int32)
        assert data.shape[0] == adj.shape[0]
        self.n = data.shape[0]
        self.r = adj.shape[1]
        self.ell = ell
        self.alpha = float(alpha)
        self.batch = batch
        self.medoid = int(medoid)
        self._cap = self.n
        self._data_host = data
        self._data_dev = jnp.asarray(data)
        self._adj_ext = jnp.concatenate(
            [jnp.asarray(adj), jnp.full((1, self.r), -1, jnp.int32)])

    @classmethod
    def build(cls, data: np.ndarray, r: int = 32, ell: int = 64,
              alpha: float = 1.2, batch: int = 1024,
              seed: int = 0) -> "IncrementalBuilder":
        adj, medoid = build_vamana_batched(data, r, ell, alpha, batch, seed)
        return cls(data, adj, medoid, ell=ell, alpha=alpha, batch=batch)

    # -- state ----------------------------------------------------------
    @property
    def adjacency(self) -> np.ndarray:
        return np.asarray(self._adj_ext[:self.n])

    @property
    def data(self) -> np.ndarray:
        return self._data_host[:self.n]

    @property
    def capacity(self) -> int:
        """Allocated rows; grows geometrically, ≥ n."""
        return self._cap

    @property
    def data_device(self) -> "jax.Array":
        """(capacity, D) device vectors — rows ≥ n are zero pads."""
        return self._data_dev

    @property
    def adjacency_device(self) -> "jax.Array":
        """(capacity, R) device adjacency — rows ≥ n are -1 pads.

        Shared with the engine's capacity-padded record store so
        steady-state inserts keep one stable array shape (no per-insert
        jit re-specialization downstream)."""
        return self._adj_ext[:self._cap]

    def _grow(self, need: int):
        cap = self._cap
        while cap < need:
            cap = max(cap + self.batch, int(cap * 1.5))
        if cap == self._cap:
            return
        d = self._data_host.shape[1]
        data = np.zeros((cap, d), np.float32)
        data[:self.n] = self._data_host[:self.n]
        self._data_host = data
        self._data_dev = jnp.asarray(data)
        body = self._adj_ext[:self.n]
        pad = jnp.full((cap + 1 - self.n, self.r), -1, jnp.int32)
        self._adj_ext = jnp.concatenate([body, pad])
        self._cap = cap

    # -- streaming insert ----------------------------------------------
    def add_batch(self, vectors: np.ndarray) -> np.ndarray:
        """Insert new vectors; returns their assigned ids (contiguous)."""
        vectors = np.asarray(vectors, np.float32)
        if vectors.ndim != 2 or vectors.shape[1] != self._data_host.shape[1]:
            raise ValueError(
                f"expected (M, {self._data_host.shape[1]}) vectors, got "
                f"{vectors.shape}")
        m = vectors.shape[0]
        if m == 0:
            return np.zeros(0, np.int64)
        self._grow(self.n + m)
        new_ids = np.arange(self.n, self.n + m, dtype=np.int64)
        self._data_host[self.n:self.n + m] = vectors
        self._data_dev = write_rows(self._data_dev, jnp.asarray(vectors),
                                    self.n)
        for s in range(0, m, self.batch):
            ids = new_ids[s:s + self.batch].astype(np.int32)
            ids, live = _pad_batch(
                ids, min(_pow2_pad(ids.size, lo=8), self.batch))
            pool_ids, _ = greedy_search_beam(
                self._data_dev, self._adj_ext, self.medoid,
                self._data_dev[jnp.asarray(ids)], self.ell,
                max_hops=self.ell)
            self._adj_ext = _apply_batch(
                self._data_dev, self._adj_ext, ids, live, pool_ids,
                r=self.r, alpha=self.alpha)
        self.n += m
        return new_ids


# ---------------------------------------------------------------------------
# 2-hop densification + stats
# ---------------------------------------------------------------------------

def densify_2hop(adj: np.ndarray, r_dense: int, seed: int = 0) -> np.ndarray:
    """Random 2-hop sample per node (paper §4.1: ~10–20× direct degree).

    Vectorized: pick random (first-hop, second-hop) slot pairs; duplicates and
    occasional self-references are tolerated (search dedups), matching the
    paper's random-subset semantics.
    """
    rng = np.random.default_rng(seed)
    n, r = adj.shape
    i1 = rng.integers(0, r, size=(n, r_dense))
    i2 = rng.integers(0, r, size=(n, r_dense))
    hop1 = np.take_along_axis(adj, i1, axis=1)               # (N, R_d)
    hop1_safe = np.where(hop1 >= 0, hop1, 0)
    hop2 = adj[hop1_safe, i2]                                # (N, R_d)
    hop2 = np.where(hop1 >= 0, hop2, -1)
    hop2 = np.where(hop2 == np.arange(n)[:, None], -1, hop2)
    return hop2.astype(np.int32)


def graph_stats(adj: np.ndarray) -> dict:
    valid = adj >= 0
    deg = valid.sum(1)
    return {"avg_degree": float(deg.mean()), "min_degree": int(deg.min()),
            "max_degree": int(deg.max())}


def greedy_recall_at_k(data: np.ndarray, adj: np.ndarray, medoid: int,
                       queries: np.ndarray, ell: int = 64, k: int = 10,
                       max_hops: int = 200) -> float:
    """Unfiltered recall@k of greedy search over a graph vs exact top-k —
    the graph-quality metric shared by the build benchmark and the
    builder-equivalence tests."""
    ids, _ = greedy_search(jnp.asarray(data), jnp.asarray(adj), medoid,
                           jnp.asarray(queries), ell=ell, max_hops=max_hops)
    ids = np.asarray(ids)
    recalls = []
    for i, q in enumerate(queries):
        exact = np.argsort(np.sum((data - q[None]) ** 2, axis=1))[:k]
        got = set(ids[i, :k].tolist())
        recalls.append(len(got & set(exact.tolist())) / k)
    return float(np.mean(recalls))
