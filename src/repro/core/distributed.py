"""Distributed filtered search + Vamana build on a TPU pod mesh
(DESIGN.md §2 mapping; docs/distributed.md has the diagrams).

Tier mapping of the paper's memory hierarchy onto the pod:

  * **Record store ("SSD")** — vectors, adjacency (+2-hop), attributes,
    and the precomputed ``cand_first`` dedup bits — sharded by vector-ID
    range across the mesh (a LAION100M-scale store is ~0.5 TB: it only
    fits sharded). A record fetch is a masked-local-gather + psum: only
    the owning shard contributes nonzero rows, every device receives the
    full record. This is the TPU analogue of a batched SSD read, and its
    payload bytes are the collective term of the ANN roofline.
  * **Probabilistic tier ("DRAM")** — PQ codes, Bloom words, bucket codes,
    the per-query visited/rare-list word bitmaps — replicated per chip
    (small: ≤ bytes/vector), probed with zero communication inside the
    beam loop, exactly like the paper's in-memory structures.

Two query layouts share that store layout:

  * :func:`distributed_filtered_search` (the original single-shot entry) —
    queries REPLICATED: every device executes the whole batch's beam
    control flow, one psum per hop coalesces the reads. Kept as the
    simplest mesh entry and the back-compat surface.
  * :class:`ShardedSearchRunner` (the production engine) — queries
    ROW-SHARDED: each shard runs the hop loop for its B/S contiguous
    query rows only, so hop compute ALSO scales with the mesh. Per hop
    each shard all-gathers the global frontier ids (S·B/S·W ids — tiny),
    answers the psum fetch from its store shard, and keeps its own rows'
    slabs; the loop terminates on the psum'd *global* active flag so every
    shard takes the same number of iterations (settled rows are exact
    fixed points of the hop step). The runner plugs into
    ``search.filtered_search_pipelined``'s ``runner=`` seam: init /
    finalize / straggler compaction / the bucket-jit cache all run
    unchanged on the host driver — only the chunked hop call crosses the
    mesh — so results stay bit-identical to the single-device driver.

The sharded Vamana build (:func:`build_vamana_sharded`) splits each
insertion batch's rows over the same axis: navigation (optionally on
PQ-approximate ADC distances) and the exact RobustPrune re-rank run per
shard, the pruned (B, R) rows are all-gathered, and the replicated
reverse-edge scatter + overflow rounds reuse the batched builder's host
half verbatim (``graph.apply_pruned_rows`` / ``graph._drain_overflow``).
Per batch that moves one (B, R) int32 all-gather and one replicated
adjacency update (~N·R·4 bytes) — small next to the O(B·ell·R·D)
navigation compute it divides by S.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import graph as graph_mod
from repro.core import pq as pq_mod
from repro.core import search as search_mod
from repro.core.records import RecordStore
from repro.core.selectors import InMemory, QueryFilter
from jax import shard_map


@dataclasses.dataclass(frozen=True)
class ShardPlan:
    mesh: object
    shard_axes: tuple = ("data", "model")   # record store shards over these

    @property
    def n_shards(self) -> int:
        s = 1
        for a in self.shard_axes:
            s *= self.mesh.shape[a]
        return s


def pad_store(store: RecordStore, n_shards: int) -> RecordStore:
    """Pad N to a shard multiple (pad records are never reachable)."""
    n = store.n
    n_pad = -(-n // n_shards) * n_shards
    if n_pad == n:
        return store
    extra = n_pad - n

    def pad(arr, fill):
        widths = [(0, extra)] + [(0, 0)] * (arr.ndim - 1)
        return jnp.pad(arr, widths, constant_values=fill)

    return RecordStore(
        vectors=pad(store.vectors, 0.0),
        neighbors=pad(store.neighbors, -1),
        dense_neighbors=pad(store.dense_neighbors, -1),
        rec_labels=pad(store.rec_labels, -1),
        rec_values=pad(store.rec_values, 0.0),
        pages_std=store.pages_std, pages_dense=store.pages_dense,
        cand_first=(None if store.cand_first is None
                    else pad(store.cand_first, False)))


def store_shardings(plan: ShardPlan, store: RecordStore) -> RecordStore:
    """NamedShardings: dim-0 (vector id) over the shard axes."""
    ax = plan.shard_axes

    def shard(arr):
        spec = P(ax, *([None] * (arr.ndim - 1)))
        return NamedSharding(plan.mesh, spec)

    return RecordStore(
        vectors=shard(store.vectors), neighbors=shard(store.neighbors),
        dense_neighbors=shard(store.dense_neighbors),
        rec_labels=shard(store.rec_labels), rec_values=shard(store.rec_values),
        pages_std=store.pages_std, pages_dense=store.pages_dense,
        cand_first=(None if store.cand_first is None
                    else shard(store.cand_first)))


def _owner_pulls(store: RecordStore, safe, mine, axis_names) -> dict:
    """The shared masked-local-gather + psum record assembly.

    ``safe``/``mine`` are shard-local row indices and ownership mask for
    some set of global ids (any shape). Only the owner contributes
    nonzero rows; the psum hands every shard the full records."""
    def pull(arr, off=0):
        """psum-combine rows: only the owner contributes nonzero. For
        id-valued arrays (`off=1`) the pad -1 survives the psum by
        shifting to a non-negative domain first."""
        got = arr[safe] + off
        got = jnp.where(
            mine.reshape(mine.shape + (1,) * (got.ndim - mine.ndim)),
            got, 0)
        return jax.lax.psum(got, axis_names) - off

    rec = {
        "vectors": pull(store.vectors),
        "neighbors": pull(store.neighbors, off=1),
        "dense_neighbors": pull(store.dense_neighbors, off=1),
        "rec_labels": pull(store.rec_labels, off=1),
        "rec_values": pull(store.rec_values),
    }
    if store.cand_first is not None:
        # bool words can't ride a psum: count in int32 (owner contributes
        # 0/1, everyone else 0) and compare back. Threading these
        # precomputed first-occurrence bits through keeps the sharded
        # W=1 hop loop off the packed-sort dedup fallback.
        got = store.cand_first[safe].astype(jnp.int32)
        got = jnp.where(mine[..., None], got, 0)
        rec["cand_first"] = jax.lax.psum(got, axis_names) > 0
    return rec


def make_sharded_fetch(plan: ShardPlan, n_total: int) -> Callable:
    """Fetch-by-global-id inside shard_map: masked local gather + psum.

    Fetch contract (shared with ``search.local_fetch``): ``ids`` may be
    any shape — the fused batched hop loop issues ONE flat ``(B·W,)``
    fetch per hop for the whole query batch (and one ``(B·W·R,)`` fetch
    in strict mode), so the psum coalesces every query's reads into a
    single collective; returned arrays are ``ids.shape + record_dims``,
    including the optional ``cand_first`` dedup bits when the store
    carries them. Inside the loop the search only consults the replicated
    in-memory tier (PQ codes, Bloom words, bucket codes, the visited word
    bitmap), so the id space is defined by ``codes.shape[0]``, never by
    the local shard size. This is the replicated-queries flavor: every
    shard issues the same global id vector."""
    n_shards = plan.n_shards
    shard_size = n_total // n_shards
    axis_names = plan.shard_axes

    def fetch(store: RecordStore, ids: jax.Array) -> dict:
        # flatten the shard axes into a linear shard index
        idx = jax.lax.axis_index(axis_names)
        lo = idx * shard_size
        local = ids - lo
        mine = (local >= 0) & (local < shard_size)
        safe = jnp.where(mine, local, 0)
        return _owner_pulls(store, safe, mine, axis_names)

    return fetch


def make_batch_sharded_fetch(plan: ShardPlan, n_total: int) -> Callable:
    """The row-sharded-queries flavor of :func:`make_sharded_fetch`.

    Each shard arrives with its own rows' flat frontier ids (any local
    length ``nl``). The shards all-gather their id vectors into the
    global batch-order frontier (``tiled`` concatenation over the shard
    axes matches the row-sharding's contiguous-block order), assemble the
    full records with the same owner-psum pull, and slice back their own
    ``nl``-row block. One all-gather of ids + one psum of records per
    hop — the coalesced batched "SSD read", now also splitting the hop
    compute S ways."""
    n_shards = plan.n_shards
    shard_size = n_total // n_shards
    axis_names = plan.shard_axes

    def fetch(store: RecordStore, ids: jax.Array) -> dict:
        nl = ids.shape[0]
        idx = jax.lax.axis_index(axis_names)
        gids = jax.lax.all_gather(ids, axis_names, tiled=True)  # (S·nl,)
        local = gids - idx * shard_size
        mine = (local >= 0) & (local < shard_size)
        safe = jnp.where(mine, local, 0)
        rec = _owner_pulls(store, safe, mine, axis_names)
        return {k: jax.lax.dynamic_slice_in_dim(v, idx * nl, nl, axis=0)
                for k, v in rec.items()}

    return fetch


class ShardedSearchRunner:
    """The mesh-sharded hop engine behind ``filtered_search_pipelined``.

    Owns a padded, ID-range-sharded device copy of the record store and a
    cache of shard_map'd hop kernels keyed like the single-device bucket
    jit cache — one entry per ``(params, distance_fn)``, with jax's shape
    cache covering the driver's power-of-two bucket widths underneath
    (the compile-once property the warmup ladder and the
    ``test_sharded_compile_once`` test pin).

    ``run(ctx, st, n_hops, params, distance_fn)`` mirrors
    ``search.run_hops``'s contract — returns ``(state, int8 active
    mask)`` with ``st`` donated — but row-shards ``ctx``/``st`` over the
    mesh, swaps in the all-gather batch fetch, and terminates on the
    global active flag so every shard steps in lockstep (inactive rows
    are exact fixed points, so lockstep extra hops keep bit-identity).
    The driver's compaction/fold logic runs on the host exactly as in
    the single-device path; bucket widths stay divisible by the shard
    count because both are powers of two and the driver raises
    ``min_bucket`` to ``n_shards``.
    """

    def __init__(self, plan: ShardPlan, store: RecordStore, codes,
                 codebook, mem: InMemory):
        n_shards = plan.n_shards
        if n_shards & (n_shards - 1):
            raise ValueError(
                f"shard count must be a power of two (got {n_shards}): the "
                "driver's bucket widths must divide evenly over the mesh")
        self.plan = plan
        self.n_shards = n_shards
        padded = pad_store(store, n_shards)
        sh = store_shardings(plan, padded)
        self.store = RecordStore(
            vectors=jax.device_put(padded.vectors, sh.vectors),
            neighbors=jax.device_put(padded.neighbors, sh.neighbors),
            dense_neighbors=jax.device_put(padded.dense_neighbors,
                                           sh.dense_neighbors),
            rec_labels=jax.device_put(padded.rec_labels, sh.rec_labels),
            rec_values=jax.device_put(padded.rec_values, sh.rec_values),
            pages_std=padded.pages_std, pages_dense=padded.pages_dense,
            cand_first=(None if padded.cand_first is None else
                        jax.device_put(padded.cand_first, sh.cand_first)))
        self.codes = codes
        self.codebook = codebook
        self.mem = mem
        self._fetch = make_batch_sharded_fetch(plan, self.store.n)
        self._store_arrays = tuple(
            a for a in (self.store.vectors, self.store.neighbors,
                        self.store.dense_neighbors, self.store.rec_labels,
                        self.store.rec_values, self.store.cand_first)
            if a is not None)
        self._run_cache: dict = {}

    # -- hop kernel ------------------------------------------------------
    def run(self, ctx, st, n_hops, params, distance_fn=pq_mod.adc_lookup):
        """``run_hops`` over the mesh: (ctx, st, n_hops) -> (st', mask)."""
        key = (params, distance_fn)
        fn = self._run_cache.get(key)
        if fn is None:
            fn = self._build_run(params, distance_fn, ctx, st)
            self._run_cache[key] = fn
        return fn(*self._store_arrays, self.codes, self.mem, ctx, st,
                  n_hops)

    def _build_run(self, params, distance_fn, ctx, st):
        ax = self.plan.shard_axes
        pages_std = self.store.pages_std
        pages_dense = self.store.pages_dense
        has_cf = self.store.cand_first is not None
        n_store = len(self._store_arrays)
        fetch = self._fetch

        def global_any(mask):
            return jax.lax.psum(jnp.any(mask).astype(jnp.int32), ax) > 0

        def body(*args):
            sl = args[:n_store]
            codes_l, mem_l, ctx_l, st_l, n_hops_l = args[n_store:]
            store_l = RecordStore(
                *sl[:5], pages_std, pages_dense,
                cand_first=sl[5] if has_cf else None)
            st_l = search_mod._hop_loop(
                store_l, codes_l, mem_l, params, distance_fn, fetch,
                ctx_l, st_l, n_hops_l, active_any=global_any)
            return st_l, st_l.active.astype(jnp.int8)

        def rows(tree):   # leading dim = query rows -> shard over the mesh
            return jax.tree_util.tree_map(
                lambda l: (P(ax, *([None] * (jnp.ndim(l) - 1)))
                           if jnp.ndim(l) else P()), tree)

        def rep(tree):
            return jax.tree_util.tree_map(
                lambda l: P(*([None] * jnp.ndim(l))), tree)

        in_specs = (tuple(P(ax, *([None] * (a.ndim - 1)))
                          for a in self._store_arrays)
                    + (rep(self.codes), rep(self.mem), rows(ctx), rows(st),
                       P()))
        out_specs = (rows(st), P(ax))
        f = shard_map(body, mesh=self.plan.mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False)
        # donate st (arg layout: store leaves, codes, mem, ctx, st, n_hops)
        return jax.jit(f, donate_argnums=(n_store + 3,))

    # -- introspection (compile-once test, server stats) ----------------
    def cache_size(self) -> int:
        return len(self._run_cache)


def distributed_filtered_search(plan: ShardPlan, store: RecordStore,
                                codes, codebook, mem: InMemory,
                                qfilters: QueryFilter, queries, entry: int,
                                params: search_mod.SearchParams):
    """shard_map-wrapped single-shot beam search over the pod.

    Record-store arrays arrive sharded over plan.shard_axes; everything
    else replicated (every shard executes the full batch's control flow).
    Output replicated. ``ShardedSearchRunner`` + the pipelined driver is
    the production path; this stays the minimal mesh entry and the
    replicated-query oracle."""
    mesh = plan.mesh
    ax = plan.shard_axes
    n_total = store.n
    fetch = make_sharded_fetch(plan, n_total)
    pages_std, pages_dense = store.pages_std, store.pages_dense
    has_cf = store.cand_first is not None
    arrays = (store.vectors, store.neighbors, store.dense_neighbors,
              store.rec_labels, store.rec_values) \
        + ((store.cand_first,) if has_cf else ())
    n_store = len(arrays)

    def body(*args):
        sl = args[:n_store]
        codes_l, cents, mem_l, qf_l, q_l = args[n_store:]
        store_l = RecordStore(*sl[:5], pages_std, pages_dense,
                              cand_first=sl[5] if has_cf else None)
        cb_l = pq_mod.PQCodebook(centroids=cents, dim=codebook.dim)
        return search_mod.filtered_search(
            store_l, codes_l, cb_l, mem_l, qf_l, q_l, entry, params,
            fetch_fn=fetch)

    def rep(tree):
        return jax.tree_util.tree_map(lambda l: P(*([None] * jnp.ndim(l))),
                                      tree)

    in_specs = (tuple(P(ax, *([None] * (a.ndim - 1))) for a in arrays)
                + (rep(codes), rep(codebook.centroids), rep(mem),
                   rep(qfilters), rep(queries)))
    # output structure from the local variant (eval_shape must not trace the
    # sharded fetch: axis_index is only bound inside shard_map)
    out_shape = jax.eval_shape(
        lambda: search_mod.filtered_search(
            RecordStore(*arrays[:5], pages_std, pages_dense,
                        cand_first=arrays[5] if has_cf else None),
            codes, codebook, mem, qfilters, queries, entry, params))
    out_specs = jax.tree_util.tree_map(lambda _: P(), out_shape)

    f = shard_map(body, mesh=mesh, in_specs=in_specs,
                  out_specs=out_specs, check_vma=False)
    return f(*arrays, codes, codebook.centroids, mem, qfilters, queries)


# ---------------------------------------------------------------------------
# Sharded Vamana build
# ---------------------------------------------------------------------------

def _make_nav_prune(plan: ShardPlan, medoid: int, pell: int, r: int,
                    alpha: float, use_pq: bool, width: int = 4):
    """shard_map'd navigate+prune over one insertion batch's rows.

    Args (data, adj_ext, codes, centroids, ids): everything replicated
    except ``ids`` (the batch's insert ids), row-sharded so each shard
    navigates and RobustPrunes B/S nodes. With ``use_pq`` the beam pool
    is steered by PQ-approximate ADC distances (the build-compute cut);
    the prune re-ranks with exact full-precision distances either way.
    Returns the all-gathered (B, R) pruned rows, replicated."""
    ax = plan.shard_axes

    def body(data_l, adj_l, codes_l, cents_l, ids_l):
        q_l = data_l[ids_l]                       # (B/S, D) insert vectors

        if use_pq:
            cb = pq_mod.PQCodebook(centroids=cents_l,
                                   dim=data_l.shape[1])

            def nav_one(q):
                table = pq_mod.distance_table(cb, q)
                return graph_mod._beam_pool(
                    adj_l, medoid, pell, pell, width,
                    lambda s: pq_mod.adc_lookup(codes_l[s], table))
        else:
            def nav_one(q):
                return graph_mod._beam_pool(
                    adj_l, medoid, pell, pell, width,
                    lambda s: jnp.sum((data_l[s] - q[None, :]) ** 2,
                                      axis=1))

        pool_ids, _ = jax.vmap(nav_one)(q_l)      # (B/S, ell)
        cand = jnp.concatenate([pool_ids, adj_l[ids_l]], axis=1)
        cand = graph_mod._dedup_ascending(cand, ids_l)
        rows_l = graph_mod.robust_prune_batch(data_l, ids_l, cand,
                                              r=r, alpha=alpha)
        return jax.lax.all_gather(rows_l, ax, tiled=True)   # (B, R)

    rep2 = P(None, None)
    f = shard_map(body, mesh=plan.mesh,
                  in_specs=(rep2, rep2, rep2, rep2, P(ax)),
                  out_specs=P(), check_vma=False)
    return jax.jit(f)


def _make_replicated_prune(plan: ShardPlan, r: int, alpha: float):
    """``robust_prune_batch`` for the sharded build's overflow rows. Their
    inputs are replicated over the mesh, and the chip's compiler cannot
    partition a Pallas kernel on its own, so every device runs the same
    prune under shard_map."""
    f = shard_map(
        lambda d, p, c: graph_mod.robust_prune_batch(d, p, c, r=r,
                                                     alpha=alpha),
        mesh=plan.mesh, in_specs=(P(), P(), P()), out_specs=P(),
        check_vma=False)
    return jax.jit(f)


def build_vamana_sharded(data: np.ndarray, plan: ShardPlan, r: int = 32,
                         ell: int = 64, alpha: float = 1.2,
                         batch: int = 1024, seed: int = 0,
                         codes=None, codebook=None,
                         stage_times: dict | None = None
                         ) -> tuple[np.ndarray, int]:
    """Mesh-sharded batched Vamana build (same RNG stream / batch schedule
    as ``graph.build_vamana_batched``). Returns (adjacency, medoid).

    Each insertion batch's rows are split over the shard axes:
    navigation + RobustPrune run per shard (`_make_nav_prune`), the
    pruned rows are all-gathered, and the replicated reverse-edge scatter
    + overflow rounds reuse the single-device host half
    (``graph.apply_pruned_rows`` / ``graph._drain_overflow``) — so the
    only semantic deviation from the batched builder is the navigation
    distance when ``codes``/``codebook`` are given (PQ-approximate ADC
    pools; exact prune re-rank). The recall budget for that deviation is
    the same ±1% the builder-equivalence tests enforce.

    ``stage_times`` (optional dict) accumulates wall seconds into
    ``nav_prune_s`` (the sharded stage) and ``scatter_s`` (the replicated
    host stage) — the build benchmark's Amdahl decomposition feed.
    """
    rng = np.random.default_rng(seed)
    data = np.asarray(data, dtype=np.float32)
    n = data.shape[0]
    medoid = int(np.argmin(
        np.sum((data - data.mean(0, keepdims=True)) ** 2, 1)))

    adj0 = rng.integers(0, n, size=(n, r), dtype=np.int64).astype(np.int32)
    adj0[adj0 == np.arange(n, dtype=np.int32)[:, None]] = medoid

    # everything but the batch ids is replicated over the mesh once, up
    # front, instead of re-broadcast by every step that reads it
    rep = NamedSharding(plan.mesh, P())
    data_dev = jax.device_put(data, rep)
    adj_ext = jax.device_put(
        np.concatenate([adj0, np.full((1, r), -1, np.int32)]), rep)
    batch = min(batch, graph_mod._pow2_pad(n))
    assert batch % plan.n_shards == 0, (
        f"batch={batch} must divide over {plan.n_shards} shards")
    use_pq = codes is not None
    if use_pq:
        assert codebook is not None
        codes_dev = jax.device_put(codes, rep)
        cents_dev = jax.device_put(codebook.centroids, rep)
    else:
        # 1-row placeholders keep one body signature (dead under !use_pq)
        codes_dev = jax.device_put(np.zeros((1, 1), np.uint8), rep)
        cents_dev = jax.device_put(np.zeros((1, 1, 1), np.float32), rep)

    for pass_i, alpha_pass in enumerate((1.0, alpha)):
        pell = ell if pass_i else max(16, (2 * ell) // 3)
        nav_prune = _make_nav_prune(plan, medoid, pell, r,
                                    float(alpha_pass), use_pq)
        prune = _make_replicated_prune(plan, r, float(alpha_pass))
        order = rng.permutation(n)
        for start in range(0, n, batch):
            ids, live = graph_mod._pad_batch(
                order[start:start + batch].astype(np.int32), batch)
            t0 = time.perf_counter()
            rows = nav_prune(data_dev, adj_ext, codes_dev, cents_dev,
                             jnp.asarray(ids))
            if stage_times is not None:
                rows.block_until_ready()
                t1 = time.perf_counter()
                stage_times["nav_prune_s"] = (
                    stage_times.get("nav_prune_s", 0.0) + (t1 - t0))
            adj_ext, st, ss, overflow = graph_mod.apply_pruned_rows(
                adj_ext, jnp.asarray(ids), jnp.asarray(live), rows)
            adj_ext = graph_mod._drain_overflow(
                data_dev, adj_ext, st, ss, overflow, ids.shape[0], r,
                float(alpha_pass), prune=prune)
            if stage_times is not None:
                adj_ext.block_until_ready()
                stage_times["scatter_s"] = (
                    stage_times.get("scatter_s", 0.0)
                    + (time.perf_counter() - t1))
    return np.asarray(adj_ext[:-1]), medoid
