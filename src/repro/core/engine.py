"""FilteredANNEngine — the end-to-end system (paper §4 Fig. 4).

Query processing: per-query cost estimation routes to speculative
pre-filtering, speculative in-filtering, or post-filtering; queries are
grouped by (mechanism, pool-size bucket) and executed as batches; exact
verification piggybacks on re-ranking everywhere.

Baseline policies (paper §5.1 compared systems) are selectable:
  * ``speculative`` — the paper's system (cost-model routing).
  * ``basefilter``  — PipeANN-BaseFilter: strict pre-filtering when
                      selectivity < 1%, otherwise post-filtering.
  * ``strict_in``   — Filtered-DiskANN-like strict in-filtering.
  * ``spec_in``     — speculative in-filtering, pinned (the router's
                      ``in`` mechanism without the cost model).
  * ``strict_pre``  — Milvus-like always-pre-filtering.
  * ``post``        — always post-filtering.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cost_model, graph, io_sim, pq as pq_mod, \
    prefilter, search
from repro.core.faults import FaultPlan
from repro.core.labels import (LabelStore, build_label_store,
                               extend_label_store, padded_rows_from_csr,
                               padded_vec_labels)
from repro.core.ranges import (MultiRangeStore, RangeStore,
                               build_multi_range_store)
from repro.core import records as records_mod
from repro.core.records import RecordStore, make_record_store
from repro.core.selectors import (InMemory, Selector, stack_filters)
from repro.utils.spans import span


@dataclasses.dataclass(frozen=True)
class IndexConfig:
    r: int = 32               # Vamana out-degree
    r_dense: int = 480        # 2-hop sample size (10-20x R, paper §4.1)
    l_build: int = 64
    alpha: float = 1.2
    pq_m: int = 16            # PQ subquantizers
    pq_iters: int = 8
    max_labels: int = 16      # per-record label slots (exact verification)
    ql: int = 8               # max labels per query
    qr: int = 4               # range-predicate slots per query (NR)
    cap: int = 2048           # merged rare-list capacity
    seed: int = 0
    builder: str = "batched"  # 'batched' (device pipeline) | 'reference'


@dataclasses.dataclass(frozen=True)
class SearchConfig:
    k: int = 10
    l: int = 32               # base pool length L (recall knob)
    beam_width: int = 1
    max_hops: int = 512
    alpha: float = 10.0       # cost-model IO weight
    beta: float = 1.0
    max_pool: int = 1024      # effective-L cap
    l_rerank_delta: int = 16  # δ extra re-ranked vectors for pre-filtering
    policy: str = "speculative"
    hop_chunk: int = 32       # hops between straggler-compaction checks in
                              # the bucketed search driver (0 = single-shot
                              # jit, the pre-pipelined execution)
    prefetch_depth: int = 2   # record slabs in flight per query (feeds the
                              # modeled SSD latency; results are invariant)
    fault_plan: FaultPlan | None = None
                              # seeded fault injection on the record-read
                              # path (core/faults.py) — None serves the
                              # unmodified clean hot path


def apply_rung(scfg: SearchConfig,
               rung: "cost_model.DegradeRung") -> SearchConfig:
    """SearchConfig for one degrade-ladder rung (cost_model.DEGRADE_LADDER):
    scaled pool length / hop budget, overridden chunking and read-ahead.
    Floors keep k servable; the ``approx`` rung's config sizes its re-rank
    budget (the scan path ignores the traversal knobs)."""
    kw = dict(l=max(scfg.k, int(round(scfg.l * rung.l_scale))),
              max_hops=max(8, int(round(scfg.max_hops
                                        * rung.max_hops_scale))))
    if rung.hop_chunk is not None:
        kw["hop_chunk"] = rung.hop_chunk
    if rung.prefetch_depth is not None:
        kw["prefetch_depth"] = rung.prefetch_depth
    return dataclasses.replace(scfg, **kw)


def scan_rerank(scfg: SearchConfig,
                rung: "cost_model.DegradeRung | None" = None) -> int:
    """Re-rank budget of the gated full-scan path for a *base* config,
    optionally as scaled by ``rung`` — must match the sizing
    ``approx_scan`` applies to its (already rung-applied) configs so the
    admission controller prices exactly what would execute."""
    l = scfg.l if rung is None else max(scfg.k,
                                        int(round(scfg.l * rung.l_scale)))
    return int(min(scfg.max_pool, max(l + scfg.l_rerank_delta,
                                      2 * scfg.k)))


@dataclasses.dataclass
class QueryStats:
    mechanism: list
    io_pages: np.ndarray
    est_io_pages: np.ndarray
    dist_comps: np.ndarray
    est_compute: np.ndarray
    hops: np.ndarray
    fp_explored: np.ndarray
    explored: np.ndarray
    n_valid: np.ndarray
    selectivity: np.ndarray
    precision_in: np.ndarray
    faults: np.ndarray        # injected fault events (0 without a plan)
    retries: np.ndarray       # extra read attempts issued by the ladder
    degraded: np.ndarray      # rows answered from the in-memory fallback
    disk: dict | None = None  # disk-tier counter delta for this batch
                              # (cache hits/misses/hit_rate, pages_read,
                              # readahead, gated_skips, measured p50 page
                              # latency) — None on the device backend

    @classmethod
    def empty(cls) -> "QueryStats":
        return cls(mechanism=[], io_pages=np.zeros(0, np.int64),
                   est_io_pages=np.zeros(0), dist_comps=np.zeros(0, np.int64),
                   est_compute=np.zeros(0), hops=np.zeros(0, np.int64),
                   fp_explored=np.zeros(0, np.int64),
                   explored=np.zeros(0, np.int64),
                   n_valid=np.zeros(0, np.int64), selectivity=np.zeros(0),
                   precision_in=np.zeros(0), faults=np.zeros(0, np.int64),
                   retries=np.zeros(0, np.int64),
                   degraded=np.zeros(0, np.int64), disk=None)


@dataclasses.dataclass
class SearchGroup:
    """One coalesced batch of :meth:`FilteredANNEngine.execute`: the
    queries (``idxs`` into the request batch) routed to one mechanism at
    one pool-size bucket and config. ``params``/``entries``/``seed_pages``
    are set for the graph-search mechanisms (in/post) only."""
    mech: str
    eff_l: int
    scfg: SearchConfig
    idxs: list
    strict: bool
    queries: np.ndarray
    qfilter: object
    params: "search.SearchParams | None" = None
    entries: np.ndarray | None = None
    seed_pages: np.ndarray | None = None


class FilteredANNEngine:
    def __init__(self, store: RecordStore, codes, codebook, mem: InMemory,
                 label_store: LabelStore, range_store: MultiRangeStore,
                 medoid: int, config: IndexConfig):
        self.store = store
        self.codes = codes
        self.codebook = codebook
        self.mem = mem
        self.label_store = label_store
        self.range_store = range_store
        self.medoid = medoid
        self.config = config
        self.n = label_store.n_vectors  # valid records (store may hold pads)
        self._builder = None      # lazy IncrementalBuilder (insert path)
        self._runner = None       # ShardedSearchRunner when shard()ed
        self.calibration: cost_model.Calibration | None = None
        self.disk_store = None    # storage.DiskRecordStore when backend=disk
        self.io_model: io_sim.IOModel | None = None
                                  # fitted from measured reads (calibrate_io)

    def calibrate(self, source="BENCH_search.json") -> bool:
        """Swap the router's hardcoded per-hop compute constants for the
        fused pipeline's measured counters (dist_comps / approx_checks /
        hops per hop, from a BENCH_search.json payload or a prebuilt
        :class:`~repro.core.cost_model.Calibration`). Opt-in: routing
        stays analytic until called. Returns True when calibration data
        was found and installed; ``calibrate(None)`` reverts."""
        if source is None or isinstance(source, cost_model.Calibration):
            self.calibration = source
        elif isinstance(source, dict):
            try:
                self.calibration = cost_model.Calibration.from_bench(source)
            except (KeyError, TypeError, ValueError):
                # malformed/trimmed payload: degrade to uncalibrated, the
                # same contract as an unreadable path
                self.calibration = None
        else:
            self.calibration = cost_model.load_calibration(source)
        return self.calibration is not None

    @property
    def n_fields(self) -> int:
        return self.range_store.n_fields

    # ------------------------------------------------------------------
    @classmethod
    def build(cls, vectors: np.ndarray, label_offsets: np.ndarray,
              label_flat: np.ndarray, n_labels: int, values: np.ndarray,
              config: IndexConfig = IndexConfig(),
              shards: int = 0) -> "FilteredANNEngine":
        """``values`` is the numeric attribute matrix, (n, F) — a flat
        (n,) array is accepted as the single-field F=1 case.

        ``shards > 1`` builds AND serves over a local mesh of that many
        devices: the Vamana link phase runs per shard with PQ-approximate
        navigation (``distributed.build_vamana_sharded`` — the codebook is
        trained first so ADC distances can steer the beam pools; the
        RobustPrune re-rank stays exact, recall within the batched
        builder's ±1% envelope), and the returned engine is already
        :meth:`shard`-ed so ``execute`` routes the hop loop through the
        mesh."""
        vectors = np.asarray(vectors, np.float32)
        n, d = vectors.shape
        # pad dim to a multiple of pq_m
        if d % config.pq_m:
            pad = config.pq_m - d % config.pq_m
            vectors = np.pad(vectors, ((0, 0), (0, pad)))
            d += pad

        # PQ first: the sharded builder navigates on ADC distances
        key = jax.random.PRNGKey(config.seed)
        codebook = pq_mod.train_pq(key, jnp.asarray(vectors), config.pq_m,
                                   iters=config.pq_iters)
        codes = pq_mod.encode_pq(codebook, jnp.asarray(vectors))

        if shards > 1:
            if config.builder != "batched":
                raise ValueError(
                    "shards > 1 requires builder='batched' (the sharded "
                    f"link path), got {config.builder!r}")
            from repro.core.distributed import ShardPlan, \
                build_vamana_sharded
            from repro.launch.mesh import make_local_mesh
            plan = ShardPlan(mesh=make_local_mesh(1, shards),
                             shard_axes=("model",))
            adj, medoid = build_vamana_sharded(
                vectors, plan, config.r, config.l_build, config.alpha,
                seed=config.seed, codes=codes, codebook=codebook)
        elif config.builder == "batched":
            adj, medoid = graph.build_vamana_batched(
                vectors, config.r, config.l_build, config.alpha,
                seed=config.seed)
        elif config.builder == "reference":
            adj, medoid = graph.build_vamana(vectors, config.r,
                                             config.l_build, config.alpha,
                                             seed=config.seed)
        else:
            raise ValueError(f"unknown builder {config.builder!r}")
        dense = graph.densify_2hop(adj, config.r_dense, seed=config.seed + 1)

        label_store = build_label_store(label_offsets, label_flat, n_labels)
        range_store = build_multi_range_store(values)
        rec_labels = padded_vec_labels(label_store, config.max_labels)

        store = make_record_store(vectors, adj, dense, rec_labels,
                                  range_store.values)

        mem = InMemory(blooms=jnp.asarray(label_store.blooms),
                       bucket_codes=jnp.asarray(range_store.bucket_codes))
        eng = cls(store, codes, codebook, mem, label_store, range_store,
                  medoid, config)
        if shards > 1:
            eng.shard(shards)
        return eng

    # ------------------------------------------------------------------
    def shard(self, shards: int) -> "FilteredANNEngine":
        """Route the pipelined hop loop through a mesh of ``shards``
        devices (``distributed.ShardedSearchRunner``): the record store is
        ID-range-sharded over the mesh's model axis, queries row-shard per
        bucket, and results stay bit-identical to the single-device driver
        (docs/distributed.md). ``shards in (0, 1)`` reverts to local
        execution. In place; returns self. Requires the device backend —
        the disk tier already owns the fetch seam."""
        if shards in (0, 1):
            self._runner = None
            return self
        if self.disk_store is not None:
            raise ValueError(
                "sharded execution requires the device backend: the disk "
                "tier's host fetch already owns the fetch_fn seam "
                "(shard before to_disk, or serve from the device store)")
        from repro.core.distributed import ShardPlan, ShardedSearchRunner
        from repro.launch.mesh import make_local_mesh
        plan = ShardPlan(mesh=make_local_mesh(1, shards),
                         shard_axes=("model",))
        self._runner = ShardedSearchRunner(plan, self.store, self.codes,
                                           self.codebook, self.mem)
        return self

    @property
    def n_shards(self) -> int:
        """Mesh shards the hop loop spans (1 = local single-device)."""
        return self._runner.n_shards if self._runner is not None else 1

    # ------------------------------------------------------------------
    def to_disk(self, path: str, storage_config=None) -> "FilteredANNEngine":
        """Switch this engine to the disk backend (storage/disk.py).

        The record arrays are spilled to page-aligned slab files at
        ``path`` and replaced by a 1-row stub carrying only shapes and
        page counts — the device tier keeps PQ codes + bloom/bucket
        words, every record byte flows through the disk store's fetch
        callable. Results are bit-identical to the device backend (the
        slabs hold the exact same float32/int32 values). In place;
        returns self.
        """
        from repro.storage import DiskRecordStore, StorageConfig
        cfg = storage_config or StorageConfig()
        ds = DiskRecordStore.from_record_store(path, self.store, n=self.n,
                                               config=cfg)
        self.attach_disk_store(ds)
        return self

    def attach_disk_store(self, disk_store) -> None:
        """Adopt an already-open :class:`~repro.storage.DiskRecordStore`
        (e.g. from a restored checkpoint) and drop the device arrays."""
        self.disk_store = disk_store
        self.store = disk_store.stub_store()
        self._runner = None   # sharded runner holds device copies; disk owns
                              # the fetch seam now

    def calibrate_io(self) -> "io_sim.IOModel | None":
        """Fit :class:`io_sim.IOModel` from the disk tier's measured read
        samples, replacing the modeled constants for latency reporting.
        Returns the fitted model (None without a disk store or samples)."""
        if self.disk_store is None or not self.disk_store.samples:
            return None
        self.io_model = io_sim.IOModel.calibrate_from_samples(
            self.disk_store.samples,
            page_bytes=self.disk_store.layout.page_bytes)
        return self.io_model

    # ------------------------------------------------------------------
    def insert(self, vectors: np.ndarray, label_offsets: np.ndarray,
               label_flat: np.ndarray, n_labels: int,
               values: np.ndarray) -> np.ndarray:
        """Append records through the incremental batched build path.

        New nodes are linked by a single final-α pass (greedy search from
        the medoid → batched RobustPrune → reverse-edge scatter). Stores
        are **capacity-padded**: device arrays are allocated at the
        builder's geometric capacity (pad rows unreachable — no edge points
        at them, labels -1, values 0) and new rows are written in place, so
        steady-state inserts keep every array shape stable and the search
        path compiles once instead of re-specializing per insert. Host
        attribute summaries extend incrementally (label postings merge at
        run ends, per-field sorted indexes merge via searchsorted; bucket
        boundaries stay fixed so approx codes remain comparable). The PQ
        codebook is *not* retrained — inserted vectors are encoded against
        the build-time centroids. Inserts always link through the batched
        pipeline regardless of ``config.builder`` — a
        ``builder='reference'`` graph becomes mixed after the first insert
        (fine for serving; rebuild if you need a pure oracle graph for
        A/B comparisons). Returns the new record ids.
        """
        cfg = self.config
        if self.disk_store is not None:
            raise NotImplementedError(
                "insert is not supported on the disk backend: slab files "
                "are append-closed in this release — rebuild the index "
                "(or insert on the device backend, then to_disk)")
        vectors = np.asarray(vectors, np.float32)
        m = vectors.shape[0]
        if m == 0:
            return np.zeros(0, np.int64)
        # store.dim may exceed the build-time input dim only by the pq_m
        # alignment pad, so any narrower batch is a caller error, not a
        # padding case — reject it rather than storing zero-padded geometry
        if not (self.store.dim - cfg.pq_m < vectors.shape[1]
                <= self.store.dim):
            raise ValueError(
                f"vector dim {vectors.shape[1]} does not match index dim "
                f"{self.store.dim} (built from inputs of dim in "
                f"({self.store.dim - cfg.pq_m}, {self.store.dim}])")
        if vectors.shape[1] < self.store.dim:
            vectors = np.pad(
                vectors, ((0, 0), (0, self.store.dim - vectors.shape[1])))
        values = np.asarray(values, np.float32)
        if values.ndim == 1:
            values = values[:, None]
        if values.shape != (m, self.n_fields):
            raise ValueError(
                f"expected ({m}, {self.n_fields}) values, got {values.shape}")
        if self._builder is None:
            self._builder = graph.IncrementalBuilder(
                np.asarray(self.store.vectors)[:self.n],
                np.asarray(self.store.neighbors)[:self.n], self.medoid,
                ell=cfg.l_build, alpha=cfg.alpha)
        n0 = self.n
        ids = self._builder.add_batch(vectors)

        # host attribute summaries: incremental extension (no rebuild)
        self.label_store = extend_label_store(
            self.label_store, np.asarray(label_offsets, np.int64),
            np.asarray(label_flat, np.int32), int(n_labels))
        self.range_store = self.range_store.append(values)

        self._refresh_padded_stores(n0, m, vectors)
        self.n = n0 + m
        if self._runner is not None:
            # the runner holds its own padded device copy of the store —
            # rebuild it over the same mesh so sharded serving sees the
            # inserted records
            self.shard(self._runner.n_shards)
        return ids

    def _refresh_padded_stores(self, n0: int, m: int, new_vectors):
        """Sync the capacity-padded device tier after a host-store extend.

        When capacity is unchanged (the steady state) only the m new rows
        are written; a capacity growth reallocates every array once at the
        new capacity. ``dense_neighbors`` is resampled over the grown graph
        either way — edges of *existing* nodes change when inserts scatter
        reverse edges into them.
        """
        cfg = self.config
        cap = self._builder.capacity
        n_new = n0 + m
        adj_dev = self._builder.adjacency_device          # (cap, R)
        dense = graph.densify_2hop(np.asarray(adj_dev), cfg.r_dense,
                                   seed=cfg.seed + 1)
        # new rows come from the *extended* label store's CSR slice, which
        # has already deduped (vector, label) pairs — padding the raw input
        # instead could drop a real label past the max_labels slots that
        # the host inverted index still serves (false negatives)
        ls = self.label_store
        row_start = int(ls.vec_offsets[n0])
        new_rec_labels = padded_rows_from_csr(
            ls.vec_offsets[n0:] - row_start, ls.vec_labels[row_start:],
            cfg.max_labels)
        # slice per field, then stack: the MultiRangeStore matrix properties
        # materialize all N rows — O(m·F) here, not O(N·F) per insert
        new_values = np.stack([s.values[n0:n_new]
                               for s in self.range_store.stores], axis=1)
        new_codes = pq_mod.encode_pq(self.codebook, jnp.asarray(new_vectors))
        new_blooms = ls.blooms[n0:n_new]
        new_buckets = np.stack([s.bucket_codes[n0:n_new]
                                for s in self.range_store.stores], axis=1)
        # a skewed-stream quantile refresh re-derives the bucket bounds and
        # re-codes EVERY row (ranges.RangeStore.append): the device code
        # column must be replaced wholesale — writing only the new rows
        # would mix codes from two incompatible bounds generations and
        # break the no-false-negative contract of is_member_approx
        rebucketed = self.range_store.bounds_refreshed

        grown = self.store.vectors.shape[0] != cap
        if grown:
            def pad_to_cap(arr_np, fill, dtype):
                out = np.full((cap,) + arr_np.shape[1:], fill, dtype)
                out[:arr_np.shape[0]] = arr_np
                return jnp.asarray(out)

            rec_labels = pad_to_cap(
                np.asarray(self.store.rec_labels)[:n0], -1, np.int32)
            rec_values = pad_to_cap(
                np.asarray(self.store.rec_values)[:n0], 0.0, np.float32)
            codes = pad_to_cap(np.asarray(self.codes)[:n0], 0, np.uint8)
            blooms = pad_to_cap(
                np.asarray(self.mem.blooms)[:n0], 0, np.uint32)
            buckets = pad_to_cap(
                np.asarray(self.mem.bucket_codes)[:n0], 0, np.uint8)
        else:
            rec_labels = self.store.rec_labels
            rec_values = self.store.rec_values
            codes = self.codes
            blooms = self.mem.blooms
            buckets = self.mem.bucket_codes

        # donated row writes (graph.write_rows): steady-state inserts reuse
        # the capacity-padded buffers in place instead of paying the
        # O(capacity) functional-update copy per array (ROADMAP item). The
        # pre-insert arrays are consumed — holders of a stale
        # ``engine.store``/``engine.mem`` must re-read after insert.
        rec_labels = graph.write_rows(
            rec_labels, jnp.asarray(new_rec_labels, rec_labels.dtype), n0)
        rec_values = graph.write_rows(
            rec_values, jnp.asarray(new_values, rec_values.dtype), n0)
        self.codes = graph.write_rows(codes, new_codes.astype(codes.dtype),
                                      n0)
        if rebucketed:
            full_buckets = np.zeros((cap, self.n_fields), np.uint8)
            full_buckets[:n_new] = self.range_store.bucket_codes
            buckets_dev = jnp.asarray(full_buckets).astype(buckets.dtype)
        else:
            buckets_dev = graph.write_rows(
                buckets, jnp.asarray(new_buckets, buckets.dtype), n0)
        self.mem = InMemory(
            blooms=graph.write_rows(
                blooms, jnp.asarray(new_blooms, blooms.dtype), n0),
            bucket_codes=buckets_dev)
        self.store = RecordStore(
            vectors=self._builder.data_device, neighbors=adj_dev,
            dense_neighbors=jnp.asarray(dense), rec_labels=rec_labels,
            rec_values=rec_values, pages_std=self.store.pages_std,
            pages_dense=self.store.pages_dense,
            # the 2-hop sample was just resampled, so the per-record
            # first-occurrence mask is re-derived with it (pad rows are
            # all -1 ⇒ all-False, unreachable anyway)
            cand_first=jnp.asarray(records_mod.candidate_first_mask(
                np.asarray(adj_dev), dense)))

    # ------------------------------------------------------------------
    def cost_inputs(self, plan, scfg: SearchConfig) -> cost_model.CostInputs:
        """The router's CostInputs for one planned query — also the serve
        tier's admission/degrade-ladder pricing basis."""
        return cost_model.CostInputs(
            n=self.n, l=scfg.l, s=plan.selectivity,
            p_pre=plan.precision_pre, p_in=plan.precision_in,
            x_pre=plan.pages_prescan, x_in=plan.pages_prefetch,
            r=self.store.degree,
            r_d=self.store.degree + self.store.dense_degree,
            s_r=self.store.pages_std, s_d=self.store.pages_dense)

    def estimate_cost(self, selector: Selector,
                      scfg: SearchConfig = None,
                      rung: "cost_model.DegradeRung | None" = None) -> float:
        """Modeled service cost of one query (α·pages + β·comps) at the
        routed mechanism — the admission controller's per-request unit,
        scaled into µs by the server's measured EWMA. ``rung`` prices the
        query at a degrade-ladder step instead of full service."""
        scfg = scfg or SearchConfig()
        cfg = self.config
        plan = selector.plan(cfg.ql, cfg.cap, cfg.qr)
        c = self.cost_inputs(plan, scfg)
        if rung is not None:
            return cost_model.rung_cost(
                c, rung, scfg.alpha, scfg.beta, scfg.max_pool,
                base_prefetch=scfg.prefetch_depth,
                rerank=scan_rerank(scfg, rung), calib=self.calibration)
        route = self._route(plan, scfg)
        return route.costs[route.mechanism].total(scfg.alpha, scfg.beta)

    def _route(self, plan, scfg: SearchConfig) -> cost_model.Route:
        c = self.cost_inputs(plan, scfg)
        full = cost_model.route_query(c, scfg.alpha, scfg.beta,
                                      scfg.max_pool, calib=self.calibration)
        if plan.force_mech is not None:
            # the selector cannot be expressed by the device filter algebra;
            # only the forced mechanism preserves correctness (MaskSelector)
            mech = plan.force_mech
        elif scfg.policy == "speculative":
            return full
        elif scfg.policy == "basefilter":
            mech = "pre" if plan.selectivity < 0.01 else "post"
        elif scfg.policy in ("strict_in", "spec_in"):
            mech = "in"
        elif scfg.policy == "strict_pre":
            mech = "pre"
        elif scfg.policy == "post":
            mech = "post"
        else:
            raise ValueError(scfg.policy)
        # strict in-filtering traverses without bridge nodes, so its pool is
        # sized by the strict branch of the shared formula (ROADMAP: weak
        # recall at small L came from reusing the speculative bridge-regime
        # pool here)
        strict_in = scfg.policy == "strict_in" and mech == "in"
        eff_l = full.effective_l if (mech == full.mechanism
                                     and not strict_in) else \
            cost_model.effective_l(mech, c, scfg.max_pool, strict=strict_in)
        return cost_model.Route(mech, full.costs, eff_l)

    # ------------------------------------------------------------------
    def execute(self, queries: np.ndarray, selectors: Sequence[Selector],
                scfgs: Sequence[SearchConfig]):
        """The batched request path (paper §4 Fig. 4, generalized).

        Each query carries its own ``SearchConfig``; queries are grouped by
        (mechanism, pool-size bucket, config) and executed as coalesced
        batches. Returns ``(ids_list, dists_list, QueryStats)`` where the
        i-th list entries are (k_i,) arrays — per-query k may differ.
        """
        queries = self._padded(queries)
        B = queries.shape[0]
        assert len(selectors) == B and len(scfgs) == B
        cfg = self.config

        with span("engine.route", size=B):
            plans = [s.plan(cfg.ql, cfg.cap, cfg.qr) for s in selectors]
            routes = [self._route(p, sc) for p, sc in zip(plans, scfgs)]
            groups = list(self._groups(queries, selectors, scfgs, plans,
                                       routes))

        out_ids: list = [None] * B
        out_d: list = [None] * B
        stats = QueryStats(
            mechanism=[r.mechanism for r in routes],
            io_pages=np.zeros(B, np.int64),
            est_io_pages=np.array(
                [r.costs[r.mechanism].io_pages for r in routes]),
            dist_comps=np.zeros(B, np.int64),
            est_compute=np.array(
                [r.costs[r.mechanism].compute for r in routes]),
            hops=np.zeros(B, np.int64),
            fp_explored=np.zeros(B, np.int64),
            explored=np.zeros(B, np.int64),
            n_valid=np.zeros(B, np.int64),
            selectivity=np.array([p.selectivity for p in plans]),
            precision_in=np.array([p.precision_in for p in plans]),
            faults=np.zeros(B, np.int64),
            retries=np.zeros(B, np.int64),
            degraded=np.zeros(B, np.int64),
        )

        ds = self.disk_store
        disk_before = ds.snapshot() if ds is not None else None
        for g in groups:
            with span("engine.group", mech=g.mech, width=len(g.idxs)):
                self._run_group(g, selectors, plans, out_ids, out_d, stats)
        if ds is not None:
            stats.disk = ds.delta(disk_before, ds.snapshot())
        return out_ids, out_d, stats

    def _run_group(self, g: SearchGroup, selectors, plans, out_ids, out_d,
                   stats: QueryStats) -> None:
        """Run one coalesced group and copy its per-query results out."""
        idxs, scfg = g.idxs, g.scfg
        ds = self.disk_store
        if ds is not None:
            # arm the disk tier with this group's knobs: the fault plan
            # (host draws must mirror the traced ladder) and the
            # read-ahead window (depth − 1 scales it)
            ds.fault_plan = scfg.fault_plan
            ds.prefetch_depth = scfg.prefetch_depth
        if g.mech == "pre":
            # the re-rank pool scales with the superset's precision
            # (effective_l = L/p_pre + L): a speculative AND scans only its
            # cheapest branch, so only ~p_pre of the superset is valid —
            # L+δ alone would starve multi-predicate queries
            pp = prefilter.PrefilterParams(
                l_rerank=g.eff_l + scfg.l_rerank_delta, k=scfg.k)
            res = prefilter.prefilter_search(
                self.store, self.codes, self.codebook,
                [selectors[i] for i in idxs], g.qfilter, g.queries, pp,
                speculative=not g.strict,
                host_fetch=ds.fetch_host if ds is not None else None)
            with span("engine.collect", width=len(idxs)):
                for j, i in enumerate(idxs):
                    out_ids[i] = np.asarray(res.ids[j])
                    out_d[i] = np.asarray(res.dists[j])
                    stats.io_pages[i] = int(res.io_pages[j])
                    stats.dist_comps[i] = int(res.dist_comps[j])
                    stats.n_valid[i] = int(res.n_valid[j])
            return
        # the bucketed pipelined driver: chunked hops + straggler
        # compaction (search.filtered_search_pipelined); hop_chunk=0 falls
        # back to the single-shot jit
        res = search.filtered_search_pipelined(
            self.store, self.codes, self.codebook, self.mem,
            g.qfilter, g.queries, self.medoid, g.params,
            entries=g.entries, hop_chunk=scfg.hop_chunk,
            **({"fetch_fn": ds.fetch_callable}
               if ds is not None else
               {"runner": self._runner}
               if self._runner is not None else {}))
        spec_in = g.params.mode == "spec_in"
        with span("engine.collect", width=len(idxs)):
            for j, i in enumerate(idxs):
                out_ids[i] = np.asarray(res.ids[j])
                out_d[i] = np.asarray(res.dists[j])
                stats.io_pages[i] = int(res.io_pages[j]) + int(
                    g.seed_pages[j]) + (
                    int(plans[i].pages_prefetch) if spec_in else 0)
                stats.dist_comps[i] = int(res.dist_comps[j])
                stats.hops[i] = int(res.hops[j])
                stats.fp_explored[i] = int(res.fp_explored[j])
                stats.explored[i] = int(res.explored[j])
                stats.n_valid[i] = int(res.n_valid[j])
                stats.faults[i] = int(res.faults[j])
                stats.retries[i] = int(res.retries[j])
                stats.degraded[i] = int(res.degraded[j])

    def _padded(self, queries) -> np.ndarray:
        """Queries zero-padded to the store's (pq_m-aligned) dim."""
        queries = np.asarray(queries, np.float32)
        pad = self.store.dim - queries.shape[1]
        return np.pad(queries, ((0, 0), (0, pad))) if pad else queries

    def plan_groups(self, queries: np.ndarray,
                    selectors: Sequence[Selector],
                    scfgs: Sequence[SearchConfig]) -> list:
        """The coalesced batches :meth:`execute` runs, with the exact
        inputs each one hands its search path — so a caller can replay a
        group through another implementation (``search.filtered_search_ref``)
        on identical parameters."""
        queries = self._padded(queries)
        cfg = self.config
        plans = [s.plan(cfg.ql, cfg.cap, cfg.qr) for s in selectors]
        routes = [self._route(p, sc) for p, sc in zip(plans, scfgs)]
        return list(self._groups(queries, selectors, scfgs, plans, routes))

    def _groups(self, queries, selectors, scfgs, plans, routes):
        groups: dict = {}
        for i, r in enumerate(routes):
            eff = 1 << max(5, math.ceil(math.log2(max(r.effective_l, 1))))
            eff = min(eff, scfgs[i].max_pool)
            groups.setdefault((r.mechanism, eff, scfgs[i]), []).append(i)
        for (mech, eff_l, scfg), idxs in groups.items():
            strict = scfg.policy in ("strict_in", "strict_pre", "basefilter")
            # keep batch assembly on the host: the raw group width is
            # composition-dependent, and the pipelined driver pads it to a
            # power-of-two bucket before anything touches the device
            g = SearchGroup(
                mech=mech, eff_l=eff_l, scfg=scfg, idxs=idxs, strict=strict,
                queries=np.ascontiguousarray(queries[idxs]),
                qfilter=stack_filters([plans[i].qfilter for i in idxs]))
            if mech == "pre":
                yield g
                continue
            mode = {"in": "strict_in" if scfg.policy == "strict_in"
                    else "spec_in", "post": "post"}[mech]
            g.params = search.SearchParams(
                l_search=eff_l, k=scfg.k, beam_width=scfg.beam_width,
                max_hops=scfg.max_hops, mode=mode, l_valid=scfg.l,
                prefetch_depth=scfg.prefetch_depth,
                fault_plan=scfg.fault_plan)
            g.seed_pages = np.zeros(len(idxs), np.int64)
            if mode == "strict_in":
                # strict in-filtering needs exactly-valid entry seeds:
                # its pool admits only valid records, so starting at the
                # medoid strands the search whenever no valid record is
                # reachable through valid nodes (the baseline's analogue
                # of Filtered-DiskANN's per-label entry points). The
                # seeds come from a query-time attribute-index scan, so
                # its pages are charged to the query — arbitrary range /
                # composite filters cannot be precomputed offline.
                ents = np.full((len(idxs), 4), -1, np.int32)
                for j, i in enumerate(idxs):
                    seeds, pages = _strict_seed_ids(selectors[i],
                                                    self.medoid, 4)
                    ents[j, :seeds.size] = seeds
                    g.seed_pages[j] = pages
                g.entries = ents
            yield g

    # ------------------------------------------------------------------
    def approx_scan(self, queries: np.ndarray,
                    selectors: Sequence[Selector],
                    scfgs: Sequence[SearchConfig]):
        """Last-rung degrade execution (serve overload ladder): a gated
        full-corpus ADC scan over the in-memory code tier, then exact
        fetch + verification of the top re-rank set — no graph traversal,
        no per-hop device round-trips, I/O bounded by the re-rank budget.

        Same return shape as :meth:`execute`. The contract matches PR 7's
        fault ladder: candidate generation is approximate (ADC order +
        superset membership gate over *every* id — no valid record can be
        excluded), results are exactly verified (no false positives), and
        served queries are flagged via ``stats.degraded``."""
        queries = self._padded(queries)
        B = queries.shape[0]
        assert len(selectors) == B and len(scfgs) == B
        cfg = self.config
        plans = [s.plan(cfg.ql, cfg.cap, cfg.qr) for s in selectors]
        out_ids: list = [None] * B
        out_d: list = [None] * B
        stats = QueryStats(
            mechanism=["scan"] * B,
            io_pages=np.zeros(B, np.int64), est_io_pages=np.zeros(B),
            dist_comps=np.zeros(B, np.int64), est_compute=np.zeros(B),
            hops=np.zeros(B, np.int64), fp_explored=np.zeros(B, np.int64),
            explored=np.zeros(B, np.int64), n_valid=np.zeros(B, np.int64),
            selectivity=np.array([p.selectivity for p in plans]),
            precision_in=np.array([p.precision_in for p in plans]),
            faults=np.zeros(B, np.int64), retries=np.zeros(B, np.int64),
            degraded=np.ones(B, np.int64))
        ds = self.disk_store
        disk_before = ds.snapshot() if ds is not None else None
        qjn = jnp.asarray(queries)
        for i in range(B):
            scfg = scfgs[i]
            rerank = int(min(scfg.max_pool,
                             max(scfg.l + scfg.l_rerank_delta,
                                 2 * scfg.k)))
            qf = plans[i].qfilter
            top_ids, _ = prefilter.scan_all_gated(
                self.codes, self.codebook, self.mem, qf, qjn[i], rerank,
                prefilter.SCAN_CHUNK)
            pp = prefilter.PrefilterParams(l_rerank=rerank, k=scfg.k)
            if ds is None:
                ids, dists, io, nv = prefilter._rerank_verify(
                    self.store, qf, qjn[i], top_ids, pp)
            else:
                tid = np.asarray(top_ids)
                rec = ds.fetch_host(np.where(tid >= 0, tid, 0))
                ids, dists, io, nv = prefilter._verify_fetched(
                    qf, qjn[i], top_ids, jnp.asarray(rec["vectors"]),
                    jnp.asarray(rec["rec_labels"]),
                    jnp.asarray(rec["rec_values"]), pp,
                    self.store.pages_std)
            est = cost_model.approx_scan_cost(
                self.cost_inputs(plans[i], scfg), rerank)
            out_ids[i] = np.asarray(ids)
            out_d[i] = np.asarray(dists)
            stats.io_pages[i] = int(io)
            stats.est_io_pages[i] = est.io_pages
            stats.dist_comps[i] = int(self.codes.shape[0])
            stats.est_compute[i] = est.compute
            stats.explored[i] = rerank
            stats.n_valid[i] = int(nv)
        if ds is not None:
            stats.disk = ds.delta(disk_before, ds.snapshot())
        return out_ids, out_d, stats

    # ------------------------------------------------------------------
    def search(self, queries: np.ndarray, selectors: Sequence[Selector],
               scfg: SearchConfig = SearchConfig()):
        """Returns (ids (B,k), dists (B,k), QueryStats).

        Thin wrapper over :meth:`execute` with one shared SearchConfig."""
        if len(selectors) == 0:
            return (np.zeros((0, scfg.k), np.int32),
                    np.zeros((0, scfg.k), np.float32), QueryStats.empty())
        ids, dists, stats = self.execute(queries, selectors,
                                         [scfg] * len(selectors))
        return (np.stack(ids).astype(np.int32),
                np.stack(dists).astype(np.float32), stats)


def _strict_seed_ids(sel: Selector, medoid: int,
                     e: int) -> tuple[np.ndarray, int]:
    """Entry seeds for strict in-filtering: up to ``e`` exactly-valid
    records, evenly spaced over the attribute index scan (diverse starting
    regions), plus the scan's page count. Falls back to the medoid when
    the filter matches nothing."""
    from repro.core.prefilter import _strict_scan
    ids, pages = _strict_scan(sel)
    ids = np.asarray(ids)
    ids = ids[ids >= 0]
    if ids.size == 0:
        return np.array([medoid], np.int32), int(pages)
    take = np.linspace(0, ids.size - 1, num=min(e, ids.size)).astype(np.int64)
    return np.unique(ids[take]).astype(np.int32), int(pages)


def brute_force_filtered(vectors: np.ndarray, rec_labels: np.ndarray,
                         rec_values: np.ndarray, qfilter, query: np.ndarray,
                         k: int) -> np.ndarray:
    """Exact ground truth: top-k valid ids by full-precision distance."""
    from repro.core.selectors import is_member
    ok = np.asarray(is_member(qfilter, jnp.asarray(rec_labels),
                              jnp.asarray(rec_values)))
    d = np.sum((vectors - query[None, :]) ** 2, axis=1)
    d = np.where(ok, d, np.inf)
    order = np.argsort(d)[:k]
    return order[np.isfinite(d[order])]


def recall_at_k(result_ids: np.ndarray, gt_ids: np.ndarray, k: int) -> float:
    gt = set(int(x) for x in gt_ids[:k])
    if not gt:
        return 1.0
    got = set(int(x) for x in result_ids[:k] if x >= 0)
    return len(got & gt) / len(gt)
