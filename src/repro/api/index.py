"""``Index`` — the public facade over the filtered-ANN engine.

Callers hand over vectors plus one plain metadata dict per record; the
facade owns the attribute :class:`~repro.api.schema.Schema`, the tag
vocabulary, CSR label arrays, attribute stores, and the engine build.
Categorical values (str/int/bool, or lists thereof) become labels in a
per-field namespace; every ``Schema.nums`` field becomes one column of
the dense ``(n, F)`` numeric value matrix — queries may then AND range
predicates over several numeric fields and still compile onto the device
verification path.

The facade is also the DSL compiler's catalog: ``Tag``/``Num`` expressions
resolve against its schema/vocabulary, and results come back with metadata
re-resolved from the attribute stores (so ``save``/``load`` round-trips
need no sidecar record storage).
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Optional, Sequence

import jax.numpy as jnp
import numpy as np

from repro.api.filters import (FilterExpr, _check_fields, compile_expr,
                               eval_mask)
from repro.api.schema import Schema
from repro.api.types import RequestStats, SearchRequest, SearchResult
from repro.ckpt import checkpoint as ckpt
from repro.core import pq as pq_mod
from repro.core.engine import (FilteredANNEngine, IndexConfig, QueryStats,
                               SearchConfig)
from repro.core.labels import LabelStore, build_label_store
from repro.core.ranges import MultiRangeStore, RangeStore
from repro.core.records import RecordStore
from repro.core.selectors import (InMemory, MaskSelector, MatchAllSelector,
                                  Selector)
from repro.utils.spans import span

_META_FILE = "index_meta.json"
_FORMAT = 2          # checkpoint format: 2 = schema-first multi-field


def _is_numeric(v) -> bool:
    return isinstance(v, (float, np.floating)) and not isinstance(v, bool)


def _norm_tag(v):
    """Canonical (hashable, JSON-able) form of a tag value."""
    if isinstance(v, (bool, np.bool_)):
        return bool(v)
    if isinstance(v, (int, np.integer)):
        return int(v)
    if isinstance(v, str):
        return v
    raise TypeError(f"unsupported tag value {v!r} "
                    "(tags must be str/int/bool)")


def _ingest_metadata(metadata: Sequence[dict], schema: Schema,
                     vocab: Optional[dict] = None):
    """Plain per-record dicts -> (vocab, CSR labels, (n, F) values).

    Pass an existing ``vocab`` to extend it in place (the insert path:
    unseen (field, value) pairs get fresh label ids appended after the
    build-time vocabulary). The schema is strict: every record must carry
    every numeric field (the value matrix is dense), tag fields may be
    sparse, and keys outside the schema are rejected — a live index cannot
    grow an attribute column retroactively.
    """
    if vocab is None:
        vocab = {}              # (field, value) -> label id
    num_col = {f: j for j, f in enumerate(schema.nums)}
    flat: list = []
    offsets = np.zeros(len(metadata) + 1, np.int64)
    values = np.zeros((len(metadata), schema.n_fields), np.float32)
    for i, d in enumerate(metadata):
        n_tags = 0
        seen: set = set()       # dedupe repeated tags within one record
        for key, v in d.items():
            if key in num_col:
                if not _is_numeric(v) and not isinstance(v, (int, np.integer)) \
                        or isinstance(v, bool):
                    raise ValueError(
                        f"record {i}: numeric field {key!r} holds "
                        f"non-numeric value {v!r}")
                values[i, num_col[key]] = float(v)
                continue
            if key not in schema.tags:
                kind = "numeric" if _is_numeric(v) else "tag"
                raise ValueError(
                    f"record {i}: field {key!r} is not in the index schema "
                    f"(tags={list(schema.tags)}, nums={list(schema.nums)}); "
                    f"a new {kind} field cannot be added to a built index")
            for tag in (v if isinstance(v, (list, tuple, set, frozenset))
                        else (v,)):
                if _is_numeric(tag):
                    raise ValueError(
                        f"record {i}: float value in tag field {key!r} "
                        f"(numeric fields: {list(schema.nums)})")
                pair = (key, _norm_tag(tag))
                if pair in seen:
                    continue
                seen.add(pair)
                lab = vocab.setdefault(pair, len(vocab))
                flat.append(lab)
                n_tags += 1
        for f in schema.nums:
            if f not in d:
                raise ValueError(
                    f"record {i} is missing the numeric field "
                    f"{f!r}; every record needs a value "
                    "(the range store is dense)")
        offsets[i + 1] = offsets[i] + n_tags
    label_flat = np.asarray(flat, np.int32)
    return vocab, offsets, label_flat, values


class Index:
    """Filtered vector index with a declarative, schema-first query surface."""

    def __init__(self, engine: FilteredANNEngine, vocab: dict,
                 schema: Schema,
                 defaults: SearchConfig = SearchConfig()):
        self.engine = engine
        self.vocab = vocab                      # (field, value) -> label id
        self.schema = schema
        self.defaults = defaults
        self._label_names = [None] * len(vocab)  # label id -> (field, value)
        for (field, value), lab in vocab.items():
            self._label_names[lab] = (field, value)

    # -- construction ---------------------------------------------------
    @classmethod
    def build(cls, vectors: np.ndarray, metadata: Sequence[dict],
              config: IndexConfig = IndexConfig(),
              schema: Optional[Schema] = None,
              numeric_field: Optional[str] = None,
              defaults: SearchConfig = SearchConfig(),
              store: str = "device",
              storage_dir: Optional[str] = None,
              storage_config=None,
              shards: int = 0) -> "Index":
        """Build an index over ``vectors`` + per-record metadata dicts.

        ``schema`` declares the attribute fields explicitly; when omitted
        it is inferred from the metadata (float values ⇒ numeric fields,
        everything else ⇒ tag fields). ``numeric_field`` is the deprecated
        single-field spelling — it pins ``Schema.nums`` to that one field
        and will be removed after one release; pass a Schema instead.

        ``store="disk"`` spills the built records to page-aligned slab
        files (docs/storage.md) at ``storage_dir`` (a temp dir when
        omitted) and serves every record read through the disk tier's
        page cache — results are bit-identical to the device backend.
        ``storage_config`` is a :class:`repro.storage.StorageConfig`
        (cache size, read-ahead, device budget). Inserts require the
        device backend.

        ``shards > 1`` builds and serves over a local mesh of that many
        devices (docs/distributed.md): the Vamana link phase shards with
        PQ-approximate navigation and the engine comes back pre-sharded,
        so :class:`~repro.api.session.Session` / the serve tier run the
        hop loop through the mesh transparently — results bit-identical
        to ``shards=0``'s search (build graphs differ within the ±1%
        recall envelope). Mutually exclusive with ``store="disk"``.
        """
        if store not in ("device", "disk"):
            raise ValueError(f"unknown store backend {store!r} "
                             "(expected 'device' or 'disk')")
        if shards > 1 and store == "disk":
            raise ValueError("shards > 1 requires the device backend: "
                             "the disk tier owns the fetch seam")
        vectors = np.asarray(vectors, np.float32)
        if len(metadata) != vectors.shape[0]:
            raise ValueError(f"{vectors.shape[0]} vectors but "
                             f"{len(metadata)} metadata dicts")
        if schema is None:
            if numeric_field is not None:
                # legacy spelling: skip inference entirely (as the
                # pre-schema path did) — the named field is the one
                # numeric column, every other key is a tag field
                fields = {k for d in metadata for k in d}
                schema = Schema(tags=tuple(sorted(fields
                                                  - {numeric_field})),
                                nums=(numeric_field,))
            else:
                schema = Schema.infer(metadata)
        elif numeric_field is not None:
            raise ValueError("pass either schema= or the deprecated "
                             "numeric_field=, not both")
        vocab, offsets, label_flat, values = _ingest_metadata(metadata,
                                                              schema)
        engine = FilteredANNEngine.build(
            vectors, offsets, label_flat, max(1, len(vocab)), values, config,
            shards=shards)
        if store == "disk":
            if storage_dir is None:
                import tempfile
                storage_dir = tempfile.mkdtemp(prefix="repro_slabs_")
            engine.to_disk(storage_dir, storage_config)
        return cls(engine, vocab, schema, defaults)

    def insert(self, vectors: np.ndarray,
               metadata: Sequence[dict]) -> np.ndarray:
        """Append records to a live index (streaming inserts).

        New nodes are linked through the engine's incremental batched build
        path; tag values unseen at build time extend the vocabulary (the
        *schema* is fixed — records must carry every ``Schema.nums`` field
        and may not introduce new fields). Returns the assigned record ids
        (contiguous, ``len(index)`` before the call onward). Previously
        compiled ``Selector`` objects hold the pre-insert attribute stores
        — recompile filters (or go through the DSL, which compiles per
        search) after inserting.
        """
        vectors = np.asarray(vectors, np.float32)
        if vectors.ndim != 2:
            raise ValueError(f"expected (M, D) vectors, got {vectors.shape}")
        if len(metadata) != vectors.shape[0]:
            raise ValueError(f"{vectors.shape[0]} vectors but "
                             f"{len(metadata)} metadata dicts")
        if vectors.shape[0] == 0:
            return np.zeros(0, np.int64)
        new_vocab = dict(self.vocab)
        new_vocab, offsets, label_flat, values = _ingest_metadata(
            metadata, self.schema, vocab=new_vocab)
        ids = self.engine.insert(vectors, offsets, label_flat,
                                 max(1, len(new_vocab)), values)
        # commit the vocabulary only after the engine accepted the batch
        self.vocab = new_vocab
        self._label_names.extend([None] * (len(new_vocab)
                                           - len(self._label_names)))
        for (field, value), lab in new_vocab.items():
            if self._label_names[lab] is None:
                self._label_names[lab] = (field, value)
        return ids

    # -- catalog duck type (used by the filter compiler) ----------------
    @property
    def label_store(self) -> LabelStore:
        return self.engine.label_store

    @property
    def range_store(self) -> MultiRangeStore:
        return self.engine.range_store

    @property
    def store(self) -> RecordStore:
        return self.engine.store

    @property
    def config(self) -> IndexConfig:
        return self.engine.config

    @property
    def n_vectors(self) -> int:
        return self.engine.n

    @property
    def ql(self) -> int:
        return self.engine.config.ql

    @property
    def qr(self) -> int:
        return self.engine.config.qr

    @property
    def numeric_field(self) -> Optional[str]:
        """Deprecated single-field accessor: the first schema numeric
        field (None when the index has none). Use ``index.schema.nums``."""
        return self.schema.nums[0] if self.schema.nums else None

    def label_id(self, field: str, value) -> Optional[int]:
        try:
            return self.vocab.get((field, _norm_tag(value)))
        except TypeError:
            return None

    def __len__(self) -> int:
        return self.n_vectors

    @property
    def dim(self) -> int:
        return self.engine.store.dim

    # -- metadata resolution --------------------------------------------
    def record_metadata(self, rec_id: int) -> dict:
        """Re-resolve one record's metadata dict from the attribute stores.

        Multi-valued tag fields come back as sorted lists."""
        out: dict = {}
        for lab in self.label_store.labels_of(rec_id):
            field, value = self._label_names[int(lab)]
            if field in out:
                prev = out[field] if isinstance(out[field], list) \
                    else [out[field]]
                out[field] = sorted(prev + [value], key=repr)
            else:
                out[field] = value
        for j, field in enumerate(self.schema.nums):
            out[field] = float(
                self.range_store.field_store(j).values[rec_id])
        return out

    # -- query path ------------------------------------------------------
    def compile_filter(self, f) -> Selector:
        if f is None:
            return MatchAllSelector(self.n_vectors)
        if isinstance(f, Selector):
            return f
        return compile_expr(f, self)

    def _resolve_scfg(self, request: SearchRequest) -> SearchConfig:
        over = request.overrides()
        return dataclasses.replace(self.defaults, **over) if over \
            else self.defaults

    def search_batch(self, requests: Sequence[SearchRequest],
                     with_stats: bool = False,
                     with_metadata: bool = True,
                     scfgs: Optional[Sequence[SearchConfig]] = None):
        """Execute a batch through the grouped request path.

        Returns list[SearchResult] (plus the raw batched QueryStats when
        ``with_stats``). ``with_metadata=False`` skips the host-side
        per-hit metadata resolution (benchmark timing paths). ``scfgs``
        replaces the per-request config resolution wholesale — the serve
        tier's degrade ladder passes rung-adjusted configs here while the
        requests themselves stay untouched."""
        if not requests:
            return ([], QueryStats.empty()) if with_stats else []
        queries, selectors, scfgs = self._prepare(requests, scfgs)
        ids, dists, stats = self.engine.execute(queries, selectors, scfgs)
        return self._assemble(requests, ids, dists, stats, with_stats,
                              with_metadata)

    def approx_scan_batch(self, requests: Sequence[SearchRequest],
                          with_stats: bool = False,
                          with_metadata: bool = True,
                          scfgs: Optional[Sequence[SearchConfig]] = None):
        """Execute a batch through the last-rung degrade path (gated
        full-corpus ADC scan + exact verify — ``engine.approx_scan``).
        Same surface as :meth:`search_batch`; results are flagged via
        ``stats.degraded``."""
        if not requests:
            return ([], QueryStats.empty()) if with_stats else []
        queries, selectors, scfgs = self._prepare(requests, scfgs)
        ids, dists, stats = self.engine.approx_scan(queries, selectors,
                                                    scfgs)
        return self._assemble(requests, ids, dists, stats, with_stats,
                              with_metadata)

    def _prepare(self, requests, scfgs):
        with span("index.prepare", size=len(requests)):
            queries = np.stack([np.asarray(r.query, np.float32).reshape(-1)
                                for r in requests])
            if queries.shape[1] > self.dim:
                raise ValueError(f"query dim {queries.shape[1]} exceeds "
                                 f"index dim {self.dim}")
            selectors = [self.compile_filter(r.filter) for r in requests]
            if scfgs is None:
                scfgs = [self._resolve_scfg(r) for r in requests]
            else:
                scfgs = list(scfgs)
                assert len(scfgs) == len(requests)
            return queries, selectors, scfgs

    def _assemble(self, requests, ids, dists, stats, with_stats,
                  with_metadata):
        with span("index.assemble", size=len(requests),
                  metadata=with_metadata):
            results = []
            for i in range(len(requests)):
                meta = [self.record_metadata(int(x))
                        if with_metadata and x >= 0 else None
                        for x in ids[i]]
                results.append(SearchResult(
                    ids=np.asarray(ids[i]), dists=np.asarray(dists[i]),
                    metadata=meta,
                    stats=RequestStats.from_query_stats(stats, i)))
            return (results, stats) if with_stats else results

    def search(self, request: SearchRequest) -> SearchResult:
        return self.search_batch([request])[0]

    def ground_truth(self, request: SearchRequest) -> np.ndarray:
        """Exact filtered top-k ids by brute force (for recall evaluation).

        Store arrays are trimmed to the valid record count — after inserts
        the capacity-padded device arrays carry unreachable pad rows that
        must not enter the host scan."""
        from repro.core.engine import brute_force_filtered
        k = request.k if request.k is not None else self.defaults.k
        n = self.n_vectors
        q = np.asarray(request.query, np.float32).reshape(-1)
        if q.shape[0] > self.dim:
            raise ValueError(f"query dim {q.shape[0]} exceeds index "
                             f"dim {self.dim}")
        if q.shape[0] != self.dim:
            q = np.pad(q, (0, self.dim - q.shape[0]))
        if self.engine.disk_store is not None:
            # disk backend: the device tier is a stub — stream the records
            # off the slab files (cache-bypassing scan)
            recs = self.engine.disk_store.scan_records(0, n)
            vecs, rl, rv = (recs["vectors"], recs["rec_labels"],
                            recs["rec_values"])
        else:
            vecs = np.asarray(self.store.vectors)[:n]
            rl = np.asarray(self.store.rec_labels)[:n]
            rv = np.asarray(self.store.rec_values)[:n]
        f = request.filter
        if f is None or isinstance(f, FilterExpr):
            if f is not None:
                _check_fields(f, self)
            mask, _ = eval_mask(f, self)
        elif isinstance(f, MaskSelector):
            mask = np.zeros(n, bool)
            mask[f.valid_ids] = True
        elif isinstance(f, Selector):
            plan = f.plan(self.config.ql, self.config.cap, self.config.qr)
            return brute_force_filtered(vecs, rl, rv, plan.qfilter, q, k)
        else:
            raise TypeError(f"unsupported filter {f!r}")
        d = np.sum((vecs - q[None, :]) ** 2, axis=1)
        d = np.where(mask, d, np.inf)
        order = np.argsort(d)[:k]
        return order[np.isfinite(d[order])]

    # -- persistence -----------------------------------------------------
    def _array_tree(self) -> dict:
        """Checkpoint leaves (format 2). Device arrays are trimmed to the
        valid record count — capacity pads are a live-index artifact, not
        index state. Per-field range structures save stacked: (F, n) sorted
        indexes, (F, B+1) bounds, (F, Q) quantiles, (n, F) values/codes."""
        e = self.engine
        n = e.n
        ls, rs = e.label_store, e.range_store
        if e.disk_store is not None:
            # disk backend: record data lives in the slab files (copied
            # alongside the step by ``save``), not in checkpoint leaves —
            # the device tier holds only a shape stub
            store_leaves = {}
        else:
            store_leaves = {
                "store_vectors": np.asarray(e.store.vectors)[:n],
                "store_neighbors": np.asarray(e.store.neighbors)[:n],
                "store_dense_neighbors":
                    np.asarray(e.store.dense_neighbors)[:n],
                "store_rec_labels": np.asarray(e.store.rec_labels)[:n],
                "store_rec_values": np.asarray(e.store.rec_values)[:n],
            }
        return {
            **store_leaves,
            "pq_codes": np.asarray(e.codes)[:n],
            "pq_centroids": np.asarray(e.codebook.centroids),
            "ls_vec_offsets": ls.vec_offsets, "ls_vec_labels": ls.vec_labels,
            "ls_inv_offsets": ls.inv_offsets,
            "ls_inv_postings": ls.inv_postings,
            "ls_label_counts": ls.label_counts, "ls_blooms": ls.blooms,
            "rs_values": rs.values,
            "rs_sorted_values": np.stack([s.sorted_values
                                          for s in rs.stores]),
            "rs_sorted_ids": np.stack([s.sorted_ids for s in rs.stores]),
            "rs_bucket_bounds": np.stack([s.bucket_bounds
                                          for s in rs.stores]),
            "rs_bucket_codes": rs.bucket_codes,
            "rs_quantiles": np.stack([s.quantiles for s in rs.stores]),
        }

    def save(self, path: str, injector=None):
        """Persist via the ckpt subsystem (atomic step dir + manifest) plus
        a JSON sidecar for the schema, vocabulary, and static config.

        Steps increment per save and the last two are kept, so a save that
        lands corrupted (bit rot, injected faults) still leaves the
        previous intact step for ``load`` to fall back to. The sidecar is
        written both at the root (back-compat, newest wins) and inside the
        step dir — array shapes may differ across steps after inserts, so
        fallback must read the meta that matches the step it restores."""
        tree = self._array_tree()
        prev = ckpt.latest_step(path)
        step = 0 if prev is None else prev + 1
        ckpt.save(path, step=step, tree=tree, async_write=False,
                  keep_last=2, injector=injector)
        e = self.engine
        slab_meta = {}
        if e.disk_store is not None:
            # slab files ride inside the step dir so the keep-last GC and
            # quarantine fallback govern them with the array leaves; meta
            # (carrying their digest) is written after the copy, so a
            # crash mid-copy leaves a step without meta → load falls
            # through to the previous intact step
            import shutil
            from repro.storage import slab as slab_mod
            slab_dir = os.path.join(path, f"step_{step}", "slabs")
            os.makedirs(slab_dir, exist_ok=True)
            for fn in (slab_mod.SLAB_FILE, slab_mod.META_FILE):
                shutil.copy2(os.path.join(e.disk_store.path, fn),
                             os.path.join(slab_dir, fn))
            slab_meta = {
                "backend": "disk",
                "slab_sha256": ckpt.file_digest(
                    os.path.join(slab_dir, slab_mod.SLAB_FILE)),
            }
        meta = {
            "format": _FORMAT,
            **slab_meta,
            "config": dataclasses.asdict(e.config),
            "defaults": dataclasses.asdict(self.defaults),
            "medoid": int(e.medoid),
            "schema": self.schema.to_json(),
            "codebook_dim": int(e.codebook.dim),
            "pages_std": int(e.store.pages_std),
            "pages_dense": int(e.store.pages_dense),
            "n_labels": int(e.label_store.n_labels),
            "k_hashes": int(e.label_store.k_hashes),
            "vocab": [[f, v, lab] for (f, v), lab in self.vocab.items()],
            "arrays": {k: {"shape": list(a.shape), "dtype": str(a.dtype)}
                       for k, a in tree.items()},
        }
        for meta_path in (os.path.join(path, _META_FILE),
                          os.path.join(path, f"step_{step}", _META_FILE)):
            with open(meta_path, "w") as fh:
                json.dump(meta, fh)

    @classmethod
    def load(cls, path: str, shards: int = 0) -> "Index":
        """Load a saved index, recovering from corrupted steps.

        ``shards > 1`` re-shards the restored device-backend engine over a
        local mesh (:meth:`FilteredANNEngine.shard`) — checkpoints carry
        no mesh state, so the shard count is a load-time serving choice.
        Rejected for disk-backend checkpoints (the disk tier owns the
        fetch seam).

        Startup first reaps stale ``step_K.tmp`` dirs (a killed writer's
        leftovers are never valid — publishes are atomic renames). Steps
        are then tried newest-first: one that fails integrity
        verification (checksum mismatch, truncated leaf, shape/dtype
        drift) is quarantined as ``step_K.quarantined`` and the previous
        step is restored instead; only when no intact step remains does
        the corruption error propagate.

        Format-1 checkpoints (the pre-schema single-numeric-field layout:
        flat ``(n,)`` range arrays + a ``numeric_field`` name) are mapped
        onto the F=1 case of the multi-field layout by a one-release
        back-compat shim — a legacy index loads and answers unchanged.
        """
        import jax
        ckpt.reap_tmp(path)
        steps = sorted(ckpt._list_steps(path), reverse=True)
        if not steps:
            raise FileNotFoundError(f"no checkpoint steps in {path}")
        t = meta = None
        for n_try, step in enumerate(steps):
            # per-step sidecar when present (array shapes track the step);
            # the root sidecar only describes the newest save
            meta_fn = os.path.join(path, f"step_{step}", _META_FILE)
            if not os.path.exists(meta_fn):
                meta_fn = os.path.join(path, _META_FILE)
            try:
                with open(meta_fn) as fh:
                    meta = json.load(fh)
                target = {k: jax.ShapeDtypeStruct(tuple(v["shape"]),
                                                  np.dtype(v["dtype"]))
                          for k, v in meta["arrays"].items()}
                t = ckpt.restore(path, step, target)
                if meta.get("backend") == "disk":
                    # the slab file is checkpoint payload too: digest it
                    # against the sidecar before serving from it
                    from repro.storage import slab as slab_mod
                    sl = os.path.join(path, f"step_{step}", "slabs",
                                      slab_mod.SLAB_FILE)
                    if ckpt.file_digest(sl) != meta.get("slab_sha256"):
                        raise ckpt.CheckpointCorruptionError(
                            f"step {step}: slab file checksum mismatch")
                break
            except (ckpt.CheckpointCorruptionError, json.JSONDecodeError,
                    OSError):
                ckpt.quarantine(path, step)
                if n_try == len(steps) - 1:
                    raise
        t = {k: np.asarray(v) for k, v in t.items()}
        legacy = meta.get("format", 1) < 2
        if legacy:
            t, meta = _shim_legacy_checkpoint(t, meta)

        from repro.core.records import candidate_first_mask
        disk = meta.get("backend") == "disk"
        if disk:
            from repro.storage import DiskRecordStore
            ds = DiskRecordStore(os.path.join(path, f"step_{step}",
                                              "slabs"))
            # record data (incl. the precomputed cand_first bits) serves
            # from the restored slabs; the device tier gets the stub
            store = ds.stub_store()
            n_rec = ds.n
        else:
            ds = None
            store = RecordStore(
                vectors=jnp.asarray(t["store_vectors"]),
                neighbors=jnp.asarray(t["store_neighbors"]),
                dense_neighbors=jnp.asarray(t["store_dense_neighbors"]),
                rec_labels=jnp.asarray(t["store_rec_labels"]),
                rec_values=jnp.asarray(t["store_rec_values"]),
                pages_std=meta["pages_std"],
                pages_dense=meta["pages_dense"],
                # derived, not checkpointed: re-precompute the per-record
                # dedup mask from the loaded graph rows
                cand_first=jnp.asarray(candidate_first_mask(
                    t["store_neighbors"], t["store_dense_neighbors"])))
            n_rec = store.n
        label_store = LabelStore(
            n_vectors=n_rec, n_labels=meta["n_labels"],
            vec_offsets=t["ls_vec_offsets"], vec_labels=t["ls_vec_labels"],
            inv_offsets=t["ls_inv_offsets"],
            inv_postings=t["ls_inv_postings"],
            label_counts=t["ls_label_counts"], blooms=t["ls_blooms"],
            k_hashes=meta["k_hashes"])
        range_store = MultiRangeStore([
            RangeStore(
                n_vectors=n_rec, values=t["rs_values"][:, j],
                sorted_values=t["rs_sorted_values"][j],
                sorted_ids=t["rs_sorted_ids"][j],
                bucket_bounds=t["rs_bucket_bounds"][j],
                bucket_codes=t["rs_bucket_codes"][:, j],
                quantiles=t["rs_quantiles"][j])
            for j in range(t["rs_values"].shape[1])])
        codebook = pq_mod.PQCodebook(
            centroids=jnp.asarray(t["pq_centroids"]),
            dim=meta["codebook_dim"])
        mem = InMemory(blooms=jnp.asarray(label_store.blooms),
                       bucket_codes=jnp.asarray(range_store.bucket_codes))
        engine = FilteredANNEngine(
            store, jnp.asarray(t["pq_codes"]), codebook, mem, label_store,
            range_store, meta["medoid"], IndexConfig(**meta["config"]))
        if ds is not None:
            engine.attach_disk_store(ds)
        if shards > 1:
            engine.shard(shards)   # raises on the disk backend
        vocab = {(f, v): lab for f, v, lab in meta["vocab"]}
        defaults = dict(meta["defaults"])
        if isinstance(defaults.get("fault_plan"), dict):
            # dataclasses.asdict flattened the plan into a nested dict
            from repro.core.faults import FaultPlan
            defaults["fault_plan"] = FaultPlan(**defaults["fault_plan"])
        return cls(engine, vocab, Schema.from_json(meta["schema"]),
                   SearchConfig(**defaults))


def _shim_legacy_checkpoint(t: dict, meta: dict) -> tuple[dict, dict]:
    """Map a format-1 (single numeric field) checkpoint onto F=1 arrays.

    Legacy layout: ``store_rec_values``/``rs_values``/``rs_bucket_codes``
    are flat ``(n,)``, per-field structures have no leading F axis, and the
    sidecar names a ``numeric_field`` instead of a schema. Tag fields are
    reconstructed from the vocabulary (legacy metas stored no field list).
    """
    t = dict(t)
    meta = dict(meta)
    for key in ("store_rec_values", "rs_values", "rs_bucket_codes"):
        if t[key].ndim == 1:
            t[key] = t[key][:, None]
    for key in ("rs_sorted_values", "rs_sorted_ids", "rs_bucket_bounds",
                "rs_quantiles"):
        if t[key].ndim == 1:
            t[key] = t[key][None]
    numeric_field = meta.pop("numeric_field", None)
    tag_fields = sorted({f for f, _, _ in meta["vocab"]})
    meta["schema"] = {"tags": tag_fields,
                      "nums": [numeric_field] if numeric_field else []}
    meta["format"] = _FORMAT
    return t, meta
