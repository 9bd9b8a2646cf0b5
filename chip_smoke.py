#!/usr/bin/env python3
"""Run the filtered-search main path once on a TPU and check what it returns.

    python3 chip_smoke.py              # one chip: phases a-f below
    python3 chip_smoke.py --chips 4    # four chips: the sharded path only

The corpus is the paper's large-corpus setting as ``launch/dryrun_ann.py``
encodes it (d=192 float32, R=96, R_d=1100, PQ m=32, up to 16 Zipf tags per
record, 2 numeric fields, query batches of 64, L=128, k=10), generated from
``--seed`` by ``repro.data.synth.make_filtered_dataset``, with N cut from
100M to what one chip builds inside the run's time limit (``--n``).

One chip, in one process, through the entry points a user calls:

  a. device   JAX sees a TPU (no CPU fallback) and the kernels dispatch to
              the compiled Pallas path.
  b. build    ``Index.build`` with the batched Vamana builder; the build
              program holds the ``prune_scan`` kernel.
  c. search   ``Index.search_batch`` over a tag, a numeric range and a tag
              AND two ranges at ~1%, ~10% and ~30% selectivity, under
              post / spec_in / strict_in / spec_in at W=4: zero false
              positives, recall@10 against ``Index.ground_truth``, and
              agreement with ``search.filtered_search_ref`` replayed on the
              engine's own batches. The hop program holds ``hop_fused`` and
              ``or_scatter``.
  d. kernels  each kernel against its jnp oracle on one captured input.
  e. disk     ``engine.to_disk`` with a page cache far below the slab file:
              spec_in / strict_in ids identical to the device backend.
  f. serve    ``SearchServer`` answers the spec_in requests at rung 0 with
              phase c's ids.

With ``--chips 4`` the index is built with ``shards=4`` and searched with
the engine at ``shard(4)`` and ``shard(1)``: ids and counters must be
bit-identical, and each device must hold its shard of the record store.

Each phase prints one line; any failed check exits non-zero. The last line
of a passing run is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import collections
import concurrent.futures
import copy
import dataclasses
import json
import os
import re
import shutil
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

# the paper's per-record shapes (launch/dryrun_ann.py)
DIM, R, R_DENSE, PQ_M, MAX_LABELS, BATCH, L, K = 192, 96, 1100, 32, 16, 64, 128, 10
L_BUILD = 128
N_LABELS, AVG_LABELS = 1000, 4.0
INTRINSIC_DIM = 16     # within-cluster spread, as in real embeddings
SELECTIVITIES = (0.01, 0.10, 0.30)
# (name, SearchRequest overrides)
MODES = (("post", dict(policy="post")),
         ("spec_in", dict(policy="spec_in")),
         ("strict_in", dict(policy="strict_in")),
         ("spec_in_w4", dict(policy="spec_in", beam_width=4)))
SLAB_BYTES_PER_RECORD = 3 * 4096
KERNELS = ("hop_fused", "or_scatter", "prune_scan")


class SmokeError(Exception):
    pass


def check(cond, msg: str):
    if not cond:
        raise SmokeError(msg)


_T0 = time.perf_counter()


def log(line: str):
    print(f"[{time.perf_counter() - _T0:6.0f}s] {line}", flush=True)


def kernels_in(compiled) -> set:
    """The Pallas kernels a compiled program runs as TPU custom calls."""
    found = set()
    for line in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            found.update(re.findall(r"jit\((%s)\)/pallas_call"
                                    % "|".join(KERNELS), line))
    return found


class CompileTimer:
    """Seconds JAX spent tracing, lowering and compiling while active."""

    def __init__(self):
        self.total = 0.0
        self._on = False
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, secs, **_):
        if self._on and name.startswith("/jax/core/compile/"):
            self.total += secs

    def __enter__(self):
        self.total, self._on = 0.0, True
        return self

    def __exit__(self, *exc):
        self._on = False


# ---------------------------------------------------------------------------
# corpus and filters
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Corpus:
    vectors: np.ndarray
    queries: np.ndarray
    metadata: list
    tags: list            # per record: set of tag ids
    tag_cover: np.ndarray   # records holding each tag id
    value: np.ndarray
    score: np.ndarray


def make_corpus(seed: int, n: int) -> Corpus:
    from repro.data.synth import make_filtered_dataset
    ds = make_filtered_dataset(n=n, d=DIM, n_queries=BATCH,
                               n_labels=N_LABELS, avg_labels=AVG_LABELS,
                               seed=seed, intrinsic_dim=INTRINSIC_DIM)
    score = np.random.default_rng(seed + 1).uniform(0, 1, n).astype(
        np.float32)
    tags = [set(ds.label_flat[s:e].tolist())
            for s, e in zip(ds.label_offsets[:-1], ds.label_offsets[1:])]
    rec = np.repeat(np.arange(n), np.diff(ds.label_offsets))
    pairs = np.unique(rec.astype(np.int64) * N_LABELS + ds.label_flat)
    cover = np.bincount(pairs % N_LABELS, minlength=N_LABELS)
    metadata = [{"tag": sorted(t), "value": float(v), "score": float(w)}
                for t, v, w in zip(tags, ds.values, score)]
    return Corpus(ds.vectors, ds.queries, metadata, tags, cover,
                  ds.values.astype(np.float32), score)


def make_filters(c: Corpus):
    """(name, DSL filter, exact (N,) mask from the raw corpus) for every
    shape × selectivity; the masks are computed here, independent of the
    index's attribute stores."""
    from repro.api import Num, Tag
    n = len(c.tags)

    def tag_near(frac):
        lab = int(np.argmin(np.abs(c.tag_cover / n - frac)))
        return Tag("tag") == lab, np.fromiter((lab in t for t in c.tags),
                                              bool, n)

    def range_near(field, vals, frac):
        srt = np.sort(vals)
        a = 0.5 - frac / 2
        lo = srt[int(a * n)]
        hi = srt[min(n - 1, int((a + frac) * n))]
        return (Num(field).between(float(lo), float(hi)),
                (vals >= lo) & (vals < hi))

    out = []
    for s in SELECTIVITIES:
        f, m = tag_near(s)
        out.append((f"tag@{s:.0%}", f, m))
        f, m = range_near("value", c.value, s)
        out.append((f"range@{s:.0%}", f, m))
        q = min(0.95, s ** (1 / 3))
        ft, mt = tag_near(s / (q * q))
        fv, mv = range_near("value", c.value, q)
        fs, ms = range_near("score", c.score, q)
        out.append((f"tag&2ranges@{s:.0%}", ft & fv & fs, mt & mv & ms))
    return out


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_device(chips: int):
    from repro.kernels import ops
    devs = jax.devices()
    d0 = devs[0]
    check(d0.platform == "tpu",
          f"JAX finds no TPU (platform {d0.platform!r}); no CPU fallback")
    check(len(devs) >= chips, f"{chips} chips asked, {len(devs)} found")
    check(ops.on_tpu(), "ops.on_tpu() is false on a TPU")
    log(f"phase a: device ok: platform=tpu kind={d0.device_kind} "
        f"count={len(devs)}; kernels dispatch to compiled Pallas")
    return d0, len(devs)


def build_index(c: Corpus, shards: int = 0):
    from repro.api import Index, IndexConfig, Schema, SearchConfig
    from repro.core import graph
    cfg = IndexConfig(r=R, r_dense=R_DENSE, l_build=L_BUILD, pq_m=PQ_M,
                      max_labels=MAX_LABELS, builder="batched")
    defaults = SearchConfig(k=K, l=L)
    timer = CompileTimer()
    t0 = time.perf_counter()
    with timer:
        index = Index.build(c.vectors, c.metadata, cfg,
                            schema=Schema(tags=("tag",),
                                          nums=("value", "score")),
                            defaults=defaults, shards=shards)
    wall = time.perf_counter() - t0
    st = graph.graph_stats(np.asarray(index.store.neighbors)[:len(c.tags)])
    return index, wall, timer.total, st


def phase_build(c: Corpus, dev):
    from jax.sharding import SingleDeviceSharding
    from repro.core import graph
    index, wall, comp, st = build_index(c)
    n = len(c.tags)
    check(st["min_degree"] >= 1 and st["max_degree"] <= R,
          f"degree out of range: {st}")
    # the link step as the build ran it, at the build's shapes
    sh = SingleDeviceSharding(dev)

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)

    link = graph._link_batch.lower(
        spec((n, DIM), jnp.float32), spec((n + 1, R), jnp.int32),
        spec((1024,), jnp.int32), spec((1024,), jnp.bool_),
        spec((1024, L_BUILD), jnp.int32), r=R, alpha=1.2).compile()
    found = kernels_in(link)
    check("prune_scan" in found,
          f"build program runs no prune_scan kernel (found {found})")
    log(f"phase b: build ok: N={n} cold {wall:.1f}s (compile {comp:.1f}s, "
        f"rest {wall - comp:.1f}s) degree avg {st['avg_degree']:.1f} "
        f"min {st['min_degree']} max {st['max_degree']}; "
        f"link step runs {sorted(found)}")
    return index


def make_requests(c: Corpus, filters, overrides):
    from repro.api import SearchRequest
    return [SearchRequest(query=c.queries[i], filter=filters[i % len(
        filters)][1], k=K, **overrides) for i in range(BATCH)]


def plan_groups(index, requests):
    """The coalesced batches ``Index.search_batch`` runs for ``requests``."""
    sels = [index.compile_filter(r.filter) for r in requests]
    scfgs = [dataclasses.replace(index.defaults, **r.overrides())
             for r in requests]
    return index.engine.plan_groups(np.stack([r.query for r in requests]),
                                    sels, scfgs)


def replay_ref(index, requests):
    """The engine's own batches (same params, filters, entry seeds)
    through the jnp oracle ``search.filtered_search_ref``."""
    from repro.core import search
    eng = index.engine
    ids = np.full((len(requests), K), -1, np.int64)
    sizes = {}
    for g in plan_groups(index, requests):
        check(g.params is not None, f"group routed to {g.mech!r}")
        res = search.filtered_search_ref(
            eng.store, eng.codes, eng.codebook, eng.mem, g.qfilter,
            g.queries, eng.medoid, g.params, entries=g.entries)
        ids[g.idxs] = np.asarray(res.ids)[:, :K]
        sizes[g.params.l_search] = len(g.idxs)
    return ids, sizes


def hop_kernels(index, requests) -> set:
    """Kernels in the hop program that served the largest group of
    ``requests``, lowered at the width the pipelined driver ran it."""
    from repro.core import search
    eng = index.engine
    g = max(plan_groups(index, requests), key=lambda g: len(g.idxs))
    b = max(search.MIN_COMPACT_BUCKET, 1 << (len(g.idxs) - 1).bit_length())

    def pad(a):
        a = np.asarray(a)
        return np.concatenate([a, np.repeat(a[:1], b - len(a), axis=0)])

    ctx, st = search.init_search(eng.store, eng.codes, eng.codebook,
                                 eng.mem, jax.tree.map(pad, g.qfilter),
                                 pad(g.queries), eng.medoid, g.params)
    return kernels_in(search.run_hops.lower(
        eng.store, eng.codes, eng.mem, ctx, st, 1, g.params).compile())


def padded_ids(results) -> np.ndarray:
    """(len, K) ids of ``SearchResult``s, -1 padded."""
    return np.stack([np.pad(r.ids, (0, K - r.ids.size), constant_values=-1)
                     for r in results])


def search_ids(index, requests):
    """(ids (B, K), wall seconds) of one ``search_batch``."""
    t0 = time.perf_counter()
    res = index.search_batch(requests, with_metadata=False)
    return padded_ids(res), time.perf_counter() - t0


def phase_search(index, c: Corpus, filters):
    from repro.api import recall_at_k
    masks = [filters[i % len(filters)][2] for i in range(BATCH)]
    reqs = {name: make_requests(c, filters, over) for name, over in MODES}
    # the modes, their oracle replays and the ground truth are independent:
    # run them side by side so their compiles share the host's cores
    with concurrent.futures.ThreadPoolExecutor(2 * len(MODES) + 1) as ex:
        gt_job = ex.submit(lambda: [index.ground_truth(r)
                                    for r in make_requests(c, filters, {})])
        jobs = {name: (ex.submit(search_ids, index, reqs[name]),
                       ex.submit(replay_ref, index, reqs[name]))
                for name, _ in MODES}
        gt = gt_job.result()
        out, recall = {}, {}
        for name, _ in MODES:
            (ids, wall), (ref, sizes) = (j.result() for j in jobs[name])
            fp = sum(int((~masks[i][row[row >= 0]]).sum())
                     for i, row in enumerate(ids))
            check(fp == 0, f"{name}: {fp} returned ids fail their filter")
            agree = float(np.mean(ids == ref))
            rec = float(np.mean([recall_at_k(ids[i], gt[i], K)
                                 for i in range(BATCH)]))
            rec_ref = float(np.mean([recall_at_k(ref[i], gt[i], K)
                                     for i in range(BATCH)]))
            check(agree >= 0.99, f"{name}: ids agree with the oracle on "
                                 f"only {agree:.4f} of slots")
            check(abs(rec - rec_ref) <= 0.01,
                  f"{name}: recall {rec:.4f} vs oracle {rec_ref:.4f}")
            out[name], recall[name] = (reqs[name], ids), rec
            log(f"phase c: search {name}: recall@10 {rec:.4f} (oracle "
                f"{rec_ref:.4f}), ids agree {agree:.4f}, false positives 0,"
                f" {wall:.2f}s incl. compile (modes side by side), queries "
                f"per pool size {sizes}")
    found = hop_kernels(index, out["spec_in"][0])
    check({"hop_fused", "or_scatter"} <= found,
          f"hop program runs {sorted(found)}, not hop_fused + or_scatter")
    sel = ", ".join(f"{f[0]}={f[2].mean():.4f}" for f in filters)
    log(f"phase c: search ok: selectivity {sel}; hop program runs "
        f"{sorted(found)}")
    return out, recall, gt


def phase_kernels(index, c: Corpus, filters, gt):
    """Each kernel against its jnp oracle on one input captured from the
    index: a W=4 spec_in hop slab, the rare-list table, a build prune."""
    from repro.core import graph, pq as pq_mod, selectors
    from repro.kernels import ops, ref
    eng, store = index.engine, index.engine.store
    n = len(c.tags)
    reqs = make_requests(c, filters, {})
    sels = [index.compile_filter(r.filter) for r in reqs]
    qf = selectors.stack_filters([s.plan(eng.config.ql, eng.config.cap,
                                         eng.config.qr).qfilter
                                  for s in sels])
    # hop slab: each query's 4 nearest valid records as the frontier
    front = np.stack([np.resize(g[:4] if g.size else [eng.medoid], 4)
                      for g in gt]).astype(np.int32)
    nb = np.asarray(store.neighbors)[front]
    dn = np.asarray(store.dense_neighbors)[front]
    cand = jnp.asarray(np.concatenate([nb, dn], axis=2).reshape(BATCH, -1))
    safe = jnp.maximum(cand, 0)
    tables = jax.vmap(lambda q: pq_mod.distance_table(eng.codebook, q))(
        jnp.asarray(c.queries))
    merged = selectors.merged_table_words(qf, n)
    merged_ref = ref.or_scatter_ref(
        jnp.zeros(merged.shape, jnp.int32), jnp.minimum(qf.merged_ids, n))
    check(bool(jnp.array_equal(merged, merged_ref)),
          "or_scatter differs from its oracle on the rare-list table")
    visited = ops.or_scatter(jnp.zeros((BATCH, (1 << 20) // 32), jnp.int32),
                             cand[:, :4 * R])
    check(bool(jnp.array_equal(visited, ref.or_scatter_ref(
        jnp.zeros(visited.shape, jnp.int32), cand[:, :4 * R]))),
        "or_scatter differs from its oracle on a visited-set update")
    in_merged = ((jnp.take_along_axis(merged, safe >> 5, axis=1)
                  >> (safe & 31)) & 1).astype(jnp.bool_)
    bl, bc = selectors.kernel_view(eng.mem)
    args = (eng.codes[safe], bl[safe], bc[safe], in_merged, tables,
            *selectors.kernel_filter_params(qf))
    key, ok = ops.hop_fused(*args)
    key_r, ok_r = ref.hop_fused_ref(*args)
    check(bool(jnp.array_equal(ok, ok_r)), "hop_fused ok differs from oracle")
    rel = float(jnp.max(jnp.abs(key - key_r) / jnp.maximum(
        jnp.abs(key_r), 1e-30)))
    check(rel <= 1e-5, f"hop_fused key off by {rel:.3g} relative")
    # prune: one build batch's candidates (out-edges ∪ 2-hop sample)
    p_ids = jnp.arange(1024, dtype=jnp.int32)
    cc = jnp.concatenate([store.neighbors[:1024],
                          store.dense_neighbors[:1024, :L_BUILD]], axis=1)
    cc = graph._dedup_ascending(cc, p_ids)
    dp_s, dcc, _ = graph.prune_distances(store.vectors, p_ids, cc)
    a2 = 1.2 * 1.2
    keep = ops.prune_scan(dp_s, dcc, a2, R)
    check(bool(jnp.array_equal(keep, ref.prune_scan_ref(dp_s, dcc, a2, R))),
          "prune_scan differs from its oracle")
    log(f"phase d: kernels ok: hop_fused ok bit-equal, key max rel err "
        f"{rel:.2e} over a ({BATCH}, {cand.shape[1]}) slab; or_scatter "
        f"bit-equal on the rare-list table and a visited update; "
        f"prune_scan bit-equal on (1024, {cc.shape[1]})")


def phase_disk(index, c: Corpus, searched):
    from repro.storage import DiskRecordStore, StorageConfig
    n = len(c.tags)
    slab_dir = os.path.join(ROOT, ".chip_smoke", "slabs")
    shutil.rmtree(slab_dir, ignore_errors=True)
    os.makedirs(slab_dir)
    need = n * SLAB_BYTES_PER_RECORD
    free = shutil.disk_usage(slab_dir).free
    check(free > 1.5 * need, f"slab file needs ~{need / 2**30:.1f} GiB, "
                             f"{free / 2**30:.1f} GiB free")
    pages = n * 3
    cfg = StorageConfig(cache_pages=max(1024, pages // 50))
    modes = ("spec_in", "strict_in")
    twins = {}
    for name in modes:
        twin = copy.copy(index)
        twin.engine = copy.copy(index.engine)
        twins[name] = twin
    t0 = time.perf_counter()
    twins[modes[0]].engine.to_disk(slab_dir, cfg)
    spill = time.perf_counter() - t0
    # one disk store (page cache, counters) per mode over the same slab
    # files, so the two modes run side by side without sharing state
    for name in modes[1:]:
        twins[name].engine.attach_disk_store(DiskRecordStore(slab_dir, cfg))
    parts = [f"slabs {n} records in {spill:.1f}s, cache {cfg.cache_pages} "
             f"pages ({cfg.cache_pages / pages:.1%} of file), free disk "
             f"{free / 2**30:.0f} GiB"]

    def run(name):
        return twins[name].search_batch(searched[name][0], with_stats=True,
                                        with_metadata=False)
    try:
        with concurrent.futures.ThreadPoolExecutor(len(modes)) as ex:
            jobs = {name: ex.submit(run, name) for name in modes}
            for name in modes:
                res, stats = jobs[name].result()
                check(np.array_equal(padded_ids(res), searched[name][1]),
                      f"disk {name}: ids differ from the device backend")
                d = stats.disk
                parts.append(f"{name} ids identical, hit rate "
                             f"{d['hit_rate']:.3f}, gated attr reads "
                             f"skipped {d['gated_skips']} of "
                             f"{d['attr_probes']}")
    finally:
        for twin in twins.values():
            if twin.engine.disk_store is not None:
                twin.engine.disk_store.close()
        shutil.rmtree(os.path.join(ROOT, ".chip_smoke"), ignore_errors=True)
    log("phase e: disk ok: " + "; ".join(parts))


def phase_serve(index, searched):
    from repro.serve.server import SearchServer, ServerConfig
    reqs, ids = searched["spec_in"]
    reqs = reqs + reqs
    t0 = time.perf_counter()
    with SearchServer(index, ServerConfig(max_queue=8 * len(reqs))) as srv:
        handles = srv.submit_many(reqs)
        res = [h.result(timeout=600) for h in handles]
        st = srv.stats()
    wall = time.perf_counter() - t0
    rungs = collections.Counter(h.rung for h in handles)
    check(set(rungs) == {"full"}, f"batches served at rungs {dict(rungs)}")
    check(st.degraded_served == 0 and st.rejected_overload == 0
          and st.shed_deadline == 0 and st.completed == len(reqs),
          f"server stats {st}")
    check(np.array_equal(padded_ids(res), np.concatenate([ids, ids])),
          "served ids differ from phase c")
    log(f"phase f: serve ok: {len(reqs)} requests, all at rung 0 (full), "
        f"ids equal phase c; {wall:.2f}s, p50 {st.p50_us / 1e3:.1f}ms "
        f"p99 {st.p99_us / 1e3:.1f}ms")


def phase_sharded(c: Corpus, filters, one_chip_recall: dict | None):
    from repro.api import recall_at_k
    index, wall, comp, st = build_index(c, shards=4)
    eng = index.engine
    check(eng.n_shards == 4, f"engine spans {eng.n_shards} shards")
    vec = eng._runner.store.vectors
    held = sorted(len(s.data) for s in vec.addressable_shards)
    devs = sorted(d.id for d in vec.sharding.device_set)
    check(len(devs) == 4 and held[0] == held[-1] and held[0] < vec.shape[0],
          f"record store not split over 4 devices: devices {devs}, "
          f"rows per shard {held}")
    use = [(d.memory_stats() or {}).get("bytes_in_use", 0)
           for d in jax.devices()[:4]]
    log(f"phase sharded build ok: N={len(c.tags)} shards=4 cold {wall:.1f}s "
        f"(compile {comp:.1f}s, rest {wall - comp:.1f}s) degree avg "
        f"{st['avg_degree']:.1f}; record store rows per device {held}, "
        f"device bytes in use {[f'{u / 2**30:.2f}G' for u in use]}")
    local = copy.copy(index)              # the same index at shard(1)
    local.engine = copy.copy(eng).shard(1)

    def run(ix, reqs):
        return ix.search_batch(reqs, with_stats=True, with_metadata=False)

    low = []
    with concurrent.futures.ThreadPoolExecutor(2 * len(MODES) + 1) as ex:
        gt_job = ex.submit(lambda: [index.ground_truth(r)
                                    for r in make_requests(c, filters, {})])
        jobs = {}
        for name, over in MODES:
            reqs = make_requests(c, filters, over)
            jobs[name] = (ex.submit(run, index, reqs),
                          ex.submit(run, local, reqs))
        gt = gt_job.result()
        for name, _ in MODES:
            (r4, s4), (r1, s1) = (j.result() for j in jobs[name])
            for a, b in zip(r4, r1):
                check(np.array_equal(a.ids, b.ids)
                      and np.array_equal(a.dists, b.dists),
                      f"{name}: shard(4) and shard(1) results differ")
            for f in ("io_pages", "hops", "dist_comps", "explored",
                      "fp_explored", "n_valid"):
                check(np.array_equal(getattr(s4, f), getattr(s1, f)),
                      f"{name}: counter {f} differs between shard(4) and "
                      f"shard(1)")
            rec = float(np.mean([recall_at_k(r.ids, g, K)
                                 for r, g in zip(r4, gt)]))
            note = ""
            if one_chip_recall and name in one_chip_recall:
                ref = one_chip_recall[name]
                if rec < ref - 0.01:
                    low.append(f"{name} {rec:.4f} vs {ref:.4f}")
                note = f" (one chip {ref:.4f})"
            log(f"phase sharded search {name}: shard(4) == shard(1) bit-"
                f"identical (ids, dists, counters); recall@10 "
                f"{rec:.4f}{note}")
    check(not low, "recall more than 0.01 below one chip's: "
                   + ", ".join(low))
    return len(jax.devices())


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n", type=int, default=250_000,
                    help="corpus size (the paper's 100M cut for one chip)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--one-chip-recall", default=None,
                    help="JSON {mode: recall@10} from a one-chip run at the "
                         "same --n/--seed, checked against with --chips 4")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("chip_smoke.py: the repository's src/repro is not beside "
              "this script", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.utils import compile_cache
    compile_cache.enable()
    phase = "a"
    try:
        dev, count = phase_device(args.chips)
        phase = "corpus"
        t0 = time.perf_counter()
        corpus = make_corpus(args.seed, args.n)
        filters = make_filters(corpus)
        log(f"corpus: N={args.n} (cut from 100M for {args.chips} chip(s) "
            f"and the run's time limit) d={DIM} seed={args.seed}, "
            f"{time.perf_counter() - t0:.1f}s")
        if args.chips == 4:
            phase = "sharded"
            recall = (json.loads(args.one_chip_recall)
                      if args.one_chip_recall else None)
            count = phase_sharded(corpus, filters, recall)
        else:
            phase = "b"
            index = phase_build(corpus, dev)
            phase = "c"
            searched, recall, gt = phase_search(index, corpus, filters)
            log("recall@10 per mode: " + json.dumps(recall))
            phase = "d"
            phase_kernels(index, corpus, filters, gt)
            phase = "e"
            phase_disk(index, corpus, searched)
            phase = "f"
            phase_serve(index, searched)
    except Exception as e:   # any failure: say where, print no result
        print(f"phase {phase}: FAIL: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        if not isinstance(e, SmokeError):
            import traceback
            traceback.print_exc()
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
