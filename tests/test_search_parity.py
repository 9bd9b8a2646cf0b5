"""Fused/pipelined hop pipeline A/B suite (docs/perf.md).

* Parity: the fused batched ``filtered_search`` against the jnp oracle
  ``filtered_search_ref`` across all three modes × three selectivities —
  recall@10 within 1%, identical ``io_pages``/``explored`` counters.
* Compaction parity: the bucketed driver ``filtered_search_pipelined``
  (chunked hops + straggler compaction) returns a bit-identical
  ``SearchResult`` vs the single-shot jit across the same grid, and its
  per-bucket jit cache compiles once per bucket.
* Compile artifacts: the hop bodies (single-shot AND chunked runner)
  contain no op that broadcasts against the ``res_cap`` explored buffer,
  and their loop conditions never sort it (the incremental-bound
  invariant). The legacy baseline is walked too, as a canary that the
  checker actually catches the pathology it guards against.
* Session-driven repeat searches hit the bucketed search jit caches
  (compile once).
"""
import functools

import numpy as np
import pytest

import jax
import jax.extend.core
import jax.numpy as jnp

from repro.core import engine as eng
from repro.core import search as search_mod
from repro.core.selectors import stack_filters

pytestmark = pytest.mark.fast   # build shared via the session-scoped cache


# ---------------------------------------------------------------------------
# A/B parity: fused vs reference oracle
# ---------------------------------------------------------------------------

SELECTIVITIES = (0.05, 0.30, 0.80)


def _range_selectors(e, selectivity: float, n_queries: int):
    from repro.data.synth import make_sliding_range_selectors
    return make_sliding_range_selectors(e, selectivity, n_queries)


def _run_mode(e, ds, mode, selectivity, impl):
    sels = _range_selectors(e, selectivity, ds.queries.shape[0])
    qf = stack_filters([s.plan(e.config.ql, e.config.cap).qfilter
                        for s in sels])
    queries = jnp.asarray(ds.queries)
    params = search_mod.SearchParams(l_search=48, k=10, max_hops=200,
                                     beam_width=2, mode=mode, l_valid=32)
    entries = None
    if mode == "strict_in":
        ents = np.full((len(sels), 4), -1, np.int32)
        for j, s in enumerate(sels):
            seeds, _ = eng._strict_seed_ids(s, e.medoid, 4)
            ents[j, :seeds.size] = seeds
        entries = jnp.asarray(ents)
    res = impl(e.store, e.codes, e.codebook, e.mem, qf, queries, e.medoid,
               params, entries=entries)
    return sels, res


def _recalls(ds, e, sels, res, k=10):
    vectors = np.asarray(e.store.vectors)
    rl = np.asarray(e.store.rec_labels)
    rv = np.asarray(e.store.rec_values)
    out = []
    for i, s in enumerate(sels):
        plan = s.plan(e.config.ql, e.config.cap)
        q = ds.queries[i]
        if q.shape[0] != vectors.shape[1]:
            q = np.pad(q, (0, vectors.shape[1] - q.shape[0]))
        gt = eng.brute_force_filtered(vectors, rl, rv, plan.qfilter, q, k)
        out.append(eng.recall_at_k(np.asarray(res.ids[i]), gt, k))
    return np.array(out)


@pytest.mark.parametrize("mode", ["post", "spec_in", "strict_in"])
@pytest.mark.parametrize("selectivity", SELECTIVITIES)
def test_fused_matches_reference(shared_ds, shared_engine, mode,
                                 selectivity):
    ds, e = shared_ds, shared_engine
    sels, fused = _run_mode(e, ds, mode, selectivity,
                            search_mod.filtered_search)
    _, ref = _run_mode(e, ds, mode, selectivity,
                       search_mod.filtered_search_ref)
    # identical exploration: the paper's algorithmic counters must agree
    # exactly — the fused pipeline is an implementation, not an algorithm
    # change (visited-set table is exact at this corpus size)
    np.testing.assert_array_equal(np.asarray(fused.io_pages),
                                  np.asarray(ref.io_pages))
    np.testing.assert_array_equal(np.asarray(fused.explored),
                                  np.asarray(ref.explored))
    np.testing.assert_array_equal(np.asarray(fused.hops),
                                  np.asarray(ref.hops))
    np.testing.assert_array_equal(np.asarray(fused.n_valid),
                                  np.asarray(ref.n_valid))
    r_f = _recalls(ds, e, sels, fused)
    r_r = _recalls(ds, e, sels, ref)
    assert abs(r_f.mean() - r_r.mean()) <= 0.01, (r_f.mean(), r_r.mean())


@pytest.mark.parametrize("mode", ["post", "spec_in", "strict_in"])
@pytest.mark.parametrize("selectivity", SELECTIVITIES)
def test_pipelined_matches_single_shot(shared_ds, shared_engine, mode,
                                       selectivity):
    """Compaction parity: the bucketed driver is pure batch re-indexing —
    every SearchResult field must match the single-shot jit bit-for-bit.
    Small chunk + min_bucket force several compaction generations."""
    ds, e = shared_ds, shared_engine
    impl = functools.partial(search_mod.filtered_search_pipelined,
                             hop_chunk=8, min_bucket=2)
    _, pipe = _run_mode(e, ds, mode, selectivity, impl)
    _, single = _run_mode(e, ds, mode, selectivity,
                          search_mod.filtered_search)
    for field in search_mod.SearchResult._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(pipe, field)),
            np.asarray(getattr(single, field)),
            err_msg=f"{mode}@{selectivity}: {field}")


def test_bucket_jit_cache_compiles_once_per_bucket(shared_ds,
                                                   shared_engine):
    """The chunked runner is keyed only by (bucket shapes, params):
    repeating the exact same search must not add cache entries, and the
    first run may compile at most one artifact per power-of-two bucket
    (+ the full width)."""
    ds, e = shared_ds, shared_engine
    B = ds.queries.shape[0]
    impl = functools.partial(search_mod.filtered_search_pipelined,
                             hop_chunk=8, min_bucket=2)
    c_before = search_mod.run_hops._cache_size()
    _, res1 = _run_mode(e, ds, "spec_in", 0.30, impl)
    c_first = search_mod.run_hops._cache_size()
    n_buckets = B.bit_length() + 2   # pow-2 widths in [2, B] + full width
    assert c_first - c_before <= n_buckets, \
        f"{c_first - c_before} compiles for ≤{n_buckets} possible buckets"
    _, res2 = _run_mode(e, ds, "spec_in", 0.30, impl)
    assert search_mod.run_hops._cache_size() == c_first, \
        "repeating an identical search re-compiled a bucket"
    np.testing.assert_array_equal(np.asarray(res1.ids),
                                  np.asarray(res2.ids))


def test_fused_results_are_valid(shared_ds, shared_engine):
    """Visited-set false positives may skip exploration but can never
    leak an invalid or duplicate result."""
    from repro.core.selectors import is_member
    ds, e = shared_ds, shared_engine
    sels, res = _run_mode(e, ds, "spec_in", 0.30,
                          search_mod.filtered_search)
    ids = np.asarray(res.ids)
    for i, s in enumerate(sels):
        got = ids[i][ids[i] >= 0]
        assert got.size == np.unique(got).size, f"query {i} duplicated ids"
        if got.size == 0:
            continue
        plan = s.plan(e.config.ql, e.config.cap)
        ok = np.asarray(is_member(plan.qfilter,
                                  e.store.rec_labels[jnp.asarray(got)],
                                  e.store.rec_values[jnp.asarray(got)]))
        assert np.all(ok), f"query {i} returned invalid ids"


@pytest.mark.parametrize("c", [2, 8, 24, 64, 128, 384])
def test_first_occurrence_matches_scan(c):
    """The packed-sort + binary-search dedup against a python scan —
    power-of-two widths included (the unrolled search once ran one
    iteration short exactly there)."""
    rng = np.random.default_rng(c)
    for n_ids in (50, 1000, 2 ** 21):
        cand = rng.integers(-1, min(n_ids, 40), (5, c)).astype(np.int32)
        live = cand >= 0
        got = np.asarray(search_mod._first_occurrence(
            jnp.asarray(cand), jnp.asarray(live), n_ids))
        for b in range(cand.shape[0]):
            seen = set()
            for i in range(c):
                if live[b, i]:
                    assert got[b, i] == (cand[b, i] not in seen), (c, b, i)
                    seen.add(cand[b, i])


def test_custom_distance_fn_keeps_parity(shared_ds, shared_engine):
    """A non-default distance_fn must route every slab through the
    caller's function (not the fused ADC kernel) so fused == ref holds
    for it too."""
    import jax.numpy as jnp
    ds, e = shared_ds, shared_engine

    def scaled_adc(codes, table):          # distinct fn identity + values
        from repro.core import pq as pq_mod
        return pq_mod.adc_lookup(codes, table) * jnp.float32(2.0)

    sels = _range_selectors(e, 0.3, ds.queries.shape[0])
    qf = stack_filters([s.plan(e.config.ql, e.config.cap).qfilter
                        for s in sels])
    queries = jnp.asarray(ds.queries)
    params = search_mod.SearchParams(l_search=32, k=10, max_hops=120,
                                     mode="spec_in")
    fused = search_mod.filtered_search(
        e.store, e.codes, e.codebook, e.mem, qf, queries, e.medoid, params,
        distance_fn=scaled_adc)
    ref = search_mod.filtered_search_ref(
        e.store, e.codes, e.codebook, e.mem, qf, queries, e.medoid, params,
        distance_fn=scaled_adc)
    np.testing.assert_array_equal(np.asarray(fused.ids), np.asarray(ref.ids))
    np.testing.assert_array_equal(np.asarray(fused.io_pages),
                                  np.asarray(ref.io_pages))
    np.testing.assert_array_equal(np.asarray(fused.explored),
                                  np.asarray(ref.explored))


# ---------------------------------------------------------------------------
# Compile artifacts: no res_cap-shaped work inside the hop loop
# ---------------------------------------------------------------------------

RES_CAP_HOPS = 77     # max_hops·W == 77: a dim no other array in the trace has


def _sub_jaxprs(v):
    if isinstance(v, jax.extend.core.ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, jax.extend.core.Jaxpr):
        yield v
    elif isinstance(v, (list, tuple)):
        for x in v:
            yield from _sub_jaxprs(x)


def _iter_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from _iter_eqns(sub)


def _find_whiles(jaxpr):
    return [e for e in _iter_eqns(jaxpr) if e.primitive.name == "while"]


def _eqn_avals(eqn):
    for var in list(eqn.invars) + list(eqn.outvars):
        aval = getattr(var, "aval", None)
        if aval is not None and getattr(aval, "shape", None) is not None:
            yield aval


def _res_cap_violations(jaxpr, res_cap: int, batch: int):
    """Ops whose operands pair the explored buffer with another axis —
    i.e. anything bigger than the (B, res_cap) buffer itself. Catches the
    legacy O(candidates · res_cap) dedup broadcast."""
    bad = []
    for eqn in _iter_eqns(jaxpr):
        for aval in _eqn_avals(eqn):
            if res_cap in aval.shape and np.prod(aval.shape) > batch * res_cap:
                bad.append((eqn.primitive.name, tuple(aval.shape)))
    return bad


def _cond_sorts_res_cap(jaxpr, res_cap: int):
    return [e for e in _iter_eqns(jaxpr)
            if e.primitive.name == "sort"
            and any(res_cap in a.shape for a in _eqn_avals(e))]


def _trace(impl, e, qf, queries, params):
    def fn(store, codes, centroids, mem, qf, q):
        cb = type(e.codebook)(centroids=centroids, dim=e.codebook.dim)
        return impl(store, codes, cb, mem, qf, q, e.medoid, params)
    return jax.make_jaxpr(fn)(e.store, e.codes, e.codebook.centroids,
                              e.mem, qf, queries)


def test_hop_body_has_no_res_cap_broadcasts(shared_ds, shared_engine):
    ds, e = shared_ds, shared_engine
    B = 3
    sels = _range_selectors(e, 0.3, B)
    qf = stack_filters([s.plan(e.config.ql, e.config.cap).qfilter
                        for s in sels])
    queries = jnp.asarray(ds.queries[:B])
    params = search_mod.SearchParams(l_search=16, k=5, beam_width=1,
                                     max_hops=RES_CAP_HOPS, mode="spec_in")
    res_cap = RES_CAP_HOPS * params.beam_width

    closed = _trace(search_mod.filtered_search, e, qf, queries, params)
    whiles = _find_whiles(closed.jaxpr)
    assert whiles, "fused search lost its while loop?"
    for w in whiles:
        body = w.params["body_jaxpr"].jaxpr
        cond = w.params["cond_jaxpr"].jaxpr
        bad = _res_cap_violations(body, res_cap, B)
        assert not bad, f"res_cap-shaped work in hop body: {bad}"
        assert not _cond_sorts_res_cap(cond, res_cap), \
            "hop condition re-sorts the explored buffer"

    # canary: the checker must flag the legacy pipeline's pathology
    closed_l = _trace(search_mod.filtered_search_legacy, e, qf, queries,
                      params)
    legacy_bad = []
    legacy_sorts = []
    for w in _find_whiles(closed_l.jaxpr):
        legacy_bad += _res_cap_violations(w.params["body_jaxpr"].jaxpr,
                                          res_cap, B)
        legacy_sorts += _cond_sorts_res_cap(w.params["cond_jaxpr"].jaxpr,
                                            res_cap)
    assert legacy_bad, "checker failed to flag the legacy dedup broadcast"
    assert legacy_sorts, "checker failed to flag the legacy cond re-sort"


def test_chunked_runner_has_no_res_cap_broadcasts(shared_ds,
                                                  shared_engine):
    """The chunked hop runner (run_hops) passes the same compile-artifact
    bar as the single-shot loop: no op pairs the res_cap axis with
    another axis, and the (now hop-budgeted) condition never sorts the
    explored buffer."""
    ds, e = shared_ds, shared_engine
    B = 3
    sels = _range_selectors(e, 0.3, B)
    qf = stack_filters([s.plan(e.config.ql, e.config.cap).qfilter
                        for s in sels])
    queries = jnp.asarray(ds.queries[:B])
    params = search_mod.SearchParams(l_search=16, k=5, beam_width=1,
                                     max_hops=RES_CAP_HOPS, mode="spec_in")
    res_cap = RES_CAP_HOPS * params.beam_width
    ctx, st = search_mod.init_search(e.store, e.codes, e.codebook, e.mem,
                                     qf, queries, e.medoid, params)

    def fn(store, codes, mem, ctx, st):
        return search_mod.run_hops(store, codes, mem, ctx, st, 16, params)

    closed = jax.make_jaxpr(fn)(e.store, e.codes, e.mem, ctx, st)
    whiles = _find_whiles(closed.jaxpr)
    assert whiles, "chunked runner lost its while loop?"
    for w in whiles:
        body = w.params["body_jaxpr"].jaxpr
        cond = w.params["cond_jaxpr"].jaxpr
        bad = _res_cap_violations(body, res_cap, B)
        assert not bad, f"res_cap-shaped work in chunked hop body: {bad}"
        assert not _cond_sorts_res_cap(cond, res_cap), \
            "chunked hop condition re-sorts the explored buffer"


# ---------------------------------------------------------------------------
# Session-driven repeat searches compile once
# ---------------------------------------------------------------------------

def test_session_repeat_search_compiles_once():
    from repro.api import (Index, Num, SearchRequest, Session,
                           SessionConfig, Tag)
    rng = np.random.default_rng(7)
    vecs = rng.normal(0, 1, (500, 16)).astype(np.float32)
    meta = [{"cat": int(rng.integers(0, 4)), "v": float(rng.uniform(0, 50))}
            for _ in range(500)]
    idx = Index.build(vecs, meta,
                      eng.IndexConfig(r=8, r_dense=48, l_build=16, pq_m=4),
                      defaults=eng.SearchConfig(k=5, l=32, max_hops=100))

    def reqs(seed):
        r = np.random.default_rng(seed)
        qs = r.normal(0, 1, (3, 16)).astype(np.float32)
        return [SearchRequest(query=qs[0]),
                SearchRequest(query=qs[1], filter=Tag("cat") == 2),
                SearchRequest(query=qs[2], filter=Num("v").between(5., 30.))]

    def caches():
        # the engine's production path: init → chunked runner → finalize
        return (search_mod.init_search._cache_size(),
                search_mod.run_hops._cache_size(),
                search_mod.finalize_search._cache_size())

    with Session(idx, SessionConfig(auto_flush=False)) as sess:
        sess.warmup(reqs(0))               # warm every (mode, pool) group
        c0 = caches()
        for seed in (1, 2):
            sess.submit_many(reqs(seed))
            sess.flush()
        assert caches() == c0, \
            "repeat Session flushes re-specialized the search jit"
