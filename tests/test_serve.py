"""Resilient serving tier tests: thread-safe Session handles, the
degrade ladder's cost/recall contracts, deadline-aware admission,
backpressure, shedding, and the threaded SearchServer end-to-end.

The load-degrade contract under test mirrors PR 7's fault ladder: every
admitted request — including ones served at a degraded rung or through
the approximate full-scan path — returns only exactly-verified results
(no false positives) and the approximate gating only over-admits (no
false negatives), so shedding/degradation trades latency and recall
headroom, never correctness.
"""
import dataclasses
import threading
import time

import numpy as np
import pytest

from repro.api import (DeadlineExceeded, Index, IndexConfig, Num, Overloaded,
                       SearchConfig, SearchRequest, Session, SessionConfig,
                       Tag)
from repro.api.session import PendingSearch
from repro.core import cost_model, search as search_mod
from repro.core.engine import apply_rung, scan_rerank
from repro.serve.server import SearchServer, ServerConfig

pytestmark = [pytest.mark.serve, pytest.mark.fast]

N = 900
N_CAT = 12


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(11)
    vectors = rng.normal(0, 1, (N, 24)).astype(np.float32)
    cats = [sorted(set(int(x) for x in
                       rng.integers(0, N_CAT, rng.integers(1, 4))))
            for _ in range(N)]
    values = rng.uniform(0, 100, N).astype(np.float32)
    metadata = [{"cat": c, "value": float(v)}
                for c, v in zip(cats, values)]
    return vectors, metadata, cats, values


@pytest.fixture(scope="module")
def index(corpus):
    vectors, metadata, *_ = corpus
    return Index.build(
        vectors, metadata,
        IndexConfig(r=12, r_dense=64, l_build=24, pq_m=8),
        defaults=SearchConfig(k=10, l=32, max_hops=128))


def make_requests(corpus, n=8, seed=3, **kw):
    vectors, _, cats, _ = corpus
    rng = np.random.default_rng(seed)
    idxs = rng.integers(0, N, n)
    return [SearchRequest(query=vectors[i], filter=Tag("cat") == cats[i][0],
                          **kw) for i in idxs]


def brute_valid(corpus, cat):
    _, _, cats, _ = corpus
    return {i for i, c in enumerate(cats) if cat in c}


# ---------------------------------------------------------------------------
# Degrade ladder: cost model
# ---------------------------------------------------------------------------

def test_effective_ladder_monotone(index):
    req = SearchRequest(query=np.zeros(24, np.float32),
                        filter=Tag("cat") == 3)
    sel = index.compile_filter(req.filter)
    eng = index.engine
    plan = sel.plan(eng.config.ql, eng.config.cap, eng.config.qr)
    ci = eng.cost_inputs(plan, index.defaults)
    eff = [c for _, c in cost_model.ladder_costs(ci)]
    assert all(a >= b - 1e-9 for a, b in zip(eff, eff[1:]))
    # the non-approx prefix is monotone even in raw cost: L shrinks and
    # read-ahead only tightens rung over rung
    raw = [c for _, c in cost_model.ladder_costs(ci, effective=False)]
    k = sum(not r.approx for r in cost_model.DEGRADE_LADDER)
    assert all(a >= b - 1e-9 for a, b in zip(raw[:k], raw[1:k]))
    # effective = running min of raw, and never above raw
    assert all(e <= r + 1e-9 for e, r in zip(eff, raw))


def test_estimate_cost_matches_routed_total(index):
    req = SearchRequest(query=np.zeros(24, np.float32),
                        filter=Tag("cat") == 3)
    sel = index.compile_filter(req.filter)
    full = index.engine.estimate_cost(sel, index.defaults)
    r0 = index.engine.estimate_cost(sel, index.defaults,
                                    rung=cost_model.DEGRADE_LADDER[0])
    assert full > 0
    # rung 0 adds only the read-ahead overage term on top of the route
    assert r0 >= full


def test_apply_rung_floors():
    scfg = SearchConfig(k=10, l=32, max_hops=128)
    for rung in cost_model.DEGRADE_LADDER:
        rc = apply_rung(scfg, rung)
        assert rc.l >= scfg.k
        assert rc.max_hops >= 8
    lean = apply_rung(scfg, cost_model.DEGRADE_LADDER[1])
    assert (lean.l, lean.max_hops) == (scfg.l, scfg.max_hops)
    assert lean.prefetch_depth == 1 and lean.hop_chunk == 16


# ---------------------------------------------------------------------------
# Approximate full-scan rung: no false negatives, no false positives
# ---------------------------------------------------------------------------

def test_approx_scan_no_false_positives(index, corpus):
    reqs = make_requests(corpus, n=6, k=10)
    for req, res in zip(reqs, index.approx_scan_batch(reqs)):
        valid = brute_valid(corpus, req.filter.value)
        for i, _, m in res.matches:
            assert i in valid
            assert m is not None


def test_approx_scan_no_false_negatives_exhaustive(index, corpus):
    """A filter with ≤ rerank valid records: the gated scan must return
    the *exact* valid top-k — the approximate gate only over-admits, the
    verifier restores exactness, so nothing valid can be lost."""
    vectors, _, _, values = corpus
    vs = np.sort(values)
    lo, hi = float(vs[0]), float(vs[14])     # 15 valid records « rerank
    valid = [i for i, v in enumerate(values) if lo <= v <= hi]
    assert len(valid) <= scan_rerank(index.defaults)
    q = vectors[5]
    exact = sorted(valid, key=lambda i: float(
        np.sum((vectors[i] - q) ** 2)))[:index.defaults.k]
    req = SearchRequest(query=q, filter=Num("value").between(lo, hi))
    res = index.approx_scan_batch([req])[0]
    got = [i for i, _, _ in res.matches]
    assert got == exact


def test_scan_rung_server_serves_verified_results(index, corpus):
    """A server pinned to the scan rung (singleton ladder) still returns
    only exactly-verified matches."""
    reqs = make_requests(corpus, n=5, seed=9, k=10)
    ladder = (cost_model.DEGRADE_LADDER[-1],)
    with SearchServer(index, ServerConfig(max_batch=8, max_delay_s=0.001),
                      ladder=ladder) as srv:
        handles = [srv.submit(r) for r in reqs]
        for req, h in zip(reqs, handles):
            res = h.result(timeout=60)
            assert h.rung == "scan"
            valid = brute_valid(corpus, req.filter.value)
            for i, _, _ in res.matches:
                assert i in valid
        assert srv.stats().degraded_served >= len(reqs)


# ---------------------------------------------------------------------------
# deadline_us is inert on the search path
# ---------------------------------------------------------------------------

def test_deadline_none_bit_identical(index, corpus):
    reqs = make_requests(corpus, n=8, seed=5, k=10)
    base = index.search_batch(reqs)
    tagged = [dataclasses.replace(r, deadline_us=None) for r in reqs]
    again = index.search_batch(tagged)
    for a, b in zip(base, again):
        assert np.array_equal(a.ids, b.ids)
        assert np.array_equal(a.dists, b.dists)
    # deadline_us never leaks into the resolved SearchConfig
    with_dl = dataclasses.replace(reqs[0], deadline_us=5e6)
    assert "deadline_us" not in with_dl.overrides()
    assert index._resolve_scfg(with_dl) == index._resolve_scfg(reqs[0])


def test_server_unloaded_bit_identical_to_direct(index, corpus):
    """At zero pressure the server runs the full rung — results must be
    bitwise what a direct batched search returns."""
    reqs = make_requests(corpus, n=8, seed=7, k=10)
    direct = index.search_batch(reqs)
    with SearchServer(index, ServerConfig(max_batch=8,
                                          max_delay_s=0.05)) as srv:
        handles = [srv.submit(r) for r in reqs]
        served = [h.result(timeout=60) for h in handles]
    for h in handles:
        assert h.rung == "full"
    for a, b in zip(direct, served):
        assert np.array_equal(a.ids, b.ids)
        assert np.array_equal(a.dists, b.dists)


def test_served_handles_carry_their_queue_wait(index, corpus):
    """Every served handle is stamped, at the batch cut, with its wait
    from admission: the batching window holds the first request longest."""
    reqs = make_requests(corpus, n=8, seed=9)
    with SearchServer(index, ServerConfig(max_batch=8,
                                          max_delay_s=30.0)) as srv:
        handles = []
        for r in reqs:
            handles.append(srv.submit(r))
            time.sleep(0.002)
        for h in handles:
            h.result(timeout=60)
    waits = [h.queue_us for h in handles]
    assert all(w is not None and w >= 0 for w in waits)
    assert waits[0] == max(waits) and waits[0] >= 8 * 2000 * 0.5


# ---------------------------------------------------------------------------
# Admission: backpressure + shedding
# ---------------------------------------------------------------------------

def test_overloaded_carries_retry_after(index, corpus):
    reqs = make_requests(corpus, n=4, seed=13)
    # a long batching window holds the worker while the tiny queue fills
    with SearchServer(index, ServerConfig(max_queue=2, max_batch=64,
                                          max_delay_s=5.0)) as srv:
        srv.submit(reqs[0])
        srv.submit(reqs[1])
        with pytest.raises(Overloaded) as ei:
            srv.submit(reqs[2])
        assert ei.value.retry_after_s > 0
        assert srv.stats().rejected_overload == 1
    # stop() drained the queue: both admitted requests resolved


def test_infeasible_deadline_shed_at_admission(index, corpus):
    req = make_requests(corpus, n=1, seed=17)[0]
    with SearchServer(index, ServerConfig()) as srv:
        with pytest.raises(DeadlineExceeded):
            srv.submit(dataclasses.replace(req, deadline_us=1e-3))
        st = srv.stats()
        assert st.shed_deadline == 1 and st.admitted == 0


def test_deadline_expires_in_queue_sheds_handle(index, corpus):
    req = make_requests(corpus, n=1, seed=19)[0]
    cfg = ServerConfig(max_batch=64, max_delay_s=0.25,
                       seed_us_per_cost=1e-3)
    with SearchServer(index, cfg) as srv:
        h = srv.submit(dataclasses.replace(req, deadline_us=2e3))
        with pytest.raises(DeadlineExceeded):
            h.result(timeout=60)
        assert srv.stats().shed_deadline == 1


def test_stats_probe_shape(index, corpus):
    with SearchServer(index, ServerConfig()) as srv:
        st = srv.stats()
        assert st.healthy and st.ready and not st.warmed
        assert st.queue_depth == 0 and st.in_flight == 0
        h = srv.submit(make_requests(corpus, n=1)[0])
        h.result(timeout=60)
        st = srv.stats()
        assert st.completed == 1 and st.p50_us > 0 and st.p99_us > 0
    assert not srv.stats().ready     # stopped servers fail readiness


def test_calibrate_service_model(index, corpus):
    with SearchServer(index, ServerConfig()) as srv:
        overhead, slope = srv.calibrate_service_model(
            make_requests(corpus, n=8))
        assert slope > 0 and overhead >= 0
        st = srv.stats()
        assert st.us_per_cost == pytest.approx(slope)
        assert st.overhead_us == pytest.approx(overhead)
        # a seeded model prices any nonzero work at a positive wall
        assert srv._predict_us(1.0) > 0


def test_tail_guard_tracks_slow_flushes(index):
    with SearchServer(index, ServerConfig()) as srv:
        with srv._lock:
            # fit a clean 100µs/unit line, then feed flushes that land
            # 2x over it: the tail guard must pick up the overrun
            for c in (10.0, 20.0, 30.0, 40.0):
                srv._refit_locked(c, c * 100.0)
            for c in (12.0, 22.0, 32.0, 42.0):
                srv._refit_locked(c, c * 200.0)
            guard = srv._tail_guard_us
            assert guard > 0.0
            # deadline-facing predictions carry exactly that margin
            assert srv._predict_tail_us(5.0) == pytest.approx(
                srv._predict_us(5.0) + guard)
        assert srv.stats().tail_guard_us == pytest.approx(guard)


# ---------------------------------------------------------------------------
# Thread-safe Session handles
# ---------------------------------------------------------------------------

def test_result_timeout_on_inflight_handle(index, corpus):
    sess = Session(index, SessionConfig(auto_flush=False))
    h = PendingSearch(sess, make_requests(corpus, n=1)[0])
    h._claimed = True       # simulate another thread's flush owning it
    t0 = time.monotonic()
    with pytest.raises(TimeoutError):
        h.result(timeout=0.05)
    assert time.monotonic() - t0 < 5


def test_result_waits_across_threads(index, corpus):
    sess = Session(index, SessionConfig(auto_flush=False))
    handles = sess.submit_many(make_requests(corpus, n=4, seed=23))
    got = {}

    def waiter():
        got["res"] = handles[-1].result(timeout=60)

    t = threading.Thread(target=waiter)
    # claim the batch before the waiter runs so its flush() sees an
    # empty queue and falls through to the event wait
    with sess._lock:
        batch, sess._pending = sess._pending, []
        for hh, _ in batch:
            hh._claimed = True
    t.start()
    time.sleep(0.05)
    sess._execute_isolated([hh for hh, _ in batch],
                           [sess.config.flush_retry_budget])
    t.join(60)
    assert not t.is_alive() and len(got["res"]) > 0


def test_concurrent_submit_result_threads(index, corpus):
    sess = Session(index, SessionConfig(max_batch=4, max_delay_s=0.0))
    reqs = make_requests(corpus, n=16, seed=29, k=10)
    direct = index.search_batch(reqs)
    errors = []
    results = [None] * len(reqs)

    def worker(i):
        try:
            results[i] = sess.submit(reqs[i]).result(timeout=120)
        except Exception as e:      # noqa: BLE001 - collected for assert
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(len(reqs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not errors
    for a, b in zip(direct, results):
        assert np.array_equal(a.ids, b.ids)
        assert np.array_equal(a.dists, b.dists)


def test_poisoned_batch_isolated_under_contention(index, corpus):
    sess = Session(index, SessionConfig(max_batch=6, max_delay_s=0.0))
    good = make_requests(corpus, n=10, seed=31)
    bad = SearchRequest(query=np.zeros(24, np.float32),
                        filter=Tag("no_such_field") == 1)
    outcomes = [None] * 11

    def worker(i, req):
        try:
            outcomes[i] = ("ok", sess.submit(req).result(timeout=120))
        except Exception as e:      # noqa: BLE001
            outcomes[i] = ("err", e)

    threads = [threading.Thread(target=worker, args=(i, r))
               for i, r in enumerate(good + [bad])]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    kinds = [o[0] for o in outcomes]
    assert kinds[:10] == ["ok"] * 10       # every good request resolved
    assert kinds[10] == "err"


# ---------------------------------------------------------------------------
# Warmup: rung variants pre-compiled
# ---------------------------------------------------------------------------

def test_warmup_covers_degrade_rungs(index, corpus):
    reqs = make_requests(corpus, n=4, seed=37)
    sess = Session(index, SessionConfig(auto_flush=False))
    sess.warmup(reqs)
    sizes = (search_mod.init_search._cache_size(),
             search_mod.run_hops._cache_size(),
             search_mod.finalize_search._cache_size())
    # re-serving the same mix at every rung must hit only warm caches
    scfgs = [index._resolve_scfg(r) for r in reqs]
    for rung in cost_model.DEGRADE_LADDER:
        rcfgs = [apply_rung(sc, rung) for sc in scfgs]
        if rung.approx:
            index.approx_scan_batch(reqs, scfgs=rcfgs, with_metadata=False)
        else:
            index.search_batch(reqs, scfgs=rcfgs, with_metadata=False)
    after = (search_mod.init_search._cache_size(),
             search_mod.run_hops._cache_size(),
             search_mod.finalize_search._cache_size())
    assert after == sizes


# ---------------------------------------------------------------------------
# Async active-count readback
# ---------------------------------------------------------------------------

def test_async_readback_bit_identical(index, corpus, monkeypatch):
    reqs = make_requests(corpus, n=6, seed=41, k=10)
    base = index.search_batch(reqs)
    orig = search_mod.filtered_search_pipelined

    def sync_driver(*args, **kw):
        kw["async_readback"] = False
        return orig(*args, **kw)

    monkeypatch.setattr(search_mod, "filtered_search_pipelined",
                        sync_driver)
    sync = index.search_batch(reqs)
    for a, b in zip(base, sync):
        assert np.array_equal(a.ids, b.ids)
        assert np.array_equal(a.dists, b.dists)
        assert a.stats.hops == b.stats.hops
        assert a.stats.dist_comps == b.stats.dist_comps
