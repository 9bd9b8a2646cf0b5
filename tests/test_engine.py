"""End-to-end behaviour of the filtered ANN engine — the paper's system."""
import numpy as np
import pytest

from repro.core import engine as eng
from repro.core.selectors import stack_filters
from repro.data.synth import make_selectors


pytestmark = pytest.mark.fast   # build shared via the session-scoped cache


@pytest.fixture(scope="module")
def built(shared_ds, shared_engine):
    return shared_ds, shared_engine


def _gt_for(ds, e, selectors, k=10):
    vectors = np.asarray(e.store.vectors)
    rl = np.asarray(e.store.rec_labels)
    rv = np.asarray(e.store.rec_values)
    gts = []
    for i, sel in enumerate(selectors):
        plan = sel.plan(e.config.ql, e.config.cap)
        q = ds.queries[i]
        if q.shape[0] != vectors.shape[1]:
            q = np.pad(q, (0, vectors.shape[1] - q.shape[0]))
        gts.append(eng.brute_force_filtered(vectors, rl, rv, plan.qfilter,
                                            q, k))
    return gts


@pytest.mark.parametrize("workload", ["label_or", "label_and", "range",
                                      "hybrid"])
def test_speculative_recall(built, workload):
    ds, e = built
    sels = make_selectors(ds, e, workload)
    scfg = eng.SearchConfig(k=10, l=48, max_hops=400, max_pool=512)
    ids, dists, stats = e.search(ds.queries, sels, scfg)
    gts = _gt_for(ds, e, sels)
    recalls = [eng.recall_at_k(ids[i], gts[i], 10) for i in range(len(sels))]
    assert np.mean(recalls) >= 0.85, \
        f"{workload}: recall {np.mean(recalls):.3f} routes {stats.mechanism}"


def test_results_are_valid(built):
    """Every returned id must satisfy the exact constraint (verification)."""
    ds, e = built
    sels = make_selectors(ds, e, "label_or")
    ids, dists, stats = e.search(ds.queries, sels,
                                 eng.SearchConfig(k=10, l=32))
    from repro.core.selectors import is_member
    import jax.numpy as jnp
    for i, sel in enumerate(sels):
        plan = sel.plan(e.config.ql, e.config.cap)
        got = ids[i][ids[i] >= 0]
        if got.size == 0:
            continue
        ok = np.asarray(is_member(plan.qfilter,
                                  e.store.rec_labels[jnp.asarray(got)],
                                  e.store.rec_values[jnp.asarray(got)]))
        assert np.all(ok), f"query {i} returned invalid ids"


def test_io_accounting_positive(built):
    ds, e = built
    sels = make_selectors(ds, e, "range")
    ids, dists, stats = e.search(ds.queries, sels,
                                 eng.SearchConfig(k=10, l=32))
    assert np.all(stats.io_pages > 0)
    assert np.all(stats.est_io_pages > 0)


def test_policies_agree_on_results_quality(built):
    """Baselines find valid results too; speculative reads fewer pages than
    strict in-filtering (the paper's core claim)."""
    ds, e = built
    sels = make_selectors(ds, e, "label_or")
    gts = _gt_for(ds, e, sels)

    spec_cfg = eng.SearchConfig(k=10, l=48, max_hops=400, policy="speculative")
    _, _, spec_stats = e.search(ds.queries, sels, spec_cfg)

    strict_cfg = eng.SearchConfig(k=10, l=48, max_hops=400, policy="strict_in")
    sids, _, strict_stats = e.search(ds.queries, sels, strict_cfg)

    spec_io = spec_stats.io_pages.sum()
    strict_io = strict_stats.io_pages.sum()
    assert spec_io < strict_io, (spec_io, strict_io)


def test_route_distribution_sane(built):
    ds, e = built
    sels = make_selectors(ds, e, "hybrid")
    _, _, stats = e.search(ds.queries, sels, eng.SearchConfig(k=10, l=32))
    assert set(stats.mechanism) <= {"pre", "in", "post"}


def test_engine_calibrate_roundtrip(built):
    """engine.calibrate installs measured per-hop constants for _route
    (cost_model.Calibration) and cleanly reverts/refuses."""
    ds, e = built
    assert e.calibration is None
    payload = {"modes": {
        "spec_in": {"mean_hops": 80.0, "mean_dist_comps": 560.0,
                    "mean_approx_checks": 24_000.0},
        "post": {"mean_hops": 50.0, "mean_dist_comps": 300.0,
                 "mean_approx_checks": 0.0}}}
    try:
        assert e.calibrate(payload)
        assert abs(e.calibration.spec_in.dist_per_hop - 7.0) < 1e-9
        # routing still works end-to-end with calibration installed
        sels = make_selectors(ds, e, "range")[:4]
        ids, _, stats = e.search(ds.queries[:4], sels,
                                 eng.SearchConfig(k=5, l=16, max_hops=60,
                                                  max_pool=128))
        assert ids.shape == (4, 5)
        assert not e.calibrate("/nonexistent/BENCH_search.json")
        # malformed payloads degrade to uncalibrated, like unreadable paths
        assert not e.calibrate({"modes": {"spec_in": {"mean_hops": 1.0}}})
        assert e.calibration is None
    finally:
        e.calibrate(None)          # shared engine: leave no state behind
    assert e.calibration is None


@pytest.mark.parametrize("policy,mode", [("spec_in", "spec_in"),
                                         ("strict_in", "strict_in"),
                                         ("post", "post")])
def test_plan_groups_replay_execute(built, policy, mode):
    """plan_groups hands out exactly what execute runs: each group
    replayed through the jnp oracle returns execute's ids, and a pinned
    policy routes every query to its one mechanism."""
    from repro.core import search
    ds, e = built
    sels = make_selectors(ds, e, "hybrid")
    scfgs = [eng.SearchConfig(k=10, l=32, max_hops=200, policy=policy)
             ] * len(sels)
    ids, _, stats = e.execute(ds.queries, sels, scfgs)
    groups = e.plan_groups(ds.queries, sels, scfgs)
    assert sorted(i for g in groups for i in g.idxs) == list(range(len(sels)))
    for g in groups:
        assert g.params.mode == mode
        res = search.filtered_search_ref(
            e.store, e.codes, e.codebook, e.mem, g.qfilter, g.queries,
            e.medoid, g.params, entries=g.entries)
        for j, i in enumerate(g.idxs):
            np.testing.assert_array_equal(np.asarray(res.ids[j]), ids[i])


# counters each group's result carries into QueryStats (the rest stay 0)
_COUNTERS = ("io_pages", "dist_comps", "hops", "fp_explored", "explored",
             "n_valid", "faults", "retries", "degraded")


def _mixed_batch(ds, e):
    """Selectors and per-query configs for 24 queries pinned to three
    groups: 8 in-filtered (a power-of-two width, so the pipelined search hands
    back device arrays), 5 post-filtered (padded to 8, handed back as
    numpy) and 11 pre-filtered (device ids and distances, host counters).
    A 32-slot pool cap keeps each policy in one pool bucket."""
    sels = make_selectors(ds, e, "hybrid")
    cfg = dict(k=10, l=32, max_hops=200, max_pool=32)
    scfgs = ([eng.SearchConfig(policy="spec_in", **cfg)] * 8
             + [eng.SearchConfig(policy="post", **cfg)] * 5
             + [eng.SearchConfig(policy="strict_pre", **cfg)]
             * (len(sels) - 13))
    return sels, scfgs


def _replay(e, g, sels):
    """One group of ``plan_groups`` run again through its own search path,
    read out a row and a field at a time: (ids, dists, counters) by query."""
    from repro.core import prefilter, search
    if g.mech == "pre":
        res = prefilter.prefilter_search(
            e.store, e.codes, e.codebook, [sels[i] for i in g.idxs],
            g.qfilter, g.queries,
            prefilter.PrefilterParams(
                l_rerank=g.eff_l + g.scfg.l_rerank_delta, k=g.scfg.k),
            speculative=not g.strict)
        pages = np.zeros(len(g.idxs), np.int64)
    else:
        res = search.filtered_search_pipelined(
            e.store, e.codes, e.codebook, e.mem, g.qfilter, g.queries,
            e.medoid, g.params, entries=g.entries, hop_chunk=g.scfg.hop_chunk)
        cfg = e.config
        pages = g.seed_pages + [
            sels[i].plan(cfg.ql, cfg.cap, cfg.qr).pages_prefetch
            if g.params.mode == "spec_in" else 0 for i in g.idxs]
    out = {}
    for j, i in enumerate(g.idxs):
        counters = {f: int(getattr(res, f)[j]) if f in res._fields else 0
                    for f in _COUNTERS}
        counters["io_pages"] += int(pages[j])
        out[i] = (np.asarray(res.ids[j]), np.asarray(res.dists[j]), counters)
    return out


def test_execute_collects_what_group_replays_give(built):
    """execute's one-copy collection hands each query the ids, distances
    and QueryStats a per-row replay of its group reads, bit for bit, for
    pre-filtered groups and for graph groups whose result comes back on
    the device (width 8) and on the host (width 5)."""
    ds, e = built
    sels, scfgs = _mixed_batch(ds, e)
    ids, dists, stats = e.execute(ds.queries, sels, scfgs)
    groups = e.plan_groups(ds.queries, sels, scfgs)
    assert sorted((g.mech, len(g.idxs)) for g in groups) == [
        ("in", 8), ("post", 5), ("pre", len(sels) - 13)]
    cfg = e.config
    for g in groups:
        for i, (rid, rd, counters) in _replay(e, g, sels).items():
            assert ids[i].shape == rid.shape and ids[i].dtype == rid.dtype
            np.testing.assert_array_equal(ids[i], rid)
            np.testing.assert_array_equal(dists[i], rd)
            assert {f: int(getattr(stats, f)[i]) for f in _COUNTERS} == \
                counters, (g.mech, i)
            route = e._route(sels[i].plan(cfg.ql, cfg.cap, cfg.qr),
                             scfgs[i])
            assert stats.mechanism[i] == g.mech == route.mechanism
            cost = route.costs[g.mech]
            assert stats.est_io_pages[i] == cost.io_pages
            assert stats.est_compute[i] == cost.compute


def test_collect_copies_each_group_result_once(built, monkeypatch):
    """Collecting a group indexes no device array: its result comes to the
    host in one ``jax.device_get`` when the search left any of it on the
    device, and in none when it is all on the host already."""
    import jax
    from jax._src.array import ArrayImpl

    ds, e = built
    sels, scfgs = _mixed_batch(ds, e)
    seen = []          # per engine.collect: [width, device_gets, slices]
    real_span, real_get = eng.span, jax.device_get
    real_item = ArrayImpl.__getitem__

    class Collect:
        def __init__(self, width):
            self.rec = [width, 0, 0]

        def __enter__(self):
            seen.append(self.rec)

        def __exit__(self, *exc):
            seen.append(None)

    def span(name, **stats):
        if name == "engine.collect":
            return Collect(stats["width"])
        return real_span(name, **stats)

    def inside():
        return seen and seen[-1] is not None

    def device_get(x):
        if inside():
            seen[-1][1] += 1
        return real_get(x)

    def getitem(self, key):
        if inside():
            seen[-1][2] += 1
        return real_item(self, key)

    monkeypatch.setattr(eng, "span", span)
    monkeypatch.setattr(jax, "device_get", device_get)
    monkeypatch.setattr(ArrayImpl, "__getitem__", getitem)
    e.execute(ds.queries, sels, scfgs)
    collects = sorted(tuple(r) for r in seen if r is not None)
    # the width-5 graph group came back padded and sliced on the host:
    # nothing left to copy
    assert collects == [(5, 0, 0), (8, 1, 0), (len(sels) - 13, 1, 0)]
