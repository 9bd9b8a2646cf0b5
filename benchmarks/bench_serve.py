"""SLO-verified load harness for the resilient serving tier (serve/server.py).

Two parts, both seeded and committed to ``BENCH_serve.json``:

**Part A — rung calibration (deterministic).** Executes the benchmark
query set at every rung of ``cost_model.DEGRADE_LADDER`` through the
batch path the server uses (``search_batch`` with rung-applied configs;
``approx_scan_batch`` for the scan rung) and records recall@10 against
exact filtered ground truth per rung. Asserted floors:

  * the *effective* ladder cost (``ladder_costs``, running minimum over
    the permitted prefix — exactly what the pressure scheduler serves
    at) is monotone non-increasing for every query;
  * the ``lean`` rung is results-invariant (bit-identical ids/dists to
    full service — it only drops read-ahead and tightens compaction);
  * per-rung recall floors (``RUNG_RECALL_FLOORS``): degradation trades
    recall *headroom*, it never collapses.

**Part B — Poisson open-loop sweep.** Measures closed-loop capacity,
then drives the threaded ``SearchServer`` with seeded open-loop Poisson
arrivals at ``LOAD_FACTORS`` × capacity. Every request carries a
``deadline_us`` equal to the derived SLO so admission, shedding, and the
degrade ladder all engage. Asserted floors (non-smoke):

  * at 0.8× capacity the server sustains ≥ ``SUSTAINED_FRACTION_FLOOR``
    of the offered rate;
  * at 2× overload the server survives: worker healthy, queue bounded at
    ``max_queue``, every request accounted (completed + shed + rejected),
    and the server-side p99 completion latency of *admitted* requests
    stays within ``P99_SLO_TOL`` × SLO;
  * deadline-miss + shed rates are reported at every load point.

``--smoke`` runs both parts on the tiny corpus with no floors and no
JSON — the bitrot check ``scripts/test_fast.sh`` wires in.
"""
from __future__ import annotations

import json
import time

import numpy as np

from benchmarks.common import get_engine
from repro.api import DeadlineExceeded, Overloaded, SearchRequest
from repro.core import cost_model
from repro.core.engine import apply_rung, recall_at_k
from repro.data.synth import make_sliding_range_selectors
from repro.serve.server import SearchServer, ServerConfig

N, N_SMOKE = 12_000, 600
K, L = 10, 64               # L matches bench_search at this corpus scale
SELECTIVITY = 0.30          # the paper's mid-selectivity operating point
OUT_PATH = "BENCH_serve.json"

LOAD_FACTORS = (0.5, 0.8, 1.0, 1.5, 2.0)
OPEN_LOOP_SECONDS = 20.0    # arrival-window length per load point; the
                            # request count scales with the offered rate
                            # so the post-window drain tail (a couple of
                            # flush walls) amortizes out of sustained QPS
N_REQ_MAX = 800
SEED = 17                   # Poisson arrival stream seed
SLO_BATCHES = 4.0           # SLO = factor × warm full-batch flush wall —
                            # a queued request may wait a few batch
                            # turnarounds before its own service; per-query
                            # µs would undercut a single flush wall
SUSTAINED_FRACTION_FLOOR = 0.9   # sustained/offered at the 0.8× point
P99_SLO_TOL = 1.10          # admitted p99 ≤ tol × SLO at 2× overload
RUNG_RECALL_FLOORS = {"full": 0.90, "lean": 0.90, "reduced": 0.80,
                      "minimal": 0.60, "scan": 0.60}


def _requests(ds, index, n_req, deadline_us=None):
    sels = make_sliding_range_selectors(index, SELECTIVITY,
                                        len(ds.queries))
    return [SearchRequest(query=ds.queries[i % len(ds.queries)],
                          filter=sels[i % len(sels)], k=K, l=L,
                          deadline_us=deadline_us)
            for i in range(n_req)]


# ---------------------------------------------------------------------------
# Part A: rung calibration
# ---------------------------------------------------------------------------

def rung_calibration(ds, index, smoke: bool) -> dict:
    reqs = _requests(ds, index, len(ds.queries))
    gts = [index.ground_truth(r) for r in reqs]
    scfgs = [index._resolve_scfg(r) for r in reqs]

    # effective ladder cost monotone for every query in the mix
    eng = index.engine
    for r in reqs:
        sel = index.compile_filter(r.filter)
        plan = sel.plan(eng.config.ql, eng.config.cap, eng.config.qr)
        ci = eng.cost_inputs(plan, scfgs[0])
        eff = [c for _, c in cost_model.ladder_costs(
            ci, calib=eng.calibration)]
        assert all(a >= b - 1e-9 for a, b in zip(eff, eff[1:])), \
            f"effective ladder not monotone: {eff}"

    out = {}
    base = None
    for rung in cost_model.DEGRADE_LADDER:
        rcfgs = [apply_rung(sc, rung) for sc in scfgs]
        if rung.approx:
            results = index.approx_scan_batch(reqs, scfgs=rcfgs,
                                              with_metadata=False)
        else:
            results = index.search_batch(reqs, scfgs=rcfgs,
                                         with_metadata=False)
        recall = float(np.mean([recall_at_k(res.ids, gt, K)
                                for res, gt in zip(results, gts)]))
        out[rung.name] = {"recall": round(recall, 4)}
        if rung.name == "full":
            base = results
        if rung.name == "lean":
            for a, b in zip(base, results):
                assert np.array_equal(a.ids, b.ids), \
                    "lean rung must be results-invariant"
                assert np.array_equal(a.dists, b.dists)
        if not smoke:
            floor = RUNG_RECALL_FLOORS[rung.name]
            assert recall >= floor, \
                f"rung {rung.name}: recall {recall:.3f} < floor {floor}"
        print(f"  rung {rung.name:8s} recall@{K} = {recall:.3f}")
    return out


# ---------------------------------------------------------------------------
# Part B: Poisson open-loop sweep
# ---------------------------------------------------------------------------

def _measure_capacity(ds, index, smoke: bool):
    """Warm closed-loop throughput of the batched path. Returns
    (capacity_qps, full-batch flush wall in µs)."""
    reqs = _requests(ds, index, len(ds.queries))
    index.search_batch(reqs, with_metadata=False)       # warm
    reps = 2 if smoke else 3
    t0 = time.monotonic()
    for _ in range(reps):
        index.search_batch(reqs, with_metadata=False)
    wall = time.monotonic() - t0
    return reps * len(reqs) / wall, wall / reps * 1e6


def load_point(ds, index, factor: float, capacity_qps: float,
               slo_us: float, n_req: int, seed: int) -> dict:
    offered = factor * capacity_qps
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / offered, n_req))
    reqs = _requests(ds, index, n_req, deadline_us=slo_us)
    cfg = ServerConfig(max_queue=64, max_batch=32, max_delay_s=0.005,
                       slo_p99_us=slo_us)
    rejected = shed = 0
    handles = []
    with SearchServer(index, cfg) as srv:
        # seed the affine service model from two measured flushes so the
        # opening wave is priced by measurement, not the config seed —
        # a cold model under-batches and over-sheds until it converges
        srv.calibrate_service_model(reqs[:cfg.max_batch])
        t0 = time.monotonic()
        for req, t_arr in zip(reqs, arrivals):
            dt = t_arr - (time.monotonic() - t0)
            if dt > 0:
                time.sleep(dt)
            try:
                handles.append(srv.submit(req))
            except Overloaded:
                rejected += 1
            except DeadlineExceeded:
                shed += 1
        completed = 0
        rungs: dict = {}
        for h in handles:
            try:
                h.result(timeout=120)
                completed += 1
                rungs[h.rung] = rungs.get(h.rung, 0) + 1
            except DeadlineExceeded:
                shed += 1
        wall = time.monotonic() - t0
        st = srv.stats()
    assert completed + shed + rejected == n_req, "request lost"
    return {
        "n_req": n_req,
        "offered_qps": round(offered, 2),
        "sustained_qps": round(completed / wall, 2),
        "admitted": st.admitted, "completed": completed,
        "rejected_overload": rejected, "shed_deadline": shed,
        "deadline_misses": st.deadline_misses,
        "degraded_served": st.degraded_served,
        "p50_us": round(st.p50_us, 1), "p99_us": round(st.p99_us, 1),
        "max_queue": cfg.max_queue, "queue_depth_final": st.queue_depth,
        "healthy": st.healthy, "rungs": rungs,
        "shed_rate": round(shed / n_req, 4),
        "reject_rate": round(rejected / n_req, 4),
    }


def run(out_path: str = OUT_PATH, smoke: bool = False) -> dict:
    n = N_SMOKE if smoke else N
    ds, index, build_s = get_engine(n)
    print(f"corpus n={n} (build {build_s:.1f}s)")

    print("Part A: degrade-rung calibration")
    rungs = rung_calibration(ds, index, smoke)

    print("Part B: Poisson open-loop sweep")
    # pre-compile the bucket-jit width ladder + rung variants so open-loop
    # latencies never include a compile stall (Session.warmup, PR 9)
    from repro.api import Session, SessionConfig
    Session(index, SessionConfig(auto_flush=False)).warmup(
        _requests(ds, index, len(ds.queries)))
    capacity, batch_wall_us = _measure_capacity(ds, index, smoke)
    slo_us = SLO_BATCHES * batch_wall_us
    print(f"  capacity {capacity:.1f} qps, SLO {slo_us/1e3:.1f} ms")
    factors = (0.8, 2.0) if smoke else LOAD_FACTORS
    sweep = {}
    for f in factors:
        n_req = 16 if smoke else min(
            N_REQ_MAX, max(48, int(OPEN_LOOP_SECONDS * f * capacity)))
        pt = load_point(ds, index, f, capacity, slo_us, n_req, SEED)
        sweep[str(f)] = pt
        print(f"  {f:>4}x: offered {pt['offered_qps']:>8.1f} "
              f"sustained {pt['sustained_qps']:>8.1f} "
              f"done {pt['completed']:>3} rej {pt['rejected_overload']:>3} "
              f"shed {pt['shed_deadline']:>3} miss {pt['deadline_misses']:>3} "
              f"p99 {pt['p99_us']/1e3:>8.1f}ms")

    if not smoke:
        pt = sweep["0.8"]
        frac = pt["sustained_qps"] / pt["offered_qps"]
        assert frac >= SUSTAINED_FRACTION_FLOOR, \
            f"0.8x: sustained {frac:.2f}x offered < " \
            f"{SUSTAINED_FRACTION_FLOOR}"
        ov = sweep["2.0"]
        assert ov["healthy"], "2x overload killed the worker"
        assert ov["queue_depth_final"] <= ov["max_queue"], "queue unbounded"
        assert ov["completed"] + ov["shed_deadline"] \
            + ov["rejected_overload"] == ov["n_req"], "request lost at 2x"
        if ov["completed"]:
            assert ov["p99_us"] <= P99_SLO_TOL * slo_us, \
                f"2x: admitted p99 {ov['p99_us']:.0f}µs breaches SLO " \
                f"{slo_us:.0f}µs"

    payload = {
        "n": n, "capacity_qps": round(capacity, 2),
        "slo_us": round(slo_us, 1), "seed": SEED,
        "rung_calibration": rungs, "sweep": sweep,
        "floors": {"sustained_fraction_at_0.8x": SUSTAINED_FRACTION_FLOOR,
                   "p99_slo_tol_at_2x": P99_SLO_TOL,
                   "rung_recall": RUNG_RECALL_FLOORS},
    }
    if not smoke:
        with open(out_path, "w") as fh:
            json.dump(payload, fh, indent=2, sort_keys=True)
        print(f"wrote {out_path}")
    return payload


def main():
    import argparse
    from repro.utils import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny corpus, no floors, no JSON")
    ap.add_argument("--out", default=OUT_PATH)
    args = ap.parse_args()
    run(out_path=args.out, smoke=args.smoke)


if __name__ == "__main__":
    main()
