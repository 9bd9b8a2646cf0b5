"""Build-throughput benchmark: batched device pipeline vs numpy reference.

Builds the benchmark corpus (the same 12 K-point clustered dataset the
workload suites use) with both Vamana builders at equal parameters and
writes ``BENCH_build.json`` — build seconds, nodes/sec, recall@10 — so the
build-perf trajectory is tracked across PRs. The batched builder is timed
twice: cold (including JIT compilation, what a one-off build pays) and warm
(steady-state, what any repeated/larger build amortizes to). The
acceptance bar is ≥5× over the reference with recall@10 within 1%.

``--smoke`` (also ``run(smoke=True)``) builds a tiny corpus end-to-end
with no perf bars and no JSON output — a bitrot check cheap enough for
the tier-1-adjacent ``scripts/test_fast.sh`` lane.

``--shards`` adds a ``sharded`` block: each shard count in {1, 2, 4}
runs ``distributed.build_vamana_sharded`` (PQ-approximate navigation, the
exact RobustPrune re-rank) in its own subprocess under a 4-fake-device
mesh, reporting the honest wall clock (serialized fake devices — slower),
the per-stage split (sharded navigate+prune vs replicated scatter/drain),
recall@10 vs the batched baseline (±1% gate), and an Amdahl model of a
real mesh: ``T(S) = t_scatter + t_nav_prune / S`` from the 1-shard stage
timers. The PQ-navigation compute cut (sharded-1 wall vs the batched
builder) is reported separately so the two effects don't get conflated.
Methodology: docs/distributed.md.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

from benchmarks.common import BenchResult
from repro.core import graph
from repro.data.synth import make_filtered_dataset

N, D = 12_000, 48
N_SMOKE = 600
R, ELL, ALPHA = 24, 48, 1.2
N_QUERIES = 32
OUT_PATH = "BENCH_build.json"


def run(out_path: str = OUT_PATH, smoke: bool = False) -> list:
    n = N_SMOKE if smoke else N
    ds = make_filtered_dataset(n=n, d=D, n_queries=N_QUERIES, seed=0)
    data, queries = ds.vectors, ds.queries

    if smoke:
        adj_b, med_b = graph.build_vamana_batched(data, R, ELL, ALPHA,
                                                  seed=0)
        adj_r, med_r = graph.build_vamana(data, R, ELL, ALPHA, seed=0)
        rec_b = graph.greedy_recall_at_k(data, adj_b, med_b, queries, ell=64)
        rec_r = graph.greedy_recall_at_k(data, adj_r, med_r, queries, ell=64)
        # end-to-end sanity only — no timing bars on a shared CI box
        assert adj_b.shape == adj_r.shape == (n, R)
        assert rec_b >= 0.5 and rec_r >= 0.5, (rec_b, rec_r)
        return [BenchResult(name="build/smoke", us_per_call=0.0,
                            derived={"n": n, "recall_batched": f"{rec_b:.3f}",
                                     "recall_reference": f"{rec_r:.3f}"})]

    t0 = time.time()
    adj_b, med_b = graph.build_vamana_batched(data, R, ELL, ALPHA, seed=0)
    cold_s = time.time() - t0
    # best-of-3 warm: the CI box is a small shared container with very
    # noisy CPU timings; min over repeats is the steady-state number
    warm_times = []
    for _ in range(3):
        t0 = time.time()
        adj_b, med_b = graph.build_vamana_batched(data, R, ELL, ALPHA,
                                                  seed=0)
        warm_times.append(time.time() - t0)
    warm_s = min(warm_times)

    t0 = time.time()
    adj_r, med_r = graph.build_vamana(data, R, ELL, ALPHA, seed=0)
    ref_s = time.time() - t0

    rec_b = graph.greedy_recall_at_k(data, adj_b, med_b, queries, ell=64)
    rec_r = graph.greedy_recall_at_k(data, adj_r, med_r, queries, ell=64)

    payload = {
        "corpus": {"n": N, "d": D, "r": R, "l_build": ELL, "alpha": ALPHA},
        "batched": {"seconds": warm_s, "seconds_cold": cold_s,
                    "nodes_per_sec": N / warm_s, "recall_at_10": rec_b,
                    "stats": graph.graph_stats(adj_b)},
        "reference": {"seconds": ref_s, "nodes_per_sec": N / ref_s,
                      "recall_at_10": rec_r,
                      "stats": graph.graph_stats(adj_r)},
        "speedup_warm": ref_s / warm_s,
        "speedup_cold": ref_s / cold_s,
        "recall_gap": rec_r - rec_b,
    }
    with open(out_path, "w") as fh:
        json.dump(payload, fh, indent=2)

    assert payload["speedup_warm"] >= 5.0, \
        f"batched builder only {payload['speedup_warm']:.1f}x vs reference"
    # one-sided: the batched graph may be better, just not >1% worse
    assert payload["recall_gap"] <= 0.01, \
        f"batched recall trails reference by {payload['recall_gap']:.3f}"

    return [
        BenchResult(name="build/batched", us_per_call=warm_s * 1e6,
                    derived={"nodes_per_sec": f"{N / warm_s:.0f}",
                             "cold_s": f"{cold_s:.1f}",
                             "recall@10": f"{rec_b:.3f}"}),
        BenchResult(name="build/reference", us_per_call=ref_s * 1e6,
                    derived={"nodes_per_sec": f"{N / ref_s:.0f}",
                             "recall@10": f"{rec_r:.3f}"}),
        BenchResult(name="build/speedup", us_per_call=0.0,
                    derived={"warm": f"{payload['speedup_warm']:.1f}x",
                             "cold": f"{payload['speedup_cold']:.1f}x"}),
    ]


# ---------------------------------------------------------------------------
# Sharded build (--shards): subprocess per shard count, 4 fake devices
# ---------------------------------------------------------------------------
SHARD_COUNTS = (1, 2, 4)
SHARD_DEVICES = 4
SCALING_MODEL = "amdahl_stage_decomposition"
RECALL_GAP_MAX = 0.01


def _shard_worker(shards: int, smoke: bool, out_path: str) -> None:
    """One shard count in a subprocess: PQ-nav sharded build (cold +
    warm-with-stage-timers) and, at shards=1 only, the batched baseline
    for the recall gate and the PQ-nav compute-cut column."""
    import jax
    import jax.numpy as jnp
    from repro.core import pq as pq_mod
    from repro.core.distributed import ShardPlan, build_vamana_sharded
    from repro.launch.mesh import make_local_mesh

    n = N_SMOKE if smoke else N
    ds = make_filtered_dataset(n=n, d=D, n_queries=N_QUERIES, seed=0)
    data, queries = ds.vectors, ds.queries

    t0 = time.time()
    cb = pq_mod.train_pq(jax.random.PRNGKey(0), jnp.asarray(data), 8,
                         iters=8)
    codes = pq_mod.encode_pq(cb, jnp.asarray(data))
    jax.block_until_ready(codes)
    pq_s = time.time() - t0

    plan = ShardPlan(mesh=make_local_mesh(1, shards),
                     shard_axes=("model",))
    t0 = time.time()
    build_vamana_sharded(data, plan, R, ELL, ALPHA, seed=0, codes=codes,
                         codebook=cb)
    cold_s = time.time() - t0
    stages: dict = {}
    t0 = time.time()
    adj, med = build_vamana_sharded(data, plan, R, ELL, ALPHA, seed=0,
                                    codes=codes, codebook=cb,
                                    stage_times=stages)
    warm_s = time.time() - t0
    rec = graph.greedy_recall_at_k(data, adj, med, queries, ell=64)

    block = {"shards": shards, "pq_train_s": pq_s,
             "wall_s": warm_s, "wall_s_cold": cold_s,
             "stage_times": stages, "recall_at_10": rec}
    if shards == 1:
        t0 = time.time()
        graph.build_vamana_batched(data, R, ELL, ALPHA, seed=0)
        cold_b = time.time() - t0
        t0 = time.time()
        adj_b, med_b = graph.build_vamana_batched(data, R, ELL, ALPHA,
                                                  seed=0)
        block["batched_warm_s"] = time.time() - t0
        block["batched_cold_s"] = cold_b
        block["batched_recall_at_10"] = graph.greedy_recall_at_k(
            data, adj_b, med_b, queries, ell=64)
    with open(out_path, "w") as fh:
        json.dump(block, fh)


def run_sharded(out_path: str = OUT_PATH, smoke: bool = False) -> list:
    """Orchestrate the shard-count subprocesses and merge a ``sharded``
    block into ``out_path`` (leaving the plain-bench payload in place)."""
    blocks = {}
    for s in SHARD_COUNTS:
        with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as f:
            tmp = f.name
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count="
                            + str(SHARD_DEVICES)).strip()
        cmd = [sys.executable, "-m", "benchmarks.bench_build",
               "--shard-worker", str(s), "--worker-out", tmp]
        if smoke:
            cmd.append("--smoke")
        out = subprocess.run(cmd, env=env, capture_output=True, text=True)
        assert out.returncode == 0, \
            f"shard worker {s} failed:\n{out.stdout}\n{out.stderr}"
        with open(tmp) as fh:
            blocks[s] = json.load(fh)
        os.unlink(tmp)

    b1 = blocks[1]
    nav = b1["stage_times"]["nav_prune_s"]
    rest = b1["stage_times"]["scatter_s"]
    n = N_SMOKE if smoke else N
    shards_out = {}
    for s in SHARD_COUNTS:
        modeled = rest + nav / s
        shards_out[str(s)] = dict(
            blocks[s],
            modeled_s=modeled,
            nodes_per_sec_modeled=n / modeled,
            build_scaling_modeled=(rest + nav) / modeled,
            speedup_vs_batched_modeled=(b1.get("batched_warm_s", 0.0)
                                        / modeled)
            if "batched_warm_s" in b1 else None,
        )
    sharded = {
        "devices": SHARD_DEVICES,
        "scaling_model": SCALING_MODEL,
        "note": "fake single-core devices execute shard_map serially: "
                "wall_s is the honest (slower) measured time; modeled_s = "
                "t_scatter + t_nav_prune/S from the 1-shard stage timers "
                "(navigation+prune shard over the mesh, the reverse-edge "
                "scatter/overflow drain stays replicated). The PQ-nav "
                "compute cut (batched_warm_s vs shards=1 wall_s) is a "
                "separate, fully measured effect (docs/distributed.md)",
        "recall_gap_max": RECALL_GAP_MAX,
        "shards": shards_out,
    }

    results = []
    for s in SHARD_COUNTS:
        bk = shards_out[str(s)]
        results.append(BenchResult(
            name=f"build/shards{s}", us_per_call=bk["wall_s"] * 1e6,
            derived={"modeled_s": f"{bk['modeled_s']:.1f}",
                     "scaling": f"{bk['build_scaling_modeled']:.2f}x",
                     "recall@10": f"{bk['recall_at_10']:.3f}"}))

    if not smoke:
        rb = b1["batched_recall_at_10"]
        for s in SHARD_COUNTS:
            gap = rb - blocks[s]["recall_at_10"]
            assert gap <= RECALL_GAP_MAX, \
                f"shards={s}: PQ-nav build recall trails batched by " \
                f"{gap:.3f} (> {RECALL_GAP_MAX})"
        try:
            with open(out_path) as fh:
                payload = json.load(fh)
        except FileNotFoundError:
            payload = {}
        payload["sharded"] = sharded
        with open(out_path, "w") as fh:
            json.dump(payload, fh, indent=2)
    return results


def main():
    import argparse
    from repro.utils import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny end-to-end run, no perf bars / JSON output")
    ap.add_argument("--shards", action="store_true",
                    help="run the sharded-build scaling block (subprocess "
                         "per shard count in {1,2,4} under a 4-fake-device "
                         "mesh) and merge it into the JSON")
    ap.add_argument("--shard-worker", type=int, default=0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--worker-out", default="", help=argparse.SUPPRESS)
    ap.add_argument("--out", default=OUT_PATH)
    args = ap.parse_args()
    if args.shard_worker:
        _shard_worker(args.shard_worker, args.smoke, args.worker_out)
        return
    if args.shards:
        for res in run_sharded(out_path=args.out, smoke=args.smoke):
            print(res.csv())
        return
    for res in run(out_path=args.out, smoke=args.smoke):
        print(res.csv())


if __name__ == "__main__":
    main()
