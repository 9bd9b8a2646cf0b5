"""Multi-device correctness: runs subprocesses with fake CPU devices
(XLA_FLAGS can't change after jax init, so each scenario is a script).

The whole module is marked ``dist`` — scripts/test_fast.sh runs it as its
own leg under ``--xla_force_host_platform_device_count=4``; tier-1 runs
it unmarked too."""
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.dist

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str, devices: int = 8, timeout: int = 420):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={devices}").strip()
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_sp_decode_attention_matches_reference():
    _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_local_mesh
from repro.serve import sp_attention as SP

mesh = make_local_mesh(1, 8)
b, t, hq, hkv, dh = 2, 64, 8, 4, 16
rng = np.random.default_rng(0)
q = jnp.asarray(rng.normal(0, 1, (b, 1, hq, dh)).astype(np.float32))
k = jnp.asarray(rng.normal(0, 1, (b, t, hkv, dh)).astype(np.float32))
v = jnp.asarray(rng.normal(0, 1, (b, t, hkv, dh)).astype(np.float32))
pos = jnp.asarray(40, jnp.int32)

def body(q, k, v, pos):
    return SP.sp_decode_attention_local(q, k, v, pos, n_kv=hkv,
                                        axis_name="model")

from jax import shard_map
f = jax.jit(shard_map(
    body, mesh=mesh,
    in_specs=(P(), P(None, "model", None, None), P(None, "model", None, None),
              P()),
    out_specs=P(), check_vma=False))
got = f(q, k, v, pos)
want = SP.reference_decode_attention(q, k, v, pos, n_kv=hkv)
np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                           rtol=2e-5, atol=2e-5)
print("SP-ATTN-OK")
""")


def test_sp_cache_update_owner_only():
    _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_local_mesh
from repro.serve import sp_attention as SP

mesh = make_local_mesh(1, 8)
b, t, hkv, dh = 1, 32, 2, 4
k_cache = jnp.zeros((b, t, hkv, dh))
v_cache = jnp.zeros((b, t, hkv, dh))
k_new = jnp.ones((b, 1, hkv, dh))
v_new = jnp.full((b, 1, hkv, dh), 2.0)
pos = jnp.asarray(13, jnp.int32)

def body(kc, vc, kn, vn, pos):
    return SP.sp_cache_update(kc, vc, kn, vn, pos, axis_name="model")

from jax import shard_map
f = jax.jit(shard_map(
    body, mesh=mesh,
    in_specs=(P(None, "model", None, None), P(None, "model", None, None),
              P(), P(), P()),
    out_specs=(P(None, "model", None, None), P(None, "model", None, None)),
    check_vma=False))
k_out, v_out = f(k_cache, v_cache, k_new, v_new, pos)
k_np = np.asarray(k_out)
assert np.all(k_np[0, 13] == 1.0)
mask = np.ones(t, bool); mask[13] = False
assert np.all(k_np[0, mask] == 0.0)
assert np.all(np.asarray(v_out)[0, 13] == 2.0)
print("SP-CACHE-OK")
""")


def test_distributed_search_matches_local():
    _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import engine as eng
from repro.core import distributed as D
from repro.core import search as S
from repro.core.selectors import stack_filters
from repro.data.synth import make_filtered_dataset, make_selectors
from repro.launch.mesh import make_local_mesh

ds = make_filtered_dataset(n=2048, d=16, n_queries=4, n_labels=30, seed=0)
cfg = eng.IndexConfig(r=12, r_dense=96, l_build=24, pq_m=8, max_labels=16)
e = eng.FilteredANNEngine.build(ds.vectors, ds.label_offsets, ds.label_flat,
                                ds.n_labels, ds.values, cfg)
sels = make_selectors(ds, e, "label_or")
plans = [s.plan(cfg.ql, cfg.cap) for s in sels]
qf = stack_filters([p.qfilter for p in plans])
queries = jnp.asarray(np.pad(ds.queries, ((0, 0), (0, 0))))
params = S.SearchParams(l_search=32, k=10, max_hops=128, mode="spec_in")

local = S.filtered_search(e.store, e.codes, e.codebook, e.mem, qf,
                          queries, e.medoid, params)

mesh = make_local_mesh(2, 4)
plan = D.ShardPlan(mesh=mesh, shard_axes=("data", "model"))
store = D.pad_store(e.store, plan.n_shards)
dist = D.distributed_filtered_search(plan, store, e.codes, e.codebook,
                                     e.mem, qf, queries, e.medoid, params)
np.testing.assert_array_equal(np.asarray(local.ids), np.asarray(dist.ids))
np.testing.assert_allclose(np.asarray(local.dists), np.asarray(dist.dists),
                           rtol=1e-5)
np.testing.assert_array_equal(np.asarray(local.io_pages),
                              np.asarray(dist.io_pages))
print("DIST-SEARCH-OK")
""", timeout=600)


def test_compressed_psum_matches_mean():
    _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_local_mesh
from repro.train import grad_compress as GC

mesh = make_local_mesh(8, 1)
rng = np.random.default_rng(0)
grads = {"w": jnp.asarray(rng.normal(0, 1, (8, 64, 40)).astype(np.float32))}
err = {"w": jnp.zeros((64, 40), jnp.float32)}

def body(g, e):
    mean, new_e = GC.compressed_psum_grads(
        {"w": g["w"][0]}, {"w": e["w"]}, "data")
    return mean, {"w": new_e["w"][None]}     # stack per-device error states

from jax import shard_map
f = jax.jit(shard_map(
    body, mesh=mesh,
    in_specs=({"w": P("data", None, None)}, {"w": P()}),
    out_specs=({"w": P()}, {"w": P("data", None, None)}),
    check_vma=False))
mean, new_e = f(grads, err)
want = np.asarray(grads["w"]).mean(0)
got = np.asarray(mean["w"])
# int8-quantized mean within block-scale tolerance
tol = np.abs(np.asarray(grads["w"])).max() / 127 * 1.5
assert np.abs(got - want).max() < tol, np.abs(got - want).max()
# error feedback carries the residual
assert np.asarray(new_e["w"]).shape == (8, 64, 40)
print("GC-OK")
""")


# ---------------------------------------------------------------------------
# Sharded pipelined execution (ShardedSearchRunner / build_vamana_sharded)
# ---------------------------------------------------------------------------

def test_sharded_runner_bit_identical_across_shard_counts():
    """1-vs-2-vs-4-shard pipelined search: every SearchResult field matches
    the single-device driver bit-for-bit (distances to float tolerance),
    in all three filter modes."""
    _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import engine as eng
from repro.core import distributed as D
from repro.core import search as S
from repro.core.selectors import stack_filters
from repro.data.synth import make_filtered_dataset, make_selectors
from repro.launch.mesh import make_local_mesh

ds = make_filtered_dataset(n=2048, d=16, n_queries=33, n_labels=30, seed=0)
cfg = eng.IndexConfig(r=12, r_dense=96, l_build=24, pq_m=8, max_labels=16)
e = eng.FilteredANNEngine.build(ds.vectors, ds.label_offsets, ds.label_flat,
                                ds.n_labels, ds.values, cfg)
sels = make_selectors(ds, e, "label_or")
plans = [s.plan(cfg.ql, cfg.cap) for s in sels]
qf = stack_filters([p.qfilter for p in plans])
queries = jnp.asarray(ds.queries)

INT_FIELDS = ("ids", "io_pages", "dist_comps", "hops", "fp_explored",
              "explored", "n_valid", "faults", "retries", "degraded")
for mode in ("post", "spec_in", "strict_in"):
    params = S.SearchParams(l_search=32, k=10, max_hops=128, mode=mode)
    base = S.filtered_search_pipelined(e.store, e.codes, e.codebook, e.mem,
                                       qf, queries, e.medoid, params,
                                       hop_chunk=16)
    for shards in (2, 4):
        plan = D.ShardPlan(mesh=make_local_mesh(1, shards),
                           shard_axes=("model",))
        runner = D.ShardedSearchRunner(plan, e.store, e.codes, e.codebook,
                                       e.mem)
        got = S.filtered_search_pipelined(e.store, e.codes, e.codebook,
                                          e.mem, qf, queries, e.medoid,
                                          params, hop_chunk=16,
                                          runner=runner)
        for f in INT_FIELDS:
            if hasattr(base, f):
                np.testing.assert_array_equal(
                    np.asarray(getattr(base, f)),
                    np.asarray(getattr(got, f)), err_msg=f"{mode}:{f}")
        np.testing.assert_allclose(np.asarray(base.dists),
                                   np.asarray(got.dists), rtol=1e-5)
        assert runner.cache_size() == 1   # one shard_map jit per params
print("SHARD-PARITY-OK")
""", devices=4, timeout=900)


def test_sharded_build_recall_within_one_percent():
    """Sharded Vamana build: exact-nav reproduces the batched builder's
    RNG stream (identical recall); PQ-approximate navigation stays within
    the 1% recall@10 envelope."""
    _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import distributed as D
from repro.core import graph, pq
from repro.launch.mesh import make_local_mesh

rng = np.random.default_rng(0)
n, d = 1536, 16
data = rng.standard_normal((n, d), dtype=np.float32)
queries = rng.standard_normal((32, d), dtype=np.float32)

adj_b, med_b = graph.build_vamana_batched(data, r=12, ell=24, batch=256,
                                          seed=3)
rb = graph.greedy_recall_at_k(data, adj_b, med_b, queries, ell=32, k=10)

plan = D.ShardPlan(mesh=make_local_mesh(1, 4), shard_axes=("model",))
st = {}
adj_s, med_s = D.build_vamana_sharded(data, plan, r=12, ell=24, batch=256,
                                      seed=3, stage_times=st)
assert med_s == med_b
rs = graph.greedy_recall_at_k(data, adj_s, med_s, queries, ell=32, k=10)
assert abs(rs - rb) <= 0.01, (rs, rb)
assert st["nav_prune_s"] > 0 and st["scatter_s"] > 0

cb = pq.train_pq(jax.random.PRNGKey(0), data, m=8, iters=4)
codes = pq.encode_pq(cb, data)
adj_p, med_p = D.build_vamana_sharded(data, plan, r=12, ell=24, batch=256,
                                      seed=3, codes=codes, codebook=cb)
rp = graph.greedy_recall_at_k(data, adj_p, med_p, queries, ell=32, k=10)
assert rp >= rb - 0.01, (rp, rb)
print("SHARD-BUILD-OK", rb, rs, rp)
""", devices=4, timeout=900)


def test_sharded_warmup_compiles_once_then_serves_hot():
    """Index.build(shards=…) -> Session.warmup covers the sharded bucket-jit
    ladder: serving production-width batches afterwards triggers NO fresh
    compile (runner jit cache sizes frozen), and repeat widths reuse the
    same single shard_map artifact per params."""
    _run("""
import numpy as np
from repro.api import Index, SearchRequest, Session
from repro.api.filters import Tag
from repro.core.engine import IndexConfig

rng = np.random.default_rng(0)
n, d = 1536, 16
vectors = rng.standard_normal((n, d), dtype=np.float32)
cats = ["a", "b", "c", "d"]
meta = [{"cat": cats[int(rng.integers(0, 4))], "price": float(rng.random())}
        for _ in range(n)]
idx = Index.build(vectors, meta,
                  IndexConfig(r=12, r_dense=96, l_build=24, pq_m=8,
                              max_labels=16), shards=2)
runner = idx.engine._runner
assert runner is not None and runner.n_shards == 2

# policy="post" pins the graph-search mechanism: the prefilter route
# never touches the hop loop, so it would leave the runner cache cold
reqs = [SearchRequest(query=vectors[i] + 0.01, k=5, policy="post",
                      filter=Tag("cat") == cats[i % 4])
        for i in range(16)]
with Session(idx) as sess:
    sess.warmup(reqs, rungs=())
    # snapshot: outer shard_map jits (one per params variant warmed) and
    # their per-width compile counts
    n_outer = runner.cache_size()
    n_inner = sum(f._cache_size() for f in runner._run_cache.values())
    assert n_outer >= 1 and n_inner >= 1
    # production traffic at widths the ladder covered: must stay hot
    for lo, hi in ((0, 16), (4, 12), (0, 8), (7, 8)):
        hs = sess.submit_many(reqs[lo:hi])
        sess.flush()
        for h in hs:
            h.result(timeout=300)
    assert runner.cache_size() == n_outer
    assert sum(f._cache_size()
               for f in runner._run_cache.values()) == n_inner, \
        "fresh sharded jit mid-serve: warmup ladder missed a width"
print("SHARD-WARM-OK", n_outer, n_inner)
""", devices=4, timeout=900)
