"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.pq_scan import pq_scan
from repro.kernels.approx_probe import approx_probe
from repro.kernels.l2_rerank import l2_rerank
from repro.kernels.prune_scan import prune_scan


# ---------------------------------------------------------------------------
# pq_scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 5, 128, 700, 1024])
@pytest.mark.parametrize("m,k", [(8, 256), (16, 256), (32, 16)])
@pytest.mark.parametrize("codes_dtype", [jnp.uint8, jnp.int32])
def test_pq_scan_matches_ref(n, m, k, codes_dtype):
    rng = np.random.default_rng(n * m + k)
    codes = jnp.asarray(rng.integers(0, k, (n, m)), dtype=codes_dtype)
    table = jnp.asarray(rng.normal(0, 1, (m, k)).astype(np.float32))
    got = pq_scan(codes, table, interpret=True, tile_n=256)
    want = ref.pq_scan_ref(codes, table)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-5)


def test_pq_scan_tile_invariance():
    rng = np.random.default_rng(0)
    codes = jnp.asarray(rng.integers(0, 256, (1000, 16)), dtype=jnp.uint8)
    table = jnp.asarray(rng.normal(0, 1, (16, 256)).astype(np.float32))
    a = pq_scan(codes, table, interpret=True, tile_n=128)
    b = pq_scan(codes, table, interpret=True, tile_n=512)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


# ---------------------------------------------------------------------------
# approx_probe
# ---------------------------------------------------------------------------

def _rand_probe_inputs(rng, n, ql=8):
    blooms = jnp.asarray(rng.integers(0, 2 ** 31, n, dtype=np.int64)
                         .astype(np.uint32))
    buckets = jnp.asarray(rng.integers(0, 256, n).astype(np.uint8))
    or_masks = jnp.asarray(rng.integers(0, 2 ** 16, ql).astype(np.uint32))
    params = jnp.asarray(np.array([
        int(rng.integers(0, 2 ** 16)),   # and_mask
        ql,                               # n_or_masks
        int(rng.integers(0, 128)),        # lo
        int(rng.integers(128, 256)),      # hi
        int(rng.integers(0, 3)),          # label_mode
        int(rng.integers(0, 2)),          # range_on
        int(rng.integers(0, 2)),          # combine
        0], np.int32))
    return blooms, buckets, or_masks, params


@pytest.mark.parametrize("n", [1, 64, 999, 2048])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_approx_probe_matches_ref(n, seed):
    rng = np.random.default_rng(seed)
    blooms, buckets, or_masks, params = _rand_probe_inputs(rng, n)
    got = approx_probe(blooms, buckets, or_masks, params,
                       interpret=True, tile_n=256)
    want = ref.approx_probe_ref(blooms, buckets, or_masks, params)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_approx_probe_all_mode_combos():
    rng = np.random.default_rng(7)
    n = 333
    blooms = jnp.asarray(rng.integers(0, 2 ** 31, n, dtype=np.int64)
                         .astype(np.uint32))
    buckets = jnp.asarray(rng.integers(0, 256, n).astype(np.uint8))
    or_masks = jnp.asarray(rng.integers(0, 2 ** 12, 8).astype(np.uint32))
    for label_mode in (0, 1, 2):
        for range_on in (0, 1):
            for combine in (0, 1):
                params = jnp.asarray(np.array(
                    [0b1010, 8, 50, 200, label_mode, range_on, combine, 0],
                    np.int32))
                got = approx_probe(blooms, buckets, or_masks, params,
                                   interpret=True, tile_n=128)
                want = ref.approx_probe_ref(blooms, buckets, or_masks, params)
                np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# hop_fused (the filtered-search hot loop's candidate pass)
# ---------------------------------------------------------------------------

def _rand_hop_inputs(rng, b, c, m=8, k=256, f=3, ql=8, nr=4):
    codes = jnp.asarray(rng.integers(0, k, (b, c, m)).astype(np.uint8))
    blooms = jnp.asarray(rng.integers(0, 2 ** 31, (b, c), dtype=np.int64)
                         .astype(np.int32))
    buckets = jnp.asarray(rng.integers(0, 256, (b, c, f)).astype(np.int32))
    in_merged = jnp.asarray(rng.integers(0, 2, (b, c)).astype(bool))
    table = jnp.asarray(rng.normal(0, 1, (b, m, k)).astype(np.float32))
    scalars = jnp.asarray(np.stack([
        rng.integers(0, 2 ** 16, b),      # and_mask
        rng.integers(0, 3, b),            # label_mode
        rng.integers(0, 3, b),            # merged_mode
        rng.integers(0, 2, b)], axis=1).astype(np.int32))   # combine
    or_masks = jnp.asarray(rng.integers(0, 2 ** 12, (b, ql)).astype(np.int32))
    range_field = jnp.asarray(
        np.where(rng.random((b, nr)) < 0.5,
                 rng.integers(0, f, (b, nr)), -1).astype(np.int32))
    lo = rng.integers(0, 128, (b, nr)).astype(np.int32)
    hi = rng.integers(128, 256, (b, nr)).astype(np.int32)
    return (codes, blooms, buckets, in_merged, table, scalars, or_masks,
            range_field, jnp.asarray(lo), jnp.asarray(hi))


# b=9 and b=13 span two 8-row blocks with padded rows in the second
@pytest.mark.parametrize("b,c", [(1, 7), (3, 64), (4, 300), (2, 520),
                                 (9, 130), (13, 40)])
@pytest.mark.parametrize("seed", [0, 1])
def test_hop_fused_matches_ref(b, c, seed):
    rng = np.random.default_rng(seed * 100 + b * c)
    args = _rand_hop_inputs(rng, b, c)
    key_k, ok_k = ops.hop_fused_interpret(*args)
    key_r, ok_r = ref.hop_fused_ref(*args)
    np.testing.assert_array_equal(np.asarray(ok_k), np.asarray(ok_r))
    np.testing.assert_allclose(np.asarray(key_k), np.asarray(key_r),
                               rtol=1e-5, atol=1e-4)


def test_hop_fused_ref_matches_selectors_and_pq():
    """The decomposed kernel inputs must reproduce the production
    primitives exactly: ok == selectors.is_member_approx on the gathered
    ids, and the distance term == pq.adc_lookup (bitwise)."""
    from repro.core import pq as core_pq
    from repro.core.selectors import (InMemory, is_member_approx,
                                      kernel_filter_params, kernel_view,
                                      merged_membership)
    from repro.data.synth import make_filtered_dataset, make_selectors
    from repro.core import engine as eng

    ds = make_filtered_dataset(n=800, d=16, n_queries=6, n_labels=20, seed=2)
    cfg = eng.IndexConfig(r=8, r_dense=32, l_build=16, pq_m=8, max_labels=8)
    e = eng.FilteredANNEngine.build(ds.vectors, ds.label_offsets,
                                   ds.label_flat, ds.n_labels, ds.values,
                                   cfg)
    rng = np.random.default_rng(0)
    for workload in ("label_or", "label_and", "range", "hybrid"):
        sels = make_selectors(ds, e, workload)
        from repro.core.selectors import stack_filters
        qf = stack_filters([s.plan(cfg.ql, cfg.cap).qfilter for s in sels])
        B = len(sels)
        ids = jnp.asarray(rng.integers(0, 800, (B, 50)).astype(np.int32))
        tables = jax.vmap(
            lambda q: core_pq.distance_table(e.codebook, q))(
                jnp.asarray(ds.queries[:B]))
        bl, bc = kernel_view(e.mem)
        in_merged = jax.vmap(merged_membership)(qf, ids)
        key, ok = ref.hop_fused_ref(e.codes[ids], bl[ids], bc[ids],
                                    in_merged, tables,
                                    *kernel_filter_params(qf))
        want_ok = jax.vmap(is_member_approx, in_axes=(0, 0, None))(
            qf, ids, e.mem)
        np.testing.assert_array_equal(np.asarray(ok), np.asarray(want_ok))
        want_d = np.asarray(
            jax.vmap(core_pq.adc_lookup)(e.codes[ids], tables))
        ok_np = np.asarray(ok)
        key_np = np.asarray(key)
        # valid candidates: key IS the distance, bitwise
        np.testing.assert_array_equal(key_np[ok_np], want_d[ok_np])
        # invalid: distance + penalty, in the same f32 arithmetic
        np.testing.assert_array_equal(
            key_np[~ok_np],
            (want_d.astype(np.float32) + np.float32(1e12))[~ok_np])


# ---------------------------------------------------------------------------
# l2_rerank
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,d", [(1, 8), (17, 64), (300, 128), (256, 48)])
def test_l2_rerank_matches_ref(b, d):
    rng = np.random.default_rng(b * d)
    vecs = jnp.asarray(rng.normal(0, 1, (b, d)).astype(np.float32))
    q = jnp.asarray(rng.normal(0, 1, d).astype(np.float32))
    got = l2_rerank(vecs, q, interpret=True, tile_b=64)
    want = ref.l2_rerank_ref(vecs, q)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# oracles agree with the production (core) implementations
# ---------------------------------------------------------------------------

def test_refs_match_core_pq():
    from repro.core import pq as core_pq
    rng = np.random.default_rng(3)
    codes = jnp.asarray(rng.integers(0, 256, (500, 8)), dtype=jnp.uint8)
    table = jnp.asarray(rng.normal(0, 1, (8, 256)).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(ref.pq_scan_ref(codes, table)),
        np.asarray(core_pq.adc_lookup(codes, table)), rtol=1e-6)


def test_ops_dispatch_cpu():
    rng = np.random.default_rng(4)
    codes = jnp.asarray(rng.integers(0, 256, (100, 8)), dtype=jnp.uint8)
    table = jnp.asarray(rng.normal(0, 1, (8, 256)).astype(np.float32))
    got = ops.pq_scan(codes, table)            # CPU -> XLA reference path
    want = ops.pq_scan_interpret(codes, table) # Pallas interpret path
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


# ---------------------------------------------------------------------------
# or_scatter
# ---------------------------------------------------------------------------

def _or_scatter_numpy(words, slots):
    out = np.asarray(words, np.int32).copy()
    n_bits = out.shape[1] * 32
    for b, row in enumerate(np.asarray(slots)):
        for s in row:
            if 0 <= s < n_bits:
                out[b, s >> 5] |= np.int32(1) << np.int32(s & 31)
    return out


# nw=1500 spans two (8, 128) word tiles, the second one padded
@pytest.mark.parametrize("b,nw,c", [(1, 1, 4), (3, 8, 33), (7, 4, 128),
                                    (2, 32, 300), (9, 1500, 200)])
@pytest.mark.parametrize("seed", [0, 1])
def test_or_scatter_matches_ref(b, nw, c, seed):
    rng = np.random.default_rng(seed * 997 + b * nw * c)
    # dense slot range + negatives and overflow sentinels + duplicates,
    # over words with bits already set
    words = jnp.asarray(
        rng.integers(-2 ** 31, 2 ** 31, (b, nw), dtype=np.int64)
        .astype(np.int32))
    slots = jnp.asarray(
        rng.integers(-8, nw * 32 + 8, (b, c)).astype(np.int32))
    want = _or_scatter_numpy(words, slots)
    got_k = ops.or_scatter_interpret(words, slots)
    got_r = ref.or_scatter_ref(words, slots)
    np.testing.assert_array_equal(np.asarray(got_r), want)
    np.testing.assert_array_equal(np.asarray(got_k), want)


def test_or_scatter_idempotent_and_sign_bit():
    words = jnp.zeros((2, 2), jnp.int32)
    # duplicate slots, the sign bit (31), and a word-1 slot; row 1 all
    # out-of-range -> untouched
    slots = jnp.asarray([[31, 31, 0, 32, 0], [-1, 64, 64, 100, -5]],
                        jnp.int32)
    want = np.array([[np.int32(1) << 31 | 1, 1], [0, 0]], np.int32)
    np.testing.assert_array_equal(
        np.asarray(ref.or_scatter_ref(words, slots)), want)
    np.testing.assert_array_equal(
        np.asarray(ops.or_scatter_interpret(words, slots)), want)
    # OR-ing into already-set words is a no-op
    again = ref.or_scatter_ref(jnp.asarray(want), slots)
    np.testing.assert_array_equal(np.asarray(again), want)


# ---------------------------------------------------------------------------
# prune_scan
# ---------------------------------------------------------------------------

def _prune_inputs(rng, b, c, pad_frac=0.3):
    """Sorted candidate→point distances (+inf right pads) + pairwise dists."""
    dp = np.sort(rng.normal(2, 1, (b, c)).astype(np.float32) ** 2, axis=1)
    for i, k in enumerate(rng.integers(0, max(1, int(c * pad_frac)), b)):
        if k:
            dp[i, -k:] = np.inf
    dcc = rng.normal(0, 1, (b, c, c)).astype(np.float32) ** 2
    dcc = (dcc + dcc.transpose(0, 2, 1)) / 2
    for i in range(b):
        np.fill_diagonal(dcc[i], 0.0)
    return jnp.asarray(dp), jnp.asarray(dcc)


@pytest.mark.parametrize("b,c,r", [(1, 16, 4), (8, 48, 12), (5, 96, 32),
                                   (2, 33, 5), (11, 130, 24)])
@pytest.mark.parametrize("alpha", [1.0, 1.2])
def test_prune_scan_matches_ref(b, c, r, alpha):
    rng = np.random.default_rng(b * c + r)
    dp, dcc = _prune_inputs(rng, b, c)
    a2 = alpha * alpha
    got = np.asarray(prune_scan(dp, dcc, a2, r, interpret=True))
    want = np.asarray(ref.prune_scan_ref(dp, dcc, a2, r))
    np.testing.assert_array_equal(got, want)
    assert (got.sum(1) <= r).all()


def test_prune_scan_matches_numpy_robust_prune():
    """Sorted-space scan keep set == the sequential numpy RobustPrune."""
    from repro.core.graph import robust_prune
    rng = np.random.default_rng(0)
    n, d, r, alpha = 80, 16, 8, 1.2
    data = rng.normal(0, 1, (n, d)).astype(np.float32)
    p_vec = rng.normal(0, 1, d).astype(np.float32)
    cand = np.arange(n)
    want = robust_prune(p_vec, cand, data, r, alpha)

    d_p = np.sum((data - p_vec[None]) ** 2, axis=1).astype(np.float32)
    order = np.argsort(d_p, kind="stable")
    dp_s = d_p[order][None]
    diff = data[order][:, None, :] - data[order][None, :, :]
    dcc = np.sum(diff * diff, axis=-1).astype(np.float32)[None]
    keep = np.asarray(ref.prune_scan_ref(
        jnp.asarray(dp_s), jnp.asarray(dcc), alpha * alpha, r))[0]
    got = cand[order][keep]        # keeps happen in ascending-distance order
    np.testing.assert_array_equal(got, want)


def test_prune_scan_respects_cap():
    rng = np.random.default_rng(7)
    dp, dcc = _prune_inputs(rng, 6, 40, pad_frac=0.0)
    # alpha=1, zero pairwise distances -> everything dominated by the first
    keep = np.asarray(ref.prune_scan_ref(
        dp, jnp.zeros_like(dcc), 1.0, 10))
    assert (keep.sum(1) == 1).all()
    # huge alpha -> nothing dominated, cap at r survivors
    keep = np.asarray(ref.prune_scan_ref(dp, dcc, 1e9, 10))
    assert (keep.sum(1) == 10).all()
