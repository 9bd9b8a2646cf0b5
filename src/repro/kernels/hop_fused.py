"""Pallas TPU kernel: fused per-hop candidate pass for filtered search.

One VMEM pass over a whole ``(B, W·C)`` candidate slab computes, per
candidate, everything the hop loop needs before the pool merge:

  * **PQ ADC distance** — ``sum_m table[m, codes[c, m]]`` via a one-hot
    compare + select + sum over the K centroids (the gather rephrased so
    it vectorizes on the VPU).
  * **approximate membership** — the bloom-word AND/OR probes plus the
    NR-slot bucket-code range test of ``selectors.is_member_approx``. The
    rare-list binary search arrives precomputed as the ``in_merged`` input
    (see ``selectors.merged_membership``): searchsorted does not tile, the
    bitwise half does.
  * **invalid-penalty key** — ``distance + INVALID_PENALTY·(¬ok)``, the
    pool-admission priority of speculative in-filtering.

Candidates run along lanes and query rows along sublanes: one program
takes 8 query rows × ``tile_c`` candidates, per-query parameters arrive
as (8, X) blocks and are lane-broadcast against the slab, so one launch
serves the whole query batch — this is what makes the batched hop loop
amortize: B queries × W beams × (R+R_d) candidates in a single kernel
instead of 3 unfused gathers per query under ``vmap``.

Grid: ``(ceil(B/8), WC_pad // tile_c)``. VMEM per program (tile_c=512,
M=32, K=256, F=2): codes 512 KB + table 1 MB (lane-padded) + one (K, T)
one-hot temp 512 KB, each double-buffered where streamed — under the
16 MB scoped v5e budget. The jnp oracle is ``kernels/ref.hop_fused_ref``;
dispatch lives in ``kernels/ops.hop_fused`` (compiled on TPU,
reference on CPU, interpret mode for tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import INVALID_PENALTY

TILE_C = 512
ROWS = 8                  # query rows per program: one sublane tile
_PENALTY_LITERAL = 1e12   # the kernel body's copy: bodies cannot capture
import numpy as _np                                 # traced array constants
assert float(INVALID_PENALTY) == float(_np.float32(_PENALTY_LITERAL))


def _hop_fused_kernel(codes_ref, blooms_ref, buckets_ref, merged_ref,
                      table_ref, scal_ref, om_ref, rf_ref, blo_ref, bhi_ref,
                      key_ref, ok_ref):
    m, rows, t = codes_ref.shape                       # (M, 8, T)
    f = buckets_ref.shape[0]                           # (F, 8, T)
    k = table_ref.shape[1]                             # (8, K, M)
    blooms = blooms_ref[...]                           # (8, T)
    in_merged = merged_ref[...] != 0                   # (8, T)

    def col(ref, j):
        """Per-query parameter j as an (8, T) plane (lane broadcast)."""
        return jnp.broadcast_to(ref[:, j:j + 1], (rows, t))

    # --- PQ ADC distance, one query row at a time: the (K, T) one-hot
    # compare selects table[sub, code] per lane; exactly one K entry is
    # nonzero, so the sublane sum is exact and the M-accumulation order
    # matches ref.adc_slab_ref ---
    kk = jax.lax.broadcasted_iota(jnp.int32, (k, t), 0)
    row_ix = jax.lax.broadcasted_iota(jnp.int32, (rows, t), 0)

    def adc_row(q, d):
        tab = table_ref[q]                             # (K, M)
        dq = jnp.zeros((1, t), jnp.float32)
        for sub in range(m):                           # M static: unrolled
            onehot = codes_ref[sub, pl.ds(q, 1), :] == kk       # (K, T)
            dq = dq + jnp.sum(jnp.where(onehot, tab[:, sub:sub + 1], 0.0),
                              axis=0, keepdims=True)
        return jnp.where(row_ix == q, jnp.broadcast_to(dq, (rows, t)), d)

    d = jax.lax.fori_loop(0, rows, adc_row,
                          jnp.zeros((rows, t), jnp.float32))

    # --- frequent-label Bloom probes. Predicates combine with &/| only:
    # the chip's compiler cannot select between boolean vectors ---
    and_mask = col(scal_ref, 0)
    lm1, lm2 = col(scal_ref, 1) == 1, col(scal_ref, 1) == 2
    mm1, mm2 = col(scal_ref, 2) == 1, col(scal_ref, 2) == 2
    c_or = col(scal_ref, 3) == 1
    and_ok = (blooms & and_mask) == and_mask
    hit_any = jnp.zeros((rows, t), jnp.bool_)
    has_or = jnp.zeros((rows, t), jnp.bool_)
    for j in range(om_ref.shape[1]):                   # QL static: unrolled
        mask = col(om_ref, j)
        hit_any = hit_any | ((mask != 0) & ((blooms & mask) == mask))
        has_or = has_or | (mask != 0)

    label_or = (mm1 & (in_merged | hit_any)) | (~mm1 & has_or & hit_any)
    label_and = and_ok & (~mm2 | in_merged)
    label_present = lm1 | lm2
    label_ok = (lm1 & label_and) | (lm2 & label_or) | ~label_present

    # --- NR bucket-range slots (AND over the active ones) ---
    range_ok = jnp.ones((rows, t), jnp.bool_)
    range_present = jnp.zeros((rows, t), jnp.bool_)
    for j in range(rf_ref.shape[1]):                   # NR static: unrolled
        fj = col(rf_ref, j)
        v = jnp.zeros((rows, t), jnp.int32)
        for fi in range(f):
            v = jnp.where(fj == fi, buckets_ref[fi], v)
        ok_j = (v >= col(blo_ref, j)) & (v <= col(bhi_ref, j))
        range_ok = range_ok & (ok_j | (fj < 0))
        range_present = range_present | (fj >= 0)

    ok_and = (label_ok | ~label_present) & (range_ok | ~range_present)
    ok_or = (label_ok & label_present) | (range_ok & range_present)
    any_present = label_present | range_present
    ok = ~any_present | (c_or & ok_or) | (~c_or & ok_and)

    # _PENALTY_LITERAL (== ref.INVALID_PENALTY, asserted at import):
    # pallas_call kernels cannot capture traced array constants
    key_ref[...] = d + jnp.where(ok, 0.0, _PENALTY_LITERAL).astype(
        jnp.float32)
    ok_ref[...] = ok.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret", "tile_c"))
def hop_fused(codes_slab: jax.Array, blooms: jax.Array, buckets: jax.Array,
              in_merged: jax.Array, table: jax.Array, scalars: jax.Array,
              or_masks: jax.Array, range_field: jax.Array,
              bucket_lo: jax.Array, bucket_hi: jax.Array, *,
              interpret: bool = False,
              tile_c: int = TILE_C) -> tuple[jax.Array, jax.Array]:
    """Fused hop pass over a (B, C) candidate slab.

    codes_slab (B, C, M) uint8/int32; blooms (B, C) int32; buckets
    (B, C, F) int32; in_merged (B, C) bool; table (B, M, K) float32;
    scalars (B, 4) / or_masks (B, QL) / range_field, bucket_lo, bucket_hi
    (B, NR) int32 — the ``selectors.kernel_filter_params`` layout.
    Returns (key (B, C) float32, ok (B, C) bool).

    Candidates run along lanes and query rows along sublanes: B pads to a
    multiple of 8 and C to the lane tile, and the per-candidate tensors
    arrive candidate-minor ((M, B, C) codes, (F, B, C) buckets) so every
    block's last two dimensions are (8, tile)."""
    b, c, m = codes_slab.shape
    tile = min(tile_c, max(128, 1 << max(c - 1, 1).bit_length()))
    c_pad = -(-c // tile) * tile
    b_pad = -(-b // ROWS) * ROWS

    def pad(arr, row_axis, col_axis=None, dtype=jnp.int32):
        widths = [(0, 0)] * arr.ndim
        widths[row_axis] = (0, b_pad - b)
        if col_axis is not None:
            widths[col_axis] = (0, c_pad - c)
        return jnp.pad(arr.astype(dtype), widths)

    codes_t = pad(jnp.moveaxis(codes_slab, 2, 0), 1, 2)     # (M, B, C)
    buckets_t = pad(jnp.moveaxis(buckets, 2, 0), 1, 2)      # (F, B, C)
    blooms_p = pad(blooms, 0, 1)
    merged_p = pad(in_merged, 0, 1)
    table_t = pad(jnp.swapaxes(table, 1, 2), 0, dtype=jnp.float32)
    params = [pad(x, 0) for x in (scalars, or_masks, range_field,
                                  bucket_lo, bucket_hi)]
    f = buckets_t.shape[0]
    k = table_t.shape[1]

    def rows_spec(width):
        return pl.BlockSpec((ROWS, width), lambda i, j: (i, 0))

    key, ok = pl.pallas_call(
        _hop_fused_kernel,
        grid=(b_pad // ROWS, c_pad // tile),
        in_specs=[
            pl.BlockSpec((m, ROWS, tile), lambda i, j: (0, i, j)),
            pl.BlockSpec((ROWS, tile), lambda i, j: (i, j)),
            pl.BlockSpec((f, ROWS, tile), lambda i, j: (0, i, j)),
            pl.BlockSpec((ROWS, tile), lambda i, j: (i, j)),
            pl.BlockSpec((ROWS, k, m), lambda i, j: (i, 0, 0)),
        ] + [rows_spec(x.shape[1]) for x in params],
        out_specs=[
            pl.BlockSpec((ROWS, tile), lambda i, j: (i, j)),
            pl.BlockSpec((ROWS, tile), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b_pad, c_pad), jnp.float32),
            jax.ShapeDtypeStruct((b_pad, c_pad), jnp.int32),
        ],
        interpret=interpret,
    )(codes_t, blooms_p, buckets_t, merged_p, table_t, *params)
    return key[:b, :c], ok[:b, :c].astype(jnp.bool_)
