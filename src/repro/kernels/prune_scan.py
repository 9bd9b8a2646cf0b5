"""Pallas TPU kernel: RobustPrune domination scan — the Vamana-build hot loop.

One program per candidate row. Inputs arrive pre-sorted by distance to the
insert point (stable sort on host/XLA side), so the kernel walks lanes left
to right: lane i survives iff it was not dominated by an earlier survivor,
and each survivor prunes every lane j with α²·d(i, j) ≤ d(p, j). The scan is
inherently sequential in i but fully vectorized across the C lanes of each
step, so the VPU processes one (1, C) mask row per iteration.

The running masks are int32 (1, C) rows and every per-lane value the scan
needs (d(p, i), pruned[i]) is pulled out with a broadcasted-iota compare +
masked lane sum kept as a (1, 1) vector (TPU has no 1-D iota and no cheap
dynamic scalar reads from VMEM vectors); the pairwise row d(i, ·) is read
straight from the (C, C) distance block in VMEM with a dynamic row index.
Rows carry a unit axis ((B, 1, C) and (B, C, C)), so every block's last
two dimensions are the array's own.

VMEM per program (C=224): dcc 200 KB + a handful of (1, C) vectors — far
under budget; the grid streams rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _prune_scan_kernel(dp_ref, dcc_ref, keep_ref, *, a2: float, r: int):
    dp = dp_ref[0]                                      # (1, C)
    c = dp.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, c), 1)

    def pick(sel, x):                                   # x[i] as (1, 1)
        return jnp.sum(jnp.where(sel, x, 0), axis=1, keepdims=True)

    def body(i, state):
        pruned, keep, nk = state                        # int32 (1, C)/(1, 1)
        sel = lane == i                                 # (1, C) one-hot
        dp_i = pick(sel, dp)
        # |x| < inf is isfinite, in ops the chip's compiler lowers
        act = (pick(sel, pruned) == 0) & (nk < r) & (jnp.abs(dp_i) < jnp.inf)
        row_i = dcc_ref[0, pl.ds(i, 1), :]              # (1, C)
        hit = (act & (a2 * row_i <= dp)) | (sel & act)
        pruned = jnp.where(hit, 1, pruned)
        keep = jnp.where(sel & act, 1, keep)
        return (pruned, keep, nk + act.astype(jnp.int32))

    init = (jnp.zeros((1, c), jnp.int32), jnp.zeros((1, c), jnp.int32),
            jnp.zeros((1, 1), jnp.int32))
    _, keep, _ = jax.lax.fori_loop(0, c, body, init)
    keep_ref[0] = keep


@functools.partial(jax.jit, static_argnames=("a2", "r", "interpret"))
def prune_scan(dp_s: jax.Array, dcc_s: jax.Array, a2: float, r: int, *,
               interpret: bool = False) -> jax.Array:
    """Batched domination scan. dp_s (B, C) ascending (+inf pads);
    dcc_s (B, C, C) pairwise distances in the same order. Returns a
    (B, C) bool keep mask (≤ r survivors per row)."""
    b, c = dp_s.shape
    out = pl.pallas_call(
        functools.partial(_prune_scan_kernel, a2=float(a2), r=int(r)),
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, 1, c), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, c, c), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, c), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, 1, c), jnp.int32),
        interpret=interpret,
    )(dp_s.astype(jnp.float32).reshape(b, 1, c), dcc_s.astype(jnp.float32))
    return out.reshape(b, c) != 0
