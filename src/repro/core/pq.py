"""Product quantization: codebook training (k-means), encoding, ADC tables.

PQ-compressed vectors are the paper's in-memory tier: graph navigation compares
distances against PQ codes only; full-precision vectors are fetched from the
record store ("SSD") solely for re-ranking.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp


# float32 products at full precision: the TPU's default for a float32
# matmul is a single bfloat16 pass, which blurs nearest-centroid choices
_EXACT = jax.lax.Precision.HIGHEST


class PQCodebook(NamedTuple):
    centroids: jax.Array   # (M, ksub, dsub) float32
    dim: int               # original dimensionality (M * dsub, possibly padded)


def _kmeans_subspace(key, x, ksub: int, iters: int):
    """Plain Lloyd k-means for one subspace. x: (N, dsub)."""
    n = x.shape[0]
    idx = jax.random.choice(key, n, (ksub,), replace=n < ksub)
    cents = x[idx]

    def step(cents, _):
        # assign
        d = (jnp.sum(x * x, 1, keepdims=True)
             - 2.0 * jnp.matmul(x, cents.T, precision=_EXACT)
             + jnp.sum(cents * cents, 1)[None, :])
        assign = jnp.argmin(d, axis=1)
        onehot = jax.nn.one_hot(assign, ksub, dtype=x.dtype)      # (N, ksub)
        counts = onehot.sum(0)                                    # (ksub,)
        sums = jnp.matmul(onehot.T, x, precision=_EXACT)          # (ksub, dsub)
        new = jnp.where(counts[:, None] > 0, sums / jnp.maximum(counts, 1.0)[:, None],
                        cents)
        return new, None

    cents, _ = jax.lax.scan(step, cents, None, length=iters)
    return cents


@functools.partial(jax.jit, static_argnames=("m", "ksub", "iters"))
def train_pq(key, data, m: int, ksub: int = 256, iters: int = 8) -> PQCodebook:
    """Train M subspace codebooks of ksub centroids each. data: (N, D) float32.

    D must be divisible by m (callers pad otherwise).
    """
    n, d = data.shape
    assert d % m == 0, f"dim {d} not divisible by m {m}"
    dsub = d // m
    sub = data.reshape(n, m, dsub).transpose(1, 0, 2)   # (M, N, dsub)
    keys = jax.random.split(key, m)
    cents = jax.vmap(lambda k, x: _kmeans_subspace(k, x, ksub, iters))(keys, sub)
    return PQCodebook(centroids=cents, dim=d)


@jax.jit
def encode_pq(codebook: PQCodebook, data) -> jax.Array:
    """Encode vectors to PQ codes. Returns (N, M) uint8 (int32 when ksub>256)."""
    m, ksub, dsub = codebook.centroids.shape
    n = data.shape[0]
    sub = data.reshape(n, m, dsub)

    def enc(x_m, c_m):   # (N, dsub), (ksub, dsub)
        d = (jnp.sum(x_m * x_m, 1, keepdims=True)
             - 2.0 * jnp.matmul(x_m, c_m.T, precision=_EXACT)
             + jnp.sum(c_m * c_m, 1)[None, :])
        return jnp.argmin(d, axis=1)

    codes = jax.vmap(enc, in_axes=(1, 0), out_axes=1)(sub, codebook.centroids)
    dt = jnp.uint8 if ksub <= 256 else jnp.int32
    return codes.astype(dt)


@jax.jit
def distance_table(codebook: PQCodebook, query) -> jax.Array:
    """Per-query ADC lookup table: (M, ksub) squared-L2 partial distances."""
    m, ksub, dsub = codebook.centroids.shape
    q = query.reshape(m, 1, dsub)
    diff = q - codebook.centroids            # (M, ksub, dsub)
    return jnp.sum(diff * diff, axis=-1)     # (M, ksub)


def adc_lookup(codes, table) -> jax.Array:
    """Reference ADC distance: sum_m table[m, codes[:, m]]. codes (N, M)."""
    idx = codes.astype(jnp.int32)                         # (N, M)
    cols = jnp.arange(table.shape[0])[None, :]            # (1, M)
    return jnp.sum(table[cols, idx], axis=1)


def decode_pq(codebook: PQCodebook, codes) -> jax.Array:
    """Reconstruct approximate vectors from codes (for tests)."""
    m, ksub, dsub = codebook.centroids.shape
    idx = codes.astype(jnp.int32)                         # (N, M)
    parts = codebook.centroids[jnp.arange(m)[None, :], idx]   # (N, M, dsub)
    return parts.reshape(codes.shape[0], m * dsub)
