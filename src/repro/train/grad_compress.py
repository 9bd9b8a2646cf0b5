"""Int8 error-feedback gradient all-reduce (distributed-optimization trick).

Quantize local gradients to int8 (blockwise absmax), psum the int8 payload
(as int32 accumulators to avoid overflow), dequantize, and keep the
quantization residual as local error feedback added to the next step's
gradient. Cuts DP all-reduce bytes 4× (f32) / 2× (bf16) at equal asymptotic
convergence (error feedback makes the bias vanish).

Expressed with shard_map over the data axis so the collective payload is
explicit and shows up in the dry-run's collective-bytes accounting.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

QBLOCK = 256


def _quantize(g):
    flat = g.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    nb = -(-n // QBLOCK)
    flat = jnp.pad(flat, (0, nb * QBLOCK - n)).reshape(nb, QBLOCK)
    scale = jnp.max(jnp.abs(flat), axis=1, keepdims=True) / 127.0
    q = jnp.round(flat / jnp.maximum(scale, 1e-12)).astype(jnp.int8)
    return q, scale, n


def _dequantize(q, scale, n, shape):
    flat = (q.astype(jnp.float32) * scale).reshape(-1)[:n]
    return flat.reshape(shape)


def compressed_psum_grads(grads, error_fb, axis_name: str):
    """All-reduce a gradient pytree in int8 with error feedback.

    Must run inside shard_map/pmap over `axis_name`. Returns
    (mean_grads, new_error_fb)."""
    n_dev = jax.lax.axis_size(axis_name)

    def one(g, e):
        g_fb = g.astype(jnp.float32) + e
        flat = g_fb.reshape(-1)
        n = flat.shape[0]
        nb = -(-n // QBLOCK)
        blocks = jnp.pad(flat, (0, nb * QBLOCK - n)).reshape(nb, QBLOCK)
        # shared per-block scale across the axis -> int8 sum is exact
        local_max = jnp.max(jnp.abs(blocks), axis=1, keepdims=True)
        scale = jax.lax.pmax(local_max, axis_name) / 127.0
        q = jnp.round(blocks / jnp.maximum(scale, 1e-12)).astype(jnp.int8)
        new_e = (blocks - q.astype(jnp.float32) * scale).reshape(-1)[:n] \
            .reshape(g.shape)
        summed = jax.lax.psum(q.astype(jnp.int32), axis_name)
        deq = _dequantize(summed, scale, n, g.shape) / n_dev
        return deq.astype(g.dtype), new_e

    flat_g, td = jax.tree_util.tree_flatten(grads)
    flat_e = td.flatten_up_to(error_fb)
    out = [one(g, e) for g, e in zip(flat_g, flat_e)]
    return td.unflatten([o[0] for o in out]), td.unflatten([o[1] for o in out])


def init_error_feedback(grads_like):
    return jax.tree_util.tree_map(
        lambda g: jnp.zeros(g.shape, jnp.float32), grads_like)
