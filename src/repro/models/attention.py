"""GQA attention: RoPE, optional QKV bias, sliding window, blockwise
(flash-style) prefill for long sequences, KV-cache decode.

Layouts: activations (B, S, D); q (B, S, Hq, Dh); k/v (B, T, Hkv, Dh).
GQA is expressed with an explicit group dim in einsums (no repeat_kv
materialization) so tensor-parallel sharding over heads stays clean.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models.common import ModelConfig, cdtype, dense_init, pdtype, rotary_embed

NEG_INF = -1e30


class AttnParams(NamedTuple):
    wq: jax.Array      # (D, Hq*Dh)
    wk: jax.Array      # (D, Hkv*Dh)
    wv: jax.Array      # (D, Hkv*Dh)
    wo: jax.Array      # (Hq*Dh, D)
    bq: Optional[jax.Array]
    bk: Optional[jax.Array]
    bv: Optional[jax.Array]


def init_attn(key, cfg: ModelConfig) -> AttnParams:
    d, hq, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.head_dim
    dt = pdtype(cfg)
    ks = jax.random.split(key, 4)
    bias = (lambda n: jnp.zeros((n,), dt)) if cfg.qkv_bias else (lambda n: None)
    return AttnParams(
        wq=dense_init(ks[0], (d, hq * dh), dt),
        wk=dense_init(ks[1], (d, hkv * dh), dt),
        wv=dense_init(ks[2], (d, hkv * dh), dt),
        wo=dense_init(ks[3], (hq * dh, d), dt),
        bq=bias(hq * dh), bk=bias(hkv * dh), bv=bias(hkv * dh))


def _project_qkv(p: AttnParams, x, cfg: ModelConfig, positions):
    b, s, _ = x.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv, cfg.head_dim
    dt = cdtype(cfg)
    q = x @ p.wq.astype(dt)
    k = x @ p.wk.astype(dt)
    v = x @ p.wv.astype(dt)
    if p.bq is not None:
        q, k, v = q + p.bq.astype(dt), k + p.bk.astype(dt), v + p.bv.astype(dt)
    q = q.reshape(b, s, hq, dh)
    k = k.reshape(b, s, hkv, dh)
    v = v.reshape(b, s, hkv, dh)
    q = rotary_embed(q, positions, cfg.rope_theta)
    k = rotary_embed(k, positions, cfg.rope_theta)
    return q, k, v


def _gqa_scores(q, k, scale):
    """q: (B,S,Hkv,G,Dh), k: (B,T,Hkv,Dh) -> (B,Hkv,G,S,T)."""
    return jnp.einsum("bskgd,btkd->bkgst", q, k) * scale


def _gqa_out(probs, v):
    """probs: (B,Hkv,G,S,T), v: (B,T,Hkv,Dh) -> (B,S,Hkv,G,Dh)."""
    return jnp.einsum("bkgst,btkd->bskgd", probs, v)


def _causal_window_mask(s, t, q_offset, window):
    """(S, T) additive mask: causal + optional sliding window."""
    qpos = jnp.arange(s)[:, None] + q_offset
    kpos = jnp.arange(t)[None, :]
    ok = kpos <= qpos
    if window > 0:
        ok &= kpos > qpos - window
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def full_attention(q, k, v, cfg: ModelConfig, q_offset=0):
    """Materialized-scores attention (short sequences)."""
    b, s, hq, dh = q.shape
    t = k.shape[1]
    g = hq // cfg.n_kv
    qg = q.reshape(b, s, cfg.n_kv, g, dh)
    scores = _gqa_scores(qg, k, 1.0 / jnp.sqrt(dh).astype(jnp.float32))
    scores = scores.astype(jnp.float32) + _causal_window_mask(
        s, t, q_offset, cfg.window)[None, None, None]
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = _gqa_out(probs, v)
    return out.reshape(b, s, hq, dh)


def blockwise_attention(q, k, v, cfg: ModelConfig, q_offset=0):
    """Flash-style two-level blocking in pure JAX: the (S × T) score matrix
    is never materialized; a scan over KV chunks carries running
    (max, sum, acc) per query chunk. Causally-dead KV chunks still execute
    (shape-static) but are fully masked.
    """
    b, s, hq, dh = q.shape
    t = k.shape[1]
    g = hq // cfg.n_kv
    cq, ckv = min(cfg.attn_chunk_q, s), min(cfg.attn_chunk_kv, t)
    assert s % cq == 0 and t % ckv == 0
    nq, nkv = s // cq, t // ckv
    scale = 1.0 / jnp.sqrt(dh).astype(jnp.float32)

    qg = q.reshape(b, nq, cq, cfg.n_kv, g, dh)
    kc = k.reshape(b, nkv, ckv, cfg.n_kv, dh)
    vc = v.reshape(b, nkv, ckv, cfg.n_kv, dh)

    def q_block(qi, q_blk):
        def kv_step(carry, inp):
            m, l, acc = carry
            ki, k_blk, v_blk = inp
            sc = jnp.einsum("bskgd,btkd->bkgst", q_blk, k_blk) * scale
            sc = sc.astype(jnp.float32)
            qpos = qi * cq + jnp.arange(cq)[:, None] + q_offset
            kpos = ki * ckv + jnp.arange(ckv)[None, :]
            ok = kpos <= qpos
            if cfg.window > 0:
                ok &= kpos > qpos - cfg.window
            sc = sc + jnp.where(ok, 0.0, NEG_INF)[None, None, None]
            m_new = jnp.maximum(m, sc.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(sc - m_new[..., None])
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bkgst,btkd->bkgsd", p.astype(q.dtype), v_blk).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, cfg.n_kv, g, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, cfg.n_kv, g, cq), jnp.float32)
        a0 = jnp.zeros((b, cfg.n_kv, g, cq, dh), jnp.float32)
        ks_idx = jnp.arange(nkv)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (ks_idx, kc.transpose(1, 0, 2, 3, 4), vc.transpose(1, 0, 2, 3, 4)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.transpose(0, 3, 1, 2, 4).astype(q.dtype)  # (b,cq,kv,g,dh)

    outs = jax.lax.map(lambda args: q_block(*args),
                       (jnp.arange(nq), qg.transpose(1, 0, 2, 3, 4, 5)))
    # outs: (nq, b, cq, kv, g, dh) -> (b, s, hq, dh)
    return outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, s, hq, dh)


class KVCache(NamedTuple):
    k: jax.Array        # (B, T, Hkv, Dh) — T = window size when windowed
    v: jax.Array
    pos: jax.Array      # () int32 — absolute next position


def init_kv_cache(cfg: ModelConfig, batch: int, max_t: int, dtype) -> KVCache:
    t = min(max_t, cfg.window) if cfg.window > 0 else max_t
    shape = (batch, t, cfg.n_kv, cfg.head_dim)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   pos=jnp.zeros((), jnp.int32))


def _sp_decode_core(cfg: ModelConfig, q, k_new, v_new, cache: KVCache):
    """Split-K (flash-decoding) path: KV sequence sharded over 'model',
    partial-softmax psum combine — replaces XLA's default KV all-gather
    (the dominant memory/collective term of long-cache decode)."""
    from repro.models import common
    from repro.serve import sp_attention as SP
    from jax.sharding import PartitionSpec as P

    mesh = common._ACT_CTX["mesh"]
    dp = common._ACT_CTX["dp"] or ()
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    b_ax = dp if (dp and cache.k.shape[0] % dp_size == 0) else None

    def body(q_l, kn, vn, kc, vc, pos):
        kc, vc = SP.sp_cache_update(kc, vc, kn, vn, pos, "model")
        out = SP.sp_decode_attention_local(q_l, kc, vc, pos, cfg.n_kv,
                                           "model")
        return out, kc, vc

    rep = P(b_ax, None, None, None)
    seq = P(b_ax, "model", None, None)
    from jax import shard_map
    f = shard_map(body, mesh=mesh,
                  in_specs=(rep, rep, rep, seq, seq, P()),
                  out_specs=(rep, seq, seq), check_vma=False)
    out, k, v = f(q, k_new, v_new, cache.k, cache.v, cache.pos)
    return out, KVCache(k=k, v=v, pos=cache.pos + 1)


def decode_attention(p: AttnParams, x, cache: KVCache, cfg: ModelConfig):
    """One-token decode. x: (B, 1, D). Returns (out (B,1,D), new cache).

    Sliding-window caches are ring buffers indexed by pos % window.
    """
    b = x.shape[0]
    hq, hkv, dh = cfg.n_heads, cfg.n_kv, cfg.head_dim
    g = hq // hkv
    pos = cache.pos
    positions = jnp.full((b, 1), pos, jnp.int32)
    q, k_new, v_new = _project_qkv(p, x, cfg, positions)

    if cfg.sp_decode and cfg.window == 0:
        from repro.models import common
        mesh = common._ACT_CTX["mesh"]
        if mesh is not None and "model" in mesh.axis_names \
                and cache.k.shape[1] % mesh.shape["model"] == 0:
            out, new_cache = _sp_decode_core(cfg, q, k_new, v_new, cache)
            out = out.reshape(b, 1, hq * dh) @ p.wo.astype(x.dtype)
            return out, new_cache

    t_cache = cache.k.shape[1]
    slot = pos % t_cache if cfg.window > 0 else pos
    k = jax.lax.dynamic_update_slice(cache.k, k_new, (0, slot, 0, 0))
    v = jax.lax.dynamic_update_slice(cache.v, v_new, (0, slot, 0, 0))

    # validity of cache slots (absolute position per slot)
    slots = jnp.arange(t_cache)
    if cfg.window > 0:
        # ring: slot holds absolute position p where p % t_cache == slot and
        # p <= pos and p > pos - t_cache
        abs_pos = pos - ((pos - slots) % t_cache)
        valid = (abs_pos >= 0) & (abs_pos <= pos) & (abs_pos > pos - cfg.window)
    else:
        valid = slots <= pos

    qg = q.reshape(b, 1, hkv, g, dh)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k) / jnp.sqrt(dh)
    scores = scores.astype(jnp.float32) + jnp.where(valid, 0.0, NEG_INF)[
        None, None, None, None, :]
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v).reshape(b, 1, hq * dh)
    out = out @ p.wo.astype(x.dtype)
    return out, KVCache(k=k, v=v, pos=pos + 1)


def attention_forward(p: AttnParams, x, cfg: ModelConfig, positions=None,
                      cache: Optional[KVCache] = None):
    """Training / prefill forward. x: (B, S, D). If cache given, fills it."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    q, k, v = _project_qkv(p, x, cfg, positions)
    if s > cfg.attn_chunk_threshold:
        out = blockwise_attention(q, k, v, cfg)
    else:
        out = full_attention(q, k, v, cfg)
    out = out.reshape(b, s, cfg.n_heads * cfg.head_dim) @ p.wo.astype(x.dtype)
    if cache is not None:
        t_cache = cache.k.shape[1]
        if cfg.window > 0 and s >= t_cache:
            # keep the last `window` positions, ring-aligned
            tail_k, tail_v = k[:, -t_cache:], v[:, -t_cache:]
            shift = s % t_cache
            k_c = jnp.roll(tail_k, shift=shift, axis=1)
            v_c = jnp.roll(tail_v, shift=shift, axis=1)
        else:
            k_c = jnp.zeros_like(cache.k).at[:, :s].set(k[:, :cache.k.shape[1]])
            v_c = jnp.zeros_like(cache.v).at[:, :s].set(v[:, :cache.v.shape[1]])
        cache = KVCache(k=k_c, v=v_c, pos=jnp.asarray(s, jnp.int32))
    return out, cache
