"""Distributed flash-decode: one-token attention over a sequence-sharded cache.

The KV cache for decode shapes is sharded along its *sequence* dimension over
``ctx.seq_axes`` (``('model',)`` for decode_32k; ``('data','model')`` for
long_500k where batch=1 cannot use the data axis).  Each shard computes a
partial attention (unnormalized accumulator + running max m + normalizer l)
over its local slots, then shards combine with the standard flash logsumexp
merge via pmax/psum — no shard ever materializes the full cache.

This is what makes a half-megatoken cache fit per device; GSPMD's automatic
alternative is an all-gather of the whole cache (measured in §Perf).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


NEG_INF = -1e30


def _partial_attend(q, k, v, slot_pos, cur_pos, window, softmax_scale):
    """Local partial attention.

    q: (B,KV,G,hd); k,v: (B,S_loc,KV,hd); slot_pos: (B,S_loc); cur_pos: (B,).
    Returns (acc, m, l): acc (B,KV,G,hd) unnormalized, m/l (B,KV,G).
    """
    scores = jnp.einsum("bkgh,bskh->bkgs", q, k).astype(jnp.float32)
    scores = scores * softmax_scale
    valid = (slot_pos <= cur_pos[:, None]) & (slot_pos >= 0)
    if window is not None:
        valid &= cur_pos[:, None] - slot_pos < window
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    m = jnp.max(scores, axis=-1)                           # (B,KV,G)
    p = jnp.exp(scores - m[..., None])
    p = jnp.where(valid[:, None, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bkgs,bskh->bkgh", p.astype(v.dtype), v).astype(jnp.float32)
    return acc, m, l


def flash_decode(q, k_cache, v_cache, slot_pos, cur_pos, *, window,
                 softmax_scale, ctx, shard_kv_heads: bool = True,
                 use_kernel: bool = False):
    """q: (B,KV,G,hd); caches: (B,S,KV,hd); slot_pos: (B,S); cur_pos: (B,).

    ``use_kernel`` routes the unsharded (ctx is None) case through the
    Pallas decode kernel (repro.kernels.decode_attention) — the kernel is
    exactly this function's intra-shard partial, so the two paths agree up
    to reduction order."""
    del shard_kv_heads  # KV heads stay replicated in this scheme
    if ctx is None:
        if use_kernel:
            from repro.kernels.decode_attention.kernel import decode_attention
            B, KV, G, hd = q.shape
            out = decode_attention(q.reshape(B, KV * G, hd),
                                   k_cache.transpose(0, 2, 1, 3),
                                   v_cache.transpose(0, 2, 1, 3),
                                   slot_pos, cur_pos, window=window,
                                   softmax_scale=softmax_scale)
            return out.reshape(B, KV, G, hd)
        acc, m, l = _partial_attend(q, k_cache, v_cache, slot_pos, cur_pos,
                                    window, softmax_scale)
        return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)

    seq = ctx.seq_axes
    dp = tuple(a for a in ctx.dp if a not in seq)
    bspec = dp if dp else None

    def body(q_, k_, v_, sp_, cp_):
        acc, m, l = _partial_attend(q_, k_, v_, sp_, cp_, window, softmax_scale)
        m_g = jax.lax.pmax(m, seq)
        scale = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * scale, seq)
        acc_g = jax.lax.psum(acc * scale[..., None], seq)
        return (acc_g / jnp.maximum(l_g, 1e-30)[..., None]).astype(q_.dtype)

    return jax.shard_map(
        body,
        mesh=ctx.mesh,
        in_specs=(P(bspec, None, None, None),
                  P(bspec, seq, None, None),
                  P(bspec, seq, None, None),
                  P(bspec, seq),
                  P(bspec)),
        out_specs=P(bspec, None, None, None),
    )(q, k_cache, v_cache, slot_pos, cur_pos)
