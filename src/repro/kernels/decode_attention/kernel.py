"""Pallas TPU flash-decode kernel: one query token vs. a (ring) KV cache.

Grid: (batch * kv_heads, n_kv_blocks) with the kv axis innermost.  One
program holds the G query heads that share a KV head (GQA), so each KV
block is read once per KV head; running (m, l, acc) scratch implements the
online softmax.  Slot validity uses the cache's slot_pos array (ring caches
store non-monotonic positions), matching repro.models.flash_decode's
per-shard partial — this kernel is the *intra-shard* compute of the
distributed flash-decode: on a real pod each model-parallel shard runs this
kernel over its local cache slice and the (m, l) combine crosses shards via
psum/pmax.

TPU layout: ``cur_pos`` is a scalar-prefetch operand (SMEM), ``slot_pos``
rides as (B, 1, S) so its block's last two dims are (1, block_k), and the
m/l scratch is (G, 1) — every block's trailing dims are full or
(8, 128)-aligned, as Mosaic requires.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _decode_kernel(cp_ref, q_ref, k_ref, v_ref, sp_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, softmax_scale, window,
                   kv_heads, n_kv_blocks):
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                # (G, dh)
    k = k_ref[0].astype(jnp.float32)                # (bk, dh)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * softmax_scale                           # (G, bk)
    slot_pos = sp_ref[0]                            # (1, bk)
    cur = cp_ref[pl.program_id(0) // kv_heads]
    valid = (slot_pos >= 0) & (slot_pos <= cur)
    if window is not None:
        valid &= cur - slot_pos < window
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_ref[...]                             # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    v = v_ref[0].astype(jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha + \
        jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == n_kv_blocks - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, ...] = (acc_ref[...] / l).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, slot_pos, cur_pos, *, window=None,
                     softmax_scale=None, block_k: int = 256,
                     interpret: bool | None = None):
    """q: (B, H, dh), heads KV-major; caches: (B, KV, S, dh);
    slot_pos: (B, S); cur_pos: (B,).

    Returns (B, H, dh).  ``interpret``: see
    :func:`repro.kernels.resolve_interpret`.
    """
    B, H, dh = q.shape
    KV, S = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    scale = softmax_scale if softmax_scale is not None else dh ** -0.5
    block_k = min(block_k, S)
    Sp = -(-S // block_k) * block_k
    if Sp != S:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
        slot_pos = jnp.pad(slot_pos, ((0, 0), (0, Sp - S)),
                           constant_values=-1)
    nk = Sp // block_k

    kernel = functools.partial(_decode_kernel, softmax_scale=scale,
                               window=window, kv_heads=KV, n_kv_blocks=nk)
    kv_spec = pl.BlockSpec((1, block_k, dh), lambda bk, ik, cp: (bk, ik, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * KV, nk),
        in_specs=[
            pl.BlockSpec((1, G, dh), lambda bk, ik, cp: (bk, 0, 0)),
            kv_spec,
            kv_spec,
            pl.BlockSpec((1, 1, block_k),
                         lambda bk, ik, cp, KV=KV: (bk // KV, 0, ik)),
        ],
        out_specs=pl.BlockSpec((1, G, dh), lambda bk, ik, cp: (bk, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),        # running max m
            pltpu.VMEM((G, 1), jnp.float32),        # normalizer l
            pltpu.VMEM((G, dh), jnp.float32),       # fp32 accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * KV, G, dh), q.dtype),
        interpret=resolve_interpret(interpret),
    )(cur_pos.astype(jnp.int32),
      q.reshape(B * KV, G, dh),
      k_cache.reshape(B * KV, Sp, dh),
      v_cache.reshape(B * KV, Sp, dh),
      slot_pos.astype(jnp.int32).reshape(B, 1, Sp))
    return out.reshape(B, H, dh)
