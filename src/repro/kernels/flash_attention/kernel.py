"""Pallas TPU flash-attention (prefill) kernel.

Blocked online-softmax attention with GQA head mapping done in the BlockSpec
index maps (query head h reads KV head h // group_size — no repeated KV in
HBM).  Causal and sliding-window masking; fp32 accumulation in VMEM scratch.

Grid: (batch * q_heads, n_q_blocks, n_kv_blocks), kv dimension innermost
("arbitrary") so the (m, l, acc) running state lives in scratch across kv
steps.  Fully-masked kv blocks are skipped via @pl.when — causal prefill
does ~half the work, sliding-window layers touch only blocks inside the
window (the TPU analog of the paper's GPU-side layer-size tuning: block
shapes are chosen so q/k tiles and the fp32 accumulator fit VMEM with
128-aligned MXU dims).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 softmax_scale, block_q, block_k, seq_len, causal, window,
                 n_kv_blocks):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    # static block-level skip: block fully above the diagonal / out of window
    def live_block():
        q = q_ref[0].astype(jnp.float32)                  # (bq, dh)
        k = k_ref[0].astype(jnp.float32)                  # (bk, dh)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * softmax_scale
        mask = k_pos < seq_len
        if causal:
            mask &= q_pos >= k_pos
        if window is not None:
            mask &= q_pos - k_pos < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        v = v_ref[0].astype(jnp.float32)                  # (bk, dh)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + \
            jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if causal or window is not None:
        first_q = iq * block_q
        last_q = first_q + block_q - 1
        first_k = ik * block_k
        cond = jnp.asarray(True)
        if causal:
            cond &= first_k <= last_q
        if window is not None:
            last_k = first_k + block_k - 1
            cond &= first_q - last_k < window
        pl.when(cond)(live_block)
    else:
        live_block()

    @pl.when(ik == n_kv_blocks - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, ...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    softmax_scale=None, block_q: int = 128,
                    block_k: int = 128, interpret: bool | None = None):
    """q: (B, H, S, dh); k, v: (B, KV, S, dh).  Returns (B, H, S, dh).

    H must be a multiple of KV (GQA).  S is padded internally to block size.
    """
    B, H, S, dh = q.shape
    KV = k.shape[1]
    assert H % KV == 0
    G = H // KV
    scale = softmax_scale if softmax_scale is not None else dh ** -0.5
    block_q = min(block_q, max(S, 8))
    block_k = min(block_k, max(S, 8))
    Sp = -(-S // max(block_q, block_k)) * max(block_q, block_k)
    if Sp != S:
        pad = ((0, 0), (0, 0), (0, Sp - S), (0, 0))
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    nq = Sp // block_q
    nk = Sp // block_k

    kernel = functools.partial(
        _attn_kernel, softmax_scale=scale, block_q=block_q, block_k=block_k,
        seq_len=S, causal=causal, window=window, n_kv_blocks=nk)

    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, dh),
                         lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, dh),
                         lambda bh, iq, ik, G=G, KV=KV:
                         ((bh // (G * KV)) * KV + (bh % (G * KV)) // G,
                          ik, 0)),
            pl.BlockSpec((1, block_k, dh),
                         lambda bh, iq, ik, G=G, KV=KV:
                         ((bh // (G * KV)) * KV + (bh % (G * KV)) // G,
                          ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dh),
                               lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sp, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),           # running max m
            pltpu.VMEM((block_q,), jnp.float32),           # normalizer l
            pltpu.VMEM((block_q, dh), jnp.float32),        # fp32 accumulator
        ],
        interpret=resolve_interpret(interpret),
    )(q.reshape(B * H, Sp, dh),
      k.reshape(B * KV, Sp, dh),
      v.reshape(B * KV, Sp, dh))
    return out.reshape(B, H, Sp, dh)[:, :, :S]
