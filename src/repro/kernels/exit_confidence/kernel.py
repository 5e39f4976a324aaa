"""Pallas TPU fused exit-head kernel — the paper's per-stage hotspot.

At the end of every stage RTDeepIoT evaluates a thin classifier and needs
only (argmax class, max-softmax confidence) back on the host — not the full
probability vector over up to 262k classes.  This kernel fuses:

    RMSNorm(h) @ W_out  ->  online (max, logsumexp, argmax) over vocab blocks

so the V-sized logits row is never materialized in HBM: each grid step loads
one (d, block_v) weight tile into VMEM, computes a (rows, block_v) logit
tile on the MXU, and folds it into running (m, lse-accumulator, argmax)
scratch.  Output per row: [confidence, argmax, max_logit, lse].

Grid: (n_row_blocks, n_vocab_blocks), vocab innermost.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _exit_conf_kernel(h_ref, scale_ref, w_ref, o_ref, m_ref, l_ref, a_ref,
                      *, eps, block_v, vocab, temperature, n_v_blocks):
    iv = pl.program_id(1)

    @pl.when(iv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        a_ref[...] = jnp.zeros_like(a_ref)

    h = h_ref[...].astype(jnp.float32)                   # (rows, d)
    # fused RMSNorm (recomputed per vocab block; O(rows*d) — negligible next
    # to the rows*d*block_v matmul)
    var = jnp.mean(jnp.square(h), axis=-1, keepdims=True)
    hn = h * jax.lax.rsqrt(var + eps) * (1.0 + scale_ref[...].astype(jnp.float32))
    w = w_ref[...].astype(jnp.float32)                   # (d, bv)
    logits = jax.lax.dot_general(hn, w, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    logits = logits / temperature
    vpos = iv * block_v + jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, 1)
    logits = jnp.where(vpos < vocab, logits, NEG_INF)

    blk_max = jnp.max(logits, axis=1)
    blk_arg = iv * block_v + jnp.argmax(logits, axis=1).astype(jnp.int32)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, blk_max)
    l_ref[...] = l_ref[...] * jnp.exp(m_prev - m_new) + \
        jnp.sum(jnp.exp(logits - m_new[:, None]), axis=1)
    a_ref[...] = jnp.where(blk_max > m_prev, blk_arg, a_ref[...])
    m_ref[...] = m_new

    @pl.when(iv == n_v_blocks - 1)
    def _finish():
        m = m_ref[...]
        l = jnp.maximum(l_ref[...], 1e-30)
        conf = 1.0 / l                                  # exp(m - (m + log l))
        o_ref[...] = jnp.stack(
            [conf, a_ref[...].astype(jnp.float32), m, m + jnp.log(l)],
            axis=1).astype(o_ref.dtype)


def exit_confidence(h, scale, w_out, *, eps: float = 1e-6,
                    temperature: float = 1.0, block_rows: int = 8,
                    block_v: int = 512, interpret: bool | None = None):
    """h: (N, d) hidden rows; scale: (d,) RMSNorm scale; w_out: (d, V).

    Returns (conf (N,), pred (N,) int32, max_logit (N,), lse (N,)).
    """
    N, d = h.shape
    V = w_out.shape[1]
    block_rows = min(block_rows, N)
    block_v = min(block_v, V)
    Np = -(-N // block_rows) * block_rows
    Vp = -(-V // block_v) * block_v
    if Np != N:
        h = jnp.pad(h, ((0, Np - N), (0, 0)))
    if Vp != V:
        w_out = jnp.pad(w_out, ((0, 0), (0, Vp - V)))
    nr, nv = Np // block_rows, Vp // block_v

    kernel = functools.partial(_exit_conf_kernel, eps=eps, block_v=block_v,
                               vocab=V, temperature=temperature,
                               n_v_blocks=nv)
    out = pl.pallas_call(
        kernel,
        grid=(nr, nv),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda ir, iv: (ir, 0)),
            pl.BlockSpec((d,), lambda ir, iv: (0,)),
            pl.BlockSpec((d, block_v), lambda ir, iv: (0, iv)),
        ],
        out_specs=pl.BlockSpec((block_rows, 4), lambda ir, iv: (ir, 0)),
        out_shape=jax.ShapeDtypeStruct((Np, 4), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((block_rows,), jnp.float32),   # running max
            pltpu.VMEM((block_rows,), jnp.float32),   # sum exp(l - m)
            pltpu.VMEM((block_rows,), jnp.int32),     # running argmax
        ],
        interpret=resolve_interpret(interpret),
    )(h, scale, w_out)
    out = out[:N]
    return out[:, 0], out[:, 1].astype(jnp.int32), out[:, 2], out[:, 3]
