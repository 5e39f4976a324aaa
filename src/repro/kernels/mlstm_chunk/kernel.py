"""Pallas TPU kernel for one chunk of the chunk-parallel mLSTM.

The xLSTM paper ships a CUDA kernel for the mLSTM recurrence; the TPU-native
formulation (repro.models.xlstm.mlstm_chunked) turns each chunk into masked
MXU matmuls with per-(t,s) exponential decay weights.  This kernel fuses the
whole intra-chunk computation for one (batch, head) tile:

    scores   = q @ k^T                      (MXU)
    decay    = exp(u_s - g_t) causal mask   (VPU)
    h_num    = (scores*decay) @ v + exp(m0-g_t) * (q @ C0)
    nq       = rowsum(scores*decay) + exp(m0-g_t) * (q @ n0)
    h        = h_num / max(|nq|, exp(-m_t))
    C1,n1,m1 = decayed state + sum_s exp(u_s-g_L) k_s v_s^T

keeping q/k/v tiles, the L×L decay matrix, and the (dh, dh) state resident
in VMEM.  Grid: (batch*heads,) — one program per head-chunk; the outer scan
over chunks stays in XLA (the carry is the (C, n, m) state).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _mlstm_chunk_kernel(q_ref, k_ref, v_ref, i_ref, f_ref, c0_ref, n0_ref,
                        m0_ref, h_ref, c1_ref, n1_ref, m1_ref, *, L, dh):
    q = q_ref[0].astype(jnp.float32)                  # (L, dh)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    i_pre = i_ref[0].astype(jnp.float32)              # (L,)
    f_pre = f_ref[0].astype(jnp.float32)
    C0 = c0_ref[0].astype(jnp.float32)                # (dh, dh)
    n0 = n0_ref[0].astype(jnp.float32)                # (dh,)
    m0 = m0_ref[0]                                    # (1,) fp32

    lf = jax.nn.log_sigmoid(f_pre)
    b = jnp.cumsum(lf)                                # (L,)
    u = i_pre - b
    g = jnp.maximum(m0[0], jax.lax.cummax(u, axis=0))
    m = b + g

    scores = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    tpos = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    spos = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    dmat = jnp.where(tpos >= spos, jnp.exp(u[None, :] - g[:, None]), 0.0)
    w = scores * dmat
    inter = jnp.exp(m0[0] - g)                        # (L,)
    h_num = jax.lax.dot_general(w, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    h_num += inter[:, None] * jax.lax.dot_general(
        q, C0, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    nq = jnp.sum(w, axis=1) + inter * (q @ n0)
    denom = jnp.maximum(jnp.abs(nq), jnp.exp(-m))
    h_ref[0, ...] = (h_num / denom[:, None]).astype(h_ref.dtype)

    gL, bL = g[L - 1], b[L - 1]
    wS = jnp.exp(u - gL)                              # (L,)
    C1 = jnp.exp(m0[0] - gL) * C0 + jax.lax.dot_general(
        k * wS[:, None], v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    n1 = jnp.exp(m0[0] - gL) * n0 + jnp.sum(k * wS[:, None], axis=0)
    c1_ref[0, ...] = C1
    n1_ref[0, ...] = n1
    m1_ref[0, ...] = jnp.array([bL + gL], jnp.float32)


def mlstm_chunk(q, k, v, i_pre, f_pre, C0, n0, m0, *, interpret: bool | None = None):
    """One chunk for all (batch, head) tiles.

    q,k,v: (B,H,L,dh); i_pre,f_pre: (B,H,L); C0: (B,H,dh,dh);
    n0: (B,H,dh); m0: (B,H).  Returns (h (B,H,L,dh), C1, n1, m1).
    """
    B, H, L, dh = q.shape
    BH = B * H
    kernel = functools.partial(_mlstm_chunk_kernel, L=L, dh=dh)
    out_shapes = (
        jax.ShapeDtypeStruct((BH, L, dh), q.dtype),
        jax.ShapeDtypeStruct((BH, dh, dh), jnp.float32),
        jax.ShapeDtypeStruct((BH, dh), jnp.float32),
        jax.ShapeDtypeStruct((BH, 1), jnp.float32),
    )
    specs3 = pl.BlockSpec((1, L, dh), lambda i: (i, 0, 0))
    specs2 = pl.BlockSpec((1, L), lambda i: (i, 0))
    h, C1, n1, m1 = pl.pallas_call(
        kernel,
        grid=(BH,),
        in_specs=[specs3, specs3, specs3, specs2, specs2,
                  pl.BlockSpec((1, dh, dh), lambda i: (i, 0, 0)),
                  pl.BlockSpec((1, dh), lambda i: (i, 0)),
                  pl.BlockSpec((1, 1), lambda i: (i, 0))],
        out_specs=(specs3,
                   pl.BlockSpec((1, dh, dh), lambda i: (i, 0, 0)),
                   pl.BlockSpec((1, dh), lambda i: (i, 0)),
                   pl.BlockSpec((1, 1), lambda i: (i, 0))),
        out_shape=out_shapes,
        interpret=resolve_interpret(interpret),
    )(q.reshape(BH, L, dh), k.reshape(BH, L, dh), v.reshape(BH, L, dh),
      i_pre.reshape(BH, L), f_pre.reshape(BH, L),
      C0.astype(jnp.float32).reshape(BH, dh, dh),
      n0.astype(jnp.float32).reshape(BH, dh),
      m0.astype(jnp.float32).reshape(BH, 1))
    return (h.reshape(B, H, L, dh), C1.reshape(B, H, dh, dh),
            n1.reshape(B, H, dh), m1.reshape(B, H))
