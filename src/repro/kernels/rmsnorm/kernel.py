"""Pallas TPU RMSNorm kernel (row-blocked).

Simple but ubiquitous: every block and every exit head begins with an
RMSNorm; on TPU it is memory-bound, so the kernel keeps the row resident in
VMEM and does the reduce + scale in one pass (fp32 accumulation).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps) *
                  (1.0 + s_ref[...].astype(jnp.float32))).astype(o_ref.dtype)


def rmsnorm(x, scale, *, eps: float = 1e-6, block_rows: int = 256,
            interpret: bool | None = None):
    """x: (N, d); scale: (d,) -> (N, d)."""
    N, d = x.shape
    block_rows = min(block_rows, N)
    Np = -(-N // block_rows) * block_rows
    xp = jnp.pad(x, ((0, Np - N), (0, 0))) if Np != N else x
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(Np // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Np, d), x.dtype),
        interpret=resolve_interpret(interpret),
    )(xp, scale)
    return out[:N]
