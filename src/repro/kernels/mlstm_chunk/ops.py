"""Jitted public wrapper for the mLSTM chunk kernel."""
from __future__ import annotations

import functools

import jax

from repro.kernels.mlstm_chunk.kernel import mlstm_chunk


@functools.partial(jax.jit, static_argnames=("interpret",))
def mlstm_chunk_op(q, k, v, i_pre, f_pre, C0, n0, m0, *, interpret=None):
    return mlstm_chunk(q, k, v, i_pre, f_pre, C0, n0, m0,
                       interpret=interpret)
