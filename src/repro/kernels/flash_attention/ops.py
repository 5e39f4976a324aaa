"""Jitted public wrapper for the flash-attention kernel."""
from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention.kernel import flash_attention


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention_op(q, k, v, *, causal=True, window=None,
                       block_q=128, block_k=128, interpret=None):
    return flash_attention(q, k, v, causal=causal, window=window,
                           block_q=block_q, block_k=block_k,
                           interpret=interpret)
