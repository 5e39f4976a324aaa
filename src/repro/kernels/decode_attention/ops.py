"""Jitted public wrapper for the decode-attention kernel."""
from __future__ import annotations

import functools

import jax

from repro.kernels.decode_attention.kernel import decode_attention


@functools.partial(jax.jit, static_argnames=("window", "block_k",
                                             "interpret"))
def decode_attention_op(q, k_cache, v_cache, slot_pos, cur_pos, *,
                        window=None, block_k=256, interpret=None):
    return decode_attention(q, k_cache, v_cache, slot_pos, cur_pos,
                            window=window, block_k=block_k,
                            interpret=interpret)
