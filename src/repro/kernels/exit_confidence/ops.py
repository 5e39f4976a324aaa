"""Jitted public wrapper for the fused exit-confidence kernel."""
from __future__ import annotations

import functools

import jax

from repro.kernels.exit_confidence.kernel import exit_confidence


@functools.partial(jax.jit, static_argnames=("eps", "temperature",
                                             "block_rows", "block_v",
                                             "interpret"))
def exit_confidence_op(h, scale, w_out, *, eps=1e-6, temperature=1.0,
                       block_rows=8, block_v=512, interpret=None):
    return exit_confidence(h, scale, w_out, eps=eps, temperature=temperature,
                           block_rows=block_rows, block_v=block_v,
                           interpret=interpret)
