"""Jitted public wrapper for the RMSNorm kernel."""
from __future__ import annotations

import functools

import jax

from repro.kernels.rmsnorm.kernel import rmsnorm


@functools.partial(jax.jit, static_argnames=("eps", "block_rows",
                                             "interpret"))
def rmsnorm_op(x, scale, *, eps=1e-6, block_rows=256, interpret=None):
    return rmsnorm(x, scale, eps=eps, block_rows=block_rows,
                   interpret=interpret)
