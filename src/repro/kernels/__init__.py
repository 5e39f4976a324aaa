"""Pallas TPU kernels for the performance-critical compute layers.

Each kernel subpackage ships kernel.py (pl.pallas_call + BlockSpec VMEM
tiling), ops.py (jitted wrapper), and ref.py (pure-jnp oracle used by the
per-kernel shape/dtype-sweep allclose tests).

Whether a kernel runs in Pallas interpret mode is decided in one place,
:func:`resolve_interpret`: interpret exactly when JAX's default backend is
the CPU (tests, CPU rehearsals), compiled Mosaic kernels everywhere else.
"""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None = None) -> bool:
    """The ``interpret`` flag every ``pallas_call`` of this package uses.

    ``None`` (the default everywhere) interprets on a CPU backend and
    compiles otherwise.  ``True`` may only restate that on the CPU: asking
    for interpret mode on an accelerator raises.  ``False`` lowers the
    compiled kernel even from a CPU process — what an ahead-of-time
    compile for a described (not attached) TPU needs.
    """
    on_cpu = jax.default_backend() == "cpu"
    if interpret is None:
        return on_cpu
    if interpret and not on_cpu:
        raise ValueError(
            f"Pallas interpret mode requested on the "
            f"{jax.default_backend()!r} backend; kernels are compiled there")
    return bool(interpret)
