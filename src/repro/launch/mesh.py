"""Production meshes (assignment-fixed shapes).

Defined as FUNCTIONS so importing this module never touches jax device
state.  The dry-run entrypoint (repro.launch.dryrun) sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import;
everything else (smoke tests, benchmarks) sees the real single CPU device.
"""
from __future__ import annotations

import jax


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 4):
    """Small mesh for in-test lowering (8 host devices)."""
    return _mesh((n_data, n_model), ("data", "model"))


def make_serving_mesh(dp: int = 1, tp: int = 1, *, axes=("data", "model")):
    """A ``(dp, tp)`` serving mesh: data-parallel batch rows over
    ``axes[0]``, tensor-parallel weights within a stage over ``axes[1]``.

    The ``device-sharded`` executor (registered by :mod:`repro.launch.serve`,
    built in :mod:`repro.launch.sharded`) runs its stage fns over this mesh.
    A mesh the host cannot give raises: the mesh always holds exactly
    ``dp * tp`` devices, so a sharded result is never a silent single-device
    one.  One-device callers ask for ``dp=1, tp=1``.
    """
    dp, tp = int(dp), int(tp)
    if dp < 1 or tp < 1:
        raise ValueError(f"dp and tp must be >= 1, got dp={dp} tp={tp}")
    n = len(jax.devices())
    if dp * tp > n:
        raise ValueError(f"serving mesh needs dp*tp={dp * tp} devices, "
                         f"host has {n}")
    return _mesh((dp, tp), tuple(axes))
