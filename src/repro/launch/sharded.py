"""``device-sharded``: the batched serving executor across a device mesh.

The batched :class:`~repro.serving.runtime.device.DeviceExecutor` runs one
jitted stage fn per (stage, bucket) shape on a single device.  This module
lifts exactly that engine onto a ``(dp, tp)`` mesh from
:func:`repro.launch.mesh.make_serving_mesh`:

* **Data parallelism** — batch rows are sharded over the ``dp`` axis.  The
  bucket set is scaled to *dp-divisible* global sizes (each base bucket
  ``b`` becomes a global batch of ``b * dp`` rows, ``b`` per device), so
  padded batches always split evenly and steady state still never
  recompiles: the per-device shapes are the same small pre-compiled set.
* **Tensor parallelism** — stage weights are placed with the decode
  (TP-only) layout from :func:`repro.launch.shardings.param_shardings`, so
  a stage's matmuls shard over the ``tp`` axis without per-dispatch weight
  gathers; ``tp=1`` degenerates to full replication.
* **Hidden-state caching** — per-request state keeps the DeviceExecutor
  contract (registered at admission, persisted across stage dispatches,
  evicted on retire) but stays *device-resident*: a committed row is a
  slice of the sharded stage output, never copied back to host between
  stages.  ``cache_stats()`` exposes live/peak/evicted counts.

Everything above the executor contract — :class:`StageBatcher` formation,
admission control, pipelined dispatch, traffic scenarios — runs unchanged;
:func:`sharded_time_model` re-prices the ``BatchTimeModel`` so feasibility
checks and §II-B deadline adjustments see the dp-wide bucket set.

Registered as ``register_executor("device-sharded")`` from
:mod:`repro.launch.serve` — *outside* the serving package, like the
``traffic`` source: the registry extension-point proof at executor scale.

On a 1x1 mesh every result is bit-for-bit identical to
``device-batched`` (tests/test_sharded.py pins this parity), so
single-device CI exercises the full sharded path.  A mesh larger than the
host raises (:func:`repro.launch.mesh.make_serving_mesh`).
"""
from __future__ import annotations

import jax

from repro.launch.shardings import batch_shardings, param_shardings
from repro.serving.batch.batcher import BatchTimeModel
from repro.serving.batch.stage_fns import BatchedStageFns
from repro.serving.runtime.device import DeviceExecutor

#: executor_args keys understood by the ``device-sharded`` factory —
#: the single source of truth ``ServeSpec._validate_sharded_args`` reads
#: to reject anything else (typo guard)
SHARDED_ARGS = ("dp", "tp", "mesh", "collective")


def dp_buckets(buckets, dp: int) -> tuple:
    """Global (dp-divisible) batch buckets for a dp-way row-sharded engine.

    Each base bucket ``b`` holds ``b`` rows *per device*, so the global
    batch the engine forms and prices is ``b * dp`` rows.  ``dp=1`` is the
    identity — the single-device bucket discipline unchanged."""
    if int(dp) < 1:
        raise ValueError(f"dp must be >= 1, got {dp}")
    return tuple(int(b) * int(dp) for b in sorted(buckets))


def sharded_time_model(tm: BatchTimeModel, dp: int, *,
                       collective: float = 0.0) -> BatchTimeModel:
    """Price dp-way row-sharded dispatches.

    A global batch padded to bucket ``b * dp`` puts ``b`` rows on each
    device, so its WCET is the *single-device* WCET of bucket ``b`` plus a
    per-dispatch ``collective`` term (cross-replica sync / logit gather)
    when ``dp > 1``.  ``dp=1`` returns ``tm`` itself, keeping single-device
    pricing (and golden parity) exactly intact.
    """
    dp = int(dp)
    if dp == 1:
        return tm
    rows = tuple(tuple(float(t) + float(collective) for t in row)
                 for row in tm.times)
    return BatchTimeModel(buckets=dp_buckets(tm.buckets, dp), times=rows)


def _constrain_rows(tree, mesh, dp_axes):
    """Constrain every leaf's leading (batch-row) axis onto the dp axes
    (divisibility-guarded — :func:`batch_shardings` falls back to
    replication for non-dividing leaves, so any pytree lowers)."""
    sh = batch_shardings(mesh, tree, dp_axes)
    return jax.tree.map(jax.lax.with_sharding_constraint, tree, sh)


class ShardedStageFns(BatchedStageFns):
    """``BatchedStageFns`` whose jitted stage fns carry mesh sharding
    constraints: inputs and hidden outputs row-sharded over ``dp``, weight
    layout (tp) inherited from the committed params.

    The bucket set is the dp-divisible global set (:func:`dp_buckets`), so
    ``pad_batch`` always produces row counts that split evenly over the dp
    axis; per-device shapes stay the base pre-compiled buckets."""

    def __init__(self, cfg, buckets, mesh):
        self.mesh = mesh
        self.dp_axis, self.tp_axis = mesh.axis_names
        self.dp = int(mesh.shape[self.dp_axis])
        super().__init__(cfg, dp_buckets(buckets, self.dp))

    def fn(self, stage: int):
        if stage not in self._fns:
            from repro.models import stage_forward
            dp_axes = (self.dp_axis,)

            def f(params, h, _s=stage):
                h = _constrain_rows(h, self.mesh, dp_axes)
                h_out, logits, conf = stage_forward(self.cfg, params, _s, h,
                                                    mode="train")
                h_out = _constrain_rows(h_out, self.mesh, dp_axes)
                return h_out, logits, conf
            self._fns[stage] = jax.jit(f)
        return self._fns[stage]


class ShardedDeviceExecutor(DeviceExecutor):
    """:class:`DeviceExecutor` over a mesh — same contract (async XLA
    dispatch, single in-flight batch, per-request hidden-state cache),
    params committed once with the TP weight layout."""

    def __init__(self, stage_fns, params, time_model, mesh):
        params = jax.device_put(params,
                                param_shardings(mesh, params, layout="tp"))
        super().__init__(stage_fns, params, time_model)
        self.mesh = mesh
        self.dp = int(mesh.shape[mesh.axis_names[0]])
        self.tp = int(mesh.shape[mesh.axis_names[1]])


def build_sharded_executor(args: dict, ctx):
    """Factory behind ``register_executor("device-sharded")``.

    ``args`` (all JSON-able; validated by ``ServeSpec.validate()``):

    * ``dp`` / ``tp`` — data- / tensor-parallel ways (default 1 / 1); a
      host with fewer than ``dp * tp`` devices raises.
    * ``mesh`` — optional ``[dp_axis, tp_axis]`` axis names (default
      ``["data", "model"]``); a ready ``jax.sharding.Mesh`` may instead be
      passed as the ``mesh`` *resource*, skipping construction.
    * ``collective`` — seconds added to every dispatch's WCET when
      ``dp > 1`` (cross-replica sync pricing; default 0).

    Refines ``ctx.time_model`` to the dp-scaled model so the batcher,
    admission controller and §II-B deadline adjustment all price the
    dp-wide bucket set.  Resources: ``cfg``, ``params``, optional
    ``stage_fns`` / ``mesh``.
    """
    from repro.launch.mesh import make_serving_mesh
    dp, tp = int(args.get("dp", 1)), int(args.get("tp", 1))
    mesh = ctx.resources.get("mesh")
    if mesh is None:
        axes = tuple(args.get("mesh") or ("data", "model"))
        mesh = make_serving_mesh(dp, tp, axes=axes)
    eff_dp = int(mesh.shape[mesh.axis_names[0]])
    params = ctx.resources["params"]
    stm = sharded_time_model(
        ctx.time_model, eff_dp, collective=float(args.get("collective", 0.0)))
    sfns = ctx.resources.get("stage_fns")
    if sfns is None:
        sfns = ShardedStageFns(ctx.resources["cfg"], ctx.time_model.buckets,
                               mesh)
    elif tuple(getattr(sfns, "buckets", ())) != stm.buckets:
        # a caller-supplied stage_fns must pad to the dp-scaled global
        # buckets the engine will form — catch the mismatch at build
        # time, not at the first over-bucket dispatch on a warm engine
        raise ValueError(
            f"stage_fns resource buckets "
            f"{tuple(getattr(sfns, 'buckets', ()))} do not match the "
            f"dp-scaled bucket set {stm.buckets} (dp={eff_dp}); build a "
            f"ShardedStageFns for this mesh or omit the resource")
    # everything downstream (StageBatcher, AdmissionController, deadline
    # adjustment, max_batch) prices the dp-wide global buckets
    ctx.time_model = stm
    ex = ShardedDeviceExecutor(sfns, params, stm, mesh)
    ex.warmup = lambda sample_input: sfns.warmup(ex.params, sample_input)
    return ex
