"""``device-kernel``: Pallas-kernel-backed stage fns behind the runtime core.

The ``device-batched`` executor runs one jitted ``stage_forward`` per
(stage, bucket) shape — trunk, exit head, full logits tensor, softmax
confidence, every intermediate materialized.  This module swaps the stage
*bodies* for the repo's Pallas kernels while keeping every layer above the
executor contract unchanged:

* **Fused exit epilogue** — each stage runs
  :func:`repro.models.stage_trunk` and then
  :func:`repro.models.exits.exit_stats_fused` (the
  ``repro.kernels.exit_confidence`` online-softmax kernel): RMSNorm →
  vocab matmul → (max, normalizer, argmax) in ONE dispatch.  The stage
  returns ``(h, pred, conf)`` — the vocab-sized logits row never leaves
  the kernel and confidence never round-trips to host between stages.
  With a single vocab block the online pass folds exactly once, so in
  interpret mode ``conf``/``pred`` are bit-for-bit equal to the unfused
  reference (:func:`repro.models.exits.exit_stats_unfused`).  Kernels are
  compiled on an accelerator and interpreted on the CPU
  (:func:`repro.kernels.resolve_interpret`).
* **Ragged decode batching** — ``mode="decode"`` dispatches
  :func:`repro.models.stage_decode_step` with
  ``ParallelCtx(decode_attn="kernel")``: attention reads each request's
  KV rows through ``repro.kernels.decode_attention``, whose *per-row*
  ``slot_pos`` masking makes co-batched requests at different positions
  exact (the legacy jnp route shares row 0's slot map across the batch).
  Per-request caches live in the executor's hidden-state cache, sliced
  out of the batched step on commit (:func:`repro.models.
  slice_decode_cache`) and concatenated back in on dispatch.
* **Length buckets** — ragged sequence lengths are padded up to a small
  pre-compiled set (``len_buckets``); the refined
  :class:`~repro.serving.batch.time_model.LengthBucketTimeModel` prices
  ``(stage, batch-bucket, len-bucket)`` WCETs, so the
  :class:`~repro.serving.batch.batcher.StageBatcher` co-batches only
  same-length-bucket runners and admission/§II-B see length-exact costs.
  In decode mode a request's KV slot count IS its length bucket — every
  member of a batch shares it, so cache concat is shape-stable.
* **Deep pipeline** — ``pipeline_depth - 1`` device windows may be
  enqueued at once (``max_inflight`` on the executor); the core stacks
  further windows while the device works, so the device never drains
  between windows waiting for host-side batch formation.

Registered as ``register_executor("device-kernel")`` from
:mod:`repro.launch.serve` — outside the serving package, like
``device-sharded``: the registry extension point at executor scale.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import resolve_interpret
from repro.models import (ParallelCtx, concat_decode_caches, exit_rows,
                          exit_stats_fused, slice_decode_cache,
                          stage_decode_step, stage_trunk)
from repro.serving.batch.batcher import BatchTimeModel, bucket_for
from repro.serving.batch.stage_fns import BatchedStageFns, pad_batch
from repro.serving.batch.time_model import LengthBucketTimeModel
from repro.serving.runtime.device import DeviceExecutor

#: executor_args keys understood by the ``device-kernel`` factory — the
#: single source of truth ``ServeSpec._validate_kernel_args`` reads to
#: reject anything else (typo guard)
KERNEL_ARGS = ("mode", "interpret", "block_rows", "block_v", "len_buckets",
               "len_marginal")


def length_bucketed_time_model(tm: BatchTimeModel, len_buckets, *,
                               len_marginal: float = 0.25) \
        -> LengthBucketTimeModel:
    """Refine a 2-D ``BatchTimeModel`` with a length-bucket axis.

    The existing ``(stage, bucket)`` table is taken as the *largest*
    length bucket's cost; shorter buckets scale down linearly with a
    ``len_marginal`` floor (cost = base * (lm + (1 - lm) * lb/max_lb)) —
    the analytic analog of :meth:`LengthBucketTimeModel.linear` applied
    to an already-priced model.  Base ``times`` stay exactly ``tm.times``
    (the max over length buckets), so every length-blind consumer prices
    identically before and after refinement.
    """
    if isinstance(tm, LengthBucketTimeModel):
        return tm
    lbs = tuple(sorted(int(b) for b in len_buckets))
    lm = float(len_marginal)
    mats = []
    for lb in lbs:
        frac = lm + (1.0 - lm) * lb / lbs[-1]
        mats.append(tuple(tuple(float(t) * frac for t in row)
                          for row in tm.times))
    return LengthBucketTimeModel(buckets=tm.buckets, times=tm.times,
                                 len_buckets=lbs, times3=tuple(mats))


class KernelStageFns(BatchedStageFns):
    """``BatchedStageFns`` whose jitted stage bodies end in the fused exit
    kernel: ``stage_trunk`` → :func:`exit_stats_fused`, returning
    ``(h, pred, conf)`` with no logits tensor.

    The exit head must be a 2-D shared projection (text/vlm/features);
    the audio codebook head has no fused kernel.
    """

    def __init__(self, cfg, buckets, *, interpret: bool | None = None,
                 block_rows: int = 8, block_v: int = 512):
        if cfg.modality == "audio_stub":
            raise ValueError("device-kernel: the audio codebook exit head "
                             "has no fused kernel; use device-batched")
        super().__init__(cfg, buckets)
        self.interpret = resolve_interpret(interpret)
        self.block_rows = int(block_rows)
        self.block_v = int(block_v)

    def fn(self, stage: int):
        if stage not in self._fns:
            def f(params, h, _s=stage):
                h_out = stage_trunk(self.cfg, params, _s, h, mode="train")
                rows = exit_rows(self.cfg, h_out)
                conf, pred, _m, _lse = exit_stats_fused(
                    rows, params["exits"][_s]["ln"],
                    params["exit_shared"]["w_out"],
                    eps=self.cfg.norm_eps, interpret=self.interpret,
                    block_rows=self.block_rows, block_v=self.block_v)
                return h_out, pred, conf
            self._fns[stage] = jax.jit(f)
        return self._fns[stage]

    def run(self, stage: int, params, pytrees):
        """Pad, dispatch one fused stage, return (h, pred, conf, mask)."""
        h, mask = pad_batch(pytrees, bucket_for(len(pytrees), self.buckets),
                            staging=self.staging)
        h_out, pred, conf = self.fn(stage)(params, h)
        return h_out, pred, conf, mask


class KernelDecodeStageFns:
    """Per-stage jitted :func:`stage_decode_step` + fused exit epilogue,
    with attention routed through the Pallas decode kernel.

    ``fn(stage)(params, h, st_cache, cur_pos)`` runs one batched stage of
    a decode step over the stage's (batched) cache and returns
    ``(h, new_st_cache, pred, conf)``.  Shapes are keyed by jit tracing:
    each ``(batch bucket, KV slot count)`` pair compiles once (a request's
    slot count is its length bucket, so the shape set is the pre-compiled
    ``buckets x len_buckets`` grid); :meth:`warmup` pre-compiles the
    sample's slot count across stages and batch buckets.
    """

    def __init__(self, cfg, buckets, ctx: ParallelCtx, *,
                 interpret: bool | None = None, block_rows: int = 8,
                 block_v: int = 512):
        if cfg.modality == "audio_stub":
            raise ValueError("device-kernel: the audio codebook exit head "
                             "has no fused kernel; use device-batched")
        self.cfg = cfg
        self.buckets = tuple(sorted(buckets))
        self.ctx = ctx
        self.interpret = resolve_interpret(interpret)
        self.block_rows = int(block_rows)
        self.block_v = int(block_v)
        self._fns = {}

    def fn(self, stage: int):
        if stage not in self._fns:
            def f(params, h, st_cache, cur_pos, _s=stage):
                h, new_cache = stage_decode_step(self.cfg, params, _s,
                                                 st_cache, h, cur_pos,
                                                 ctx=self.ctx)
                conf, pred, _m, _lse = exit_stats_fused(
                    h, params["exits"][_s]["ln"],
                    params["exit_shared"]["w_out"],
                    eps=self.cfg.norm_eps, interpret=self.interpret,
                    block_rows=self.block_rows, block_v=self.block_v)
                return h, new_cache, pred, conf
            self._fns[stage] = jax.jit(f)
        return self._fns[stage]

    def warmup(self, params, sample_state):
        """Compile every (stage, bucket) shape at the sample's slot count
        before the clock starts; other length buckets compile on their
        first dispatch (pre-warm with one sample per length bucket to
        avoid that)."""
        for b in self.buckets:
            h = jnp.concatenate([sample_state["h"]] * b, axis=0)
            cur = jnp.concatenate([sample_state["cur_pos"]] * b, axis=0)
            for s in range(self.cfg.num_stages):
                cache = concat_decode_caches([sample_state["cache"][s]] * b)
                out = self.fn(s)(params, h, cache, cur)
                jax.block_until_ready(out[0])
                h = out[0]


def ragged_decode_check(fns: KernelDecodeStageFns, params, positions,
                        slots: int, *, seed: int = 0) -> dict:
    """Co-batched decode at ragged cache positions vs each request alone.

    One single-request cache per entry of ``positions`` (all with
    ``slots`` KV slots, i.e. one length bucket) is warmed to that position
    with random tokens; then one decode step of every stage runs over the
    ragged batch, over each request alone, and over a batch of the same
    shape holding copies of one request (what bucket padding builds).

    ``same_shape_equal``: the ragged batch's row of each request is
    bit-for-bit the same-shape run of that request — no row reads another
    row's cache or positions.  Against the request alone, batch shape
    changes XLA's float rounding, so the check reports whether every
    ``pred`` agreed, the largest ``|h_batched - h_alone|`` (``h_err``)
    beside the largest ``|h_alone|`` (``h_scale``), and the largest
    ``conf`` and cache-row differences, for callers to hold to the
    tolerance of their dtype and backend.
    """
    from repro.models import init_decode_cache
    cfg = fns.cfg
    rng = np.random.default_rng(seed)

    def token():
        return jnp.array([int(rng.integers(cfg.vocab_size))], jnp.int32)

    def step(states):
        """One decode step of every stage over ``states`` as one batch:
        per stage ``(h, cache, pred, conf)``."""
        h = jnp.concatenate([st["h"] for st in states])
        cur = jnp.concatenate([st["cur_pos"] for st in states])
        outs = []
        for s in range(cfg.num_stages):
            cache = concat_decode_caches([st["cache"][s] for st in states])
            h, cache, pred, conf = fns.fn(s)(params, h, cache, cur)
            outs.append((h, cache, pred, conf))
        return outs

    states = []
    for pos in positions:
        cache = init_decode_cache(cfg, 1, slots)
        for p in range(pos):
            h = token()
            for s in range(cfg.num_stages):
                h, cache[s], _pred, _conf = fns.fn(s)(
                    params, h, cache[s], jnp.full((1,), p, jnp.int32))
        states.append({"h": token(), "cache": cache,
                       "cur_pos": jnp.full((1,), pos, jnp.int32)})
    batched = step(states)

    def rows(out, i):
        h, cache, pred, conf = out
        return [h[i:i + 1], pred[i:i + 1], conf[i:i + 1],
                *jax.tree.leaves(slice_decode_cache(cache, i))]

    def err(a, b):
        return float(np.max(np.abs(np.asarray(a, np.float32)
                                   - np.asarray(b, np.float32))))

    out = dict(same_shape_equal=True, pred_equal=True, h_err=0.0,
               h_scale=0.0, conf_err=0.0, cache_err=0.0,
               positions=list(positions))
    for i, st in enumerate(states):
        same = step([st] * len(states))
        alone = step([st])
        for s in range(cfg.num_stages):
            b_rows = rows(batched[s], i)
            out["same_shape_equal"] &= all(
                np.array_equal(np.asarray(a), np.asarray(b))
                for a, b in zip(b_rows, rows(same[s], 0)))
            h, pred, conf, *cache = rows(alone[s], 0)
            out["pred_equal"] &= int(pred[0]) == int(b_rows[1][0])
            out["h_err"] = max(out["h_err"], err(h, b_rows[0]))
            out["h_scale"] = max(out["h_scale"], err(h, 0))
            out["conf_err"] = max(out["conf_err"], err(conf, b_rows[2]))
            for a, b in zip(cache, b_rows[3:]):
                out["cache_err"] = max(out["cache_err"], err(a, b))
    return out


class KernelDeviceExecutor(DeviceExecutor):
    """:class:`DeviceExecutor` over kernel-backed stage fns.

    ``mode="classifier"`` keeps the inherited dispatch (per-request hidden
    pytrees through :class:`KernelStageFns`) and only re-reads ``commit``
    for the fused payload — ``pred`` arrives as an argmax vector, not a
    logits tensor.  ``mode="decode"`` dispatches
    :class:`KernelDecodeStageFns` over per-request decode state
    ``{"h": token/hidden row, "cache": per-stage cache list, "cur_pos"}``
    held in the hidden-state cache: dispatch concatenates the stage's
    cache rows across the batch (padding replicates the last member, whose
    slot count every co-runner shares — same length bucket), commit slices
    each request's row and cache back out, device-resident throughout.
    """

    def __init__(self, stage_fns, params, time_model, *,
                 mode: str = "classifier", max_inflight: int = 1):
        super().__init__(stage_fns, params, time_model,
                         max_inflight=max_inflight)
        self.mode = mode

    def wcet(self, stage: int, n: int = 1) -> float:
        return self.time_model.wcet(stage, n)

    # -- dispatch seams -------------------------------------------------
    def _dispatch_stage(self, stage: int, tasks: list):
        if self.mode != "decode":
            return super()._dispatch_stage(stage, tasks)
        states = [self.states[t.tid][1] for t in tasks]
        b = bucket_for(len(states), self.stage_fns.buckets)
        padded = states + [states[-1]] * (b - len(states))
        h = jnp.concatenate([s["h"] for s in padded], axis=0)
        cache = concat_decode_caches([s["cache"][stage] for s in padded])
        cur = jnp.concatenate([s["cur_pos"] for s in padded], axis=0)
        return self.stage_fns.fn(stage)(self.params, h, cache, cur)

    def _finalize(self, payload):
        if self.mode != "decode":
            h_out, pred, conf = payload
            return h_out, np.asarray(pred), np.asarray(conf)
        h_out, new_cache, pred, conf = payload
        return h_out, new_cache, np.asarray(pred), np.asarray(conf)

    def commit(self, task, k: int) -> float:
        stage, done = self._done
        w0 = time.perf_counter()
        st = self.states[task.tid]
        if self.mode != "decode":
            h_out, pred, conf = done
            st[1] = jax.tree.map(lambda x: x[k:k + 1], h_out)
        else:
            h_out, new_cache, pred, conf = done
            st[1]["h"] = h_out[k:k + 1]
            st[1]["cache"][stage] = slice_decode_cache(new_cache, k)
        c = float(conf[k])
        st[2] = (int(pred[k]), c)
        self.stage_host_time[stage] += time.perf_counter() - w0
        return c


def build_kernel_executor(args: dict, ctx):
    """Factory behind ``register_executor("device-kernel")``.

    ``args`` (all JSON-able; validated by ``ServeSpec.validate()``):

    * ``mode`` — ``"classifier"`` (default: fused-exit ``stage_trunk``
      over hidden pytrees) or ``"decode"`` (ragged decode batching over
      per-request KV caches through the Pallas decode kernel).
    * ``interpret`` — optional; ``True`` restates interpret mode on a CPU
      backend and raises on an accelerator, where kernels always compile
      (default: decided by :func:`repro.kernels.resolve_interpret`).
    * ``block_rows`` / ``block_v`` — fused exit kernel tile sizes.
    * ``len_buckets`` — optional ascending lengths; refines
      ``ctx.time_model`` via :func:`length_bucketed_time_model` so the
      batcher/admission/§II-B price ``(stage, batch-bucket, len-bucket)``.
    * ``len_marginal`` — length-scaling floor of that refinement.

    ``max_inflight`` is ``spec.pipeline_depth - 1``: the depth-minus-one
    windows the core may stack on the device.  Resources: ``cfg``,
    ``params``, optional ``stage_fns`` / ``mesh``.
    """
    cfg, params = ctx.resources["cfg"], ctx.resources["params"]
    mode = args.get("mode", "classifier")
    kw = dict(interpret=args.get("interpret"),
              block_rows=int(args.get("block_rows", 8)),
              block_v=int(args.get("block_v", 512)))
    lbs = args.get("len_buckets")
    if lbs:
        # everything downstream (StageBatcher, admission, §II-B) prices
        # the (stage, batch-bucket, len-bucket) table
        ctx.time_model = length_bucketed_time_model(
            ctx.time_model, lbs,
            len_marginal=float(args.get("len_marginal", 0.25)))
    tm = ctx.time_model
    max_inflight = max(1, int(ctx.spec.pipeline_depth) - 1)
    sfns = ctx.resources.get("stage_fns")
    if mode == "decode":
        if sfns is None:
            from repro.launch.mesh import make_serving_mesh
            mesh = ctx.resources.get("mesh") or make_serving_mesh(1, 1)
            pctx = ParallelCtx(mesh=mesh, decode_attn="kernel")
            sfns = KernelDecodeStageFns(cfg, tm.buckets, pctx, **kw)
        ex = KernelDeviceExecutor(sfns, params, tm, mode="decode",
                                  max_inflight=max_inflight)
        ex.warmup = lambda sample_state: sfns.warmup(params, sample_state)
    else:
        if sfns is None:
            sfns = KernelStageFns(cfg, tm.buckets, **kw)
        ex = KernelDeviceExecutor(sfns, params, tm,
                                  max_inflight=max_inflight)
        ex.warmup = lambda sample_input: sfns.warmup(params, sample_input)
    return ex
