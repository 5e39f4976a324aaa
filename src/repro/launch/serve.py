"""Anytime-decoding serving launcher: imprecise computation per TOKEN.

The paper's stage shedding applied to autoregressive decode: each token
runs stage 1 (mandatory); deeper stages execute only while the exit
confidence is below a target — a deadline-free confidence-driven variant
of RTDeepIoT's depth assignment.

The decode loop runs through the public serving API: each *token* is one
imprecise-computation request served by ``repro.serving.Service`` from a
declarative ``ServeSpec``, with four launch-registered components proving
the registry's extension points (no core module touched):

* policy ``conf-target`` — assign full depth, stop deepening the moment
  the measured exit confidence reaches the target;
* executor ``decode`` — jitted per-depth decode steps, each depth after
  the first resuming from the one before; with
  ``speculate=True`` (``--pipeline``) the next-deeper step is dispatched
  (XLA async) before the current depth's confidence readback, so the
  host's read-and-decide overlaps device compute — a speculatively
  dispatched depth is discarded when the target was already met;
* source ``token-loop`` — a closed loop of one token at a time: retiring
  token *t* commits the chosen depth's slots (the executor writes them
  into its caches in place), samples token *t+1* and issues it as the
  next request;
* executor ``device-sharded`` (:mod:`repro.launch.sharded`) — the batched
  classifier engine with its stage fns sharded over a ``(dp, tp)`` mesh
  from :func:`repro.launch.mesh.make_serving_mesh`; a mesh larger than
  the host raises, so one-device hosts run ``dp=1, tp=1``;
* executor ``device-kernel`` (:mod:`repro.launch.kernel`) — Pallas-backed
  stage fns: fused exit-confidence epilogue (no logits round-trip) and
  ragged decode batching over per-request KV caches through the decode
  kernel, with ``(stage, batch-bucket, len-bucket)`` WCET pricing; the
  kernels compile on a TPU and run in interpret mode only on a CPU
  backend (:func:`repro.kernels.resolve_interpret`).

``--dry-run`` validates the spec against the registry and prints it as
JSON without touching the model (the CI examples-smoke job).

The registered config is served at its own width (qwen3-4b: 36 layers,
d_model 2560, bf16 — about 8.8 GB of params, one TPU v5e chip);
``--reduced`` serves the CPU-sized variant:

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --tokens 24
  PYTHONPATH=src JAX_PLATFORMS=cpu python -m repro.launch.serve --reduced
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import math
import time

import numpy as np

from repro.serving.obs.hostspans import span
from repro.serving.registry import (register_executor, register_policy,
                                    register_source)
from repro.serving.service import ServeSpec, Service


# ---------------------------------------------------------------------------
# launch-registered serving components (registry extension points in action)
# ---------------------------------------------------------------------------

@register_policy("conf-target")
def _make_conf_target(args, ctx):
    """Deadline-free depth governor: run deeper only while the measured
    exit confidence is below ``target`` (BatchPolicy imported lazily so
    the registration itself stays import-light)."""
    from repro.serving.batch.policy import BatchPolicy

    class _ConfTarget(BatchPolicy):
        name = "conf-target"

        def __init__(self, target):
            super().__init__()
            self.target = target

        def on_arrival(self, active, task, now):
            task.assigned_depth = task.clamp_depth(task.num_stages)

        def on_stage_done(self, active, task, now):
            c = task.last_confidence
            if c is not None and c >= self.target:
                task.assigned_depth = task.executed      # stop deepening

        def next_batch(self, active, now):
            r = self._runnable(active, now)
            if not r:
                return None
            t = min(r, key=lambda x: x.tid)
            return t.executed, [t]

    return _ConfTarget(float(args.get("target", 0.7)))


@functools.cache
def _slot_writer():
    """``write_decode_slots`` jitted once per process with the stage
    caches donated: one program per depth written, each in place."""
    import jax

    from repro.models import write_decode_slots
    return jax.jit(write_decode_slots, donate_argnums=0)


class DecodeExecutor:
    """Jitted per-depth decode steps behind the runtime Executor contract.

    Depth *d*'s "stage" takes the token to depth d+1.  Depth 1 runs from
    the embedding; depth d+1 resumes from depth d: its step gets depth
    d's ``h_final`` as a :class:`~repro.models.DecodeFrom` and runs only
    stage d+1 and its exit.  Every step reads the committed stage caches,
    each passed as a :class:`~repro.models.CommittedCache`, and returns
    only the token's slots of the stages it runs; the exits and slots of
    the depths before are carried over on the host, so each output holds
    one exit per stage run and the slots of stages 1..d.  With
    ``speculate`` the next-deeper step is dispatched asynchronously before
    the current depth's confidence readback blocks, and a discarded
    speculative depth leaves the committed caches as they were.  Each
    step's confidence starts its copy to the host as soon as the step is
    dispatched, and ``commit`` averages it on the host: the readback
    enqueues no device work, so it never waits behind a speculative step.

    The executor owns ``cache``, the committed stage caches: the retired
    token's slots (:meth:`commit_slots`) are written into them in place,
    with the caches donated, before the next token's depth-1 step or the
    next read of ``cache``.  So the arrays it was given, and any that
    ``cache`` returned, are consumed by the next write.  Assigning
    ``cache`` installs a committed state and drops a write not yet made.
    """

    def __init__(self, steps, params, cache, tok, *, speculate=False):
        # jax only enters the process on the non-dry-run path, which has
        # already imported it to jit `steps` — bind it once here instead of
        # re-importing in the per-token hot methods
        import jax
        import jax.numpy as jnp

        from repro.models import CommittedCache, DecodeFrom
        self._jax, self._jnp = jax, jnp
        self._from, self._committed = DecodeFrom, CommittedCache
        self.steps = steps
        self.params = params
        self.cache = cache
        self.tok = tok
        self.speculate = speculate
        self.total_busy = 0.0
        self.speculated = 0          # deeper steps dispatched speculatively
        self.spec_hits = 0           # ... that the schedule then consumed
        self.chained = 0             # steps resumed from the depth before
        self.slot_writes = 0         # stage slots written into `cache`
        self.readbacks = 0           # confidences read back in `commit`
        self.readbacks_overlapped = 0  # ... with a deeper step in flight
        self._running = None
        self._spec = None            # (token, stage, out, slots)
        self._done = None
        self._done_at = None         # (token, stage) of `_done`
        self.chosen = None           # (out, slots) of the last commit

    @property
    def cache(self):
        """The committed stage caches, the last retired token's slots
        written in."""
        self._write_slots()
        return self._cache

    @cache.setter
    def cache(self, value):
        # placed like the caches the write returns (no copy), so that one
        # program per depth serves every write and step
        jax = self._jax
        self._install(jax.tree.map(
            lambda x: x if x.committed else jax.device_put(x, x.sharding),
            list(value)))

    def _install(self, stages):
        self._cache, self._slots = stages, None
        self._view = [self._committed(c) for c in stages]

    def commit_slots(self, slots):
        """Commit a retired token's slots, ``chosen``'s second half: the
        stages its depth ran, None for the deeper ones, which keep their
        committed caches as they are."""
        self._slots = slots

    def _write_slots(self):
        slots, self._slots = self._slots, None
        if slots is None:
            return
        d = sum(s is not None for s in slots)
        with span("repro.executor.write_slots", stages=d):
            written = _slot_writer()(self._cache[:d], slots[:d])
        self._install([*written, *self._cache[d:]])
        self.slot_writes += d

    # -- Executor contract ---------------------------------------------
    @property
    def busy(self):
        return self._running is not None

    def wcet(self, stage, n):
        return 0.0

    def _dispatch(self, stage, pos, prev=None):
        """Enqueue depth ``stage + 1`` on the committed caches: from the
        token, or resumed from ``prev``, depth ``stage``'s ``(out,
        slots)``, whose exits and slots come first in the result."""
        if prev is None:
            out, slots = self.steps[stage](self.params, self._view,
                                           self.tok, pos)
            out.confidences[-1].copy_to_host_async()
            return out, slots
        head, slots = prev
        out, new = self.steps[stage](
            self.params, self._view, self._from(head.h_final, stage), pos)
        out.confidences[-1].copy_to_host_async()
        self.chained += 1
        out = dataclasses.replace(
            out, logits=[*head.logits, *out.logits],
            confidences=[*head.confidences, *out.confidences])
        return out, [s if n is None else n for s, n in zip(slots, new)]

    def submit(self, stage, tasks, now):
        jnp = self._jnp
        task = tasks[0]
        hit = self._spec is not None and self._spec[:2] == (task.sample, stage)
        resume = (not hit and stage > 0
                  and self._done_at == (task.sample, stage - 1))
        ahead = self.speculate and stage + 1 < len(self.steps)
        with span("repro.executor.launch", depth=stage + 1, hit=hit,
                  chained=resume + ahead):
            if stage == 0:
                self._write_slots()
            pos = jnp.full((self.tok.shape[0],), task.sample, jnp.int32)
            if hit:
                out, slots = self._spec[2:]
                self.spec_hits += 1
            else:
                out, slots = self._dispatch(
                    stage, pos, self._done if resume else None)
            self._spec = None
            if ahead:
                self._spec = (task.sample, stage + 1,
                              *self._dispatch(stage + 1, pos, (out, slots)))
                self.speculated += 1
        self._running = (stage, tasks, out, slots, now)

    def finish_time(self):
        return None if self.busy else math.inf

    def complete(self, clock):
        stage, tasks, out, slots, t0 = self._running
        self._running = None
        with span("repro.executor.wait"):
            self._jax.block_until_ready(out.logits[-1])
        self.total_busy += clock.now() - t0
        self._done = (out, slots)
        self._done_at = (tasks[0].sample, stage)
        return stage, tasks

    def commit(self, task, k):
        self.chosen = self._done
        conf = self._done[0].confidences
        overlapped = self._spec is not None
        self.readbacks += 1
        self.readbacks_overlapped += overlapped
        # host-side mean of the copy `submit` started: nothing here
        # dispatches to the device
        with span("repro.executor.readback", depth=len(conf),
                  overlapped=overlapped):
            return float(np.asarray(conf[-1]).mean(dtype=np.float32))

    def running_tasks(self):
        return list(self._running[1]) if self._running is not None else []


@register_executor("decode")
def _make_decode(args, ctx):
    r = ctx.resources
    return DecodeExecutor(r["steps"], r["params"], r["cache"], r["tok"],
                          speculate=bool(args.get("speculate", False)))


@register_executor("device-sharded")
def _make_device_sharded(args, ctx):
    """``device-batched`` across a ``(dp, tp)`` mesh: batch rows sharded
    over ``dp``, stage weights over ``tp``, per-request hidden state cached
    on device between stage dispatches.  args:
    ``{"dp": ..., "tp": ..., "mesh": [dp_axis, tp_axis],
    "collective": ...}`` (see :func:`repro.launch.sharded.
    build_sharded_executor`); resources: ``cfg``, ``params``, optionally
    ``stage_fns`` / ``mesh``."""
    from repro.launch.sharded import build_sharded_executor
    return build_sharded_executor(args, ctx)


@register_executor("zoo-device")
def _make_zoo_device(args, ctx):
    """Multi-model ``device-batched``: one accelerator, per-model batched
    stage fns, windows routed on the batch's model id (the
    :class:`repro.serving.zoo.device.ZooDeviceExecutor`).  resources:
    ``zoo_models`` = ``{model: {"cfg": ..., "params": ...,
    "stage_fns": optional}}``; spec: ``ServeSpec.models``."""
    from repro.serving.zoo.device import build_zoo_device_executor
    return build_zoo_device_executor(args, ctx)


@register_executor("device-kernel")
def _make_device_kernel(args, ctx):
    """``device-batched`` with Pallas-kernel stage bodies: fused
    exit-confidence epilogue (``mode="classifier"``) or ragged decode
    batching over the per-request KV caches (``mode="decode"``), with
    optional ``(stage, batch-bucket, len-bucket)`` WCET refinement.  args:
    ``{"mode": ..., "interpret": ..., "block_rows": ..., "block_v": ...,
    "len_buckets": [...], "len_marginal": ...}`` (see :func:`repro.launch.
    kernel.build_kernel_executor`); resources: ``cfg``, ``params``,
    optionally ``stage_fns`` / ``mesh``."""
    from repro.launch.kernel import build_kernel_executor
    return build_kernel_executor(args, ctx)


class TokenLoopSource:
    """Closed loop of one token request at a time: retiring token *t*
    commits the chosen depth's slots to the executor, lets the ``advance``
    callback sample token *t+1*, and issues it as the next request."""

    def __init__(self, n_tokens, n_stages, executor, advance):
        self.n_tokens = n_tokens
        self.n_stages = n_stages
        self.executor = executor
        self.advance = advance
        self._next = 0
        self._ready = n_tokens > 0
        self._issue_time = 0.0

    def has_pending(self):
        return self._ready

    def next_time(self):
        return self._issue_time if self._ready else math.inf

    def pop(self, now):
        from repro.core.task import Task
        self._ready = False
        return Task(arrival=now, deadline=math.inf,
                    stage_times=(0.0,) * self.n_stages, mandatory=1,
                    sample=self._next)

    def on_retire(self, task, now):
        with span("repro.source.advance"):
            out, slots = self.executor.chosen
            self.executor.commit_slots(slots)
            self.executor.tok = self.advance(task, out)
        self._next += 1
        if self._next < self.n_tokens:
            self._ready = True
            self._issue_time = now


@register_source("token-loop")
def _make_token_loop(args, ctx):
    return TokenLoopSource(int(args["n_tokens"]), int(args["n_stages"]),
                           ctx.executor, ctx.resources["advance"])


# ---------------------------------------------------------------------------
# launcher
# ---------------------------------------------------------------------------

def build_spec(args, n_stages: int) -> ServeSpec:
    """The launcher's serving configuration, declared once."""
    return ServeSpec(
        policy="conf-target", policy_args={"target": args.conf_target},
        executor="decode", executor_args={"speculate": bool(args.pipeline)},
        clock="wall", source="token-loop",
        source_args={"n_tokens": args.tokens, "n_stages": n_stages},
        batching={"mode": "none", "stage_times": [0.0] * n_stages})


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--reduced", action="store_true",
                    help="serve the config's reduced (CPU-sized, float32) "
                         "variant instead of its registered width")
    ap.add_argument("--tokens", type=int, default=24)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0,
                    help="PRNG seed of the random params")
    ap.add_argument("--conf-target", type=float, default=0.7)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--pipeline", action="store_true",
                    help="speculatively dispatch the next-deeper step "
                         "before reading the current confidence (async "
                         "host/device overlap)")
    ap.add_argument("--dry-run", action="store_true",
                    help="validate + print the ServeSpec (registry check) "
                         "without touching the model")
    args = ap.parse_args(argv)

    from repro.configs import get_config
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.modality == "features":
        raise SystemExit("classifier serving lives in examples/serve_anytime.py")
    n_stages = len(cfg.stage_boundaries())
    spec = build_spec(args, n_stages)
    if args.dry_run:
        spec.validate()
        print(spec.to_json(indent=1))
        print(f"DRY RUN OK: {args.arch} ({n_stages} stages, "
              f"{args.tokens} tokens) resolves through the registry")
        return spec

    import jax
    import jax.numpy as jnp

    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import (CommittedCache, DecodeFrom, decode_step,
                              init_decode_cache, init_params)
    from repro.training import checkpoint

    enable_compile_cache()
    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    if args.ckpt:
        params, _ = checkpoint.load(args.ckpt, params)
    params_gb = sum(x.nbytes for x in jax.tree.leaves(params)) / 1e9
    B = args.batch
    # committed to the device, as the executor holds it: a jitted program
    # is compiled apart for committed and uncommitted arguments
    cache = jax.device_put(init_decode_cache(cfg, B, slots=args.tokens + 1),
                           jax.devices()[0])

    # jit one step per depth (the per-stage dispatch units of the engine)
    steps = [jax.jit(lambda p, c, t, pos, _d=d: decode_step(
        cfg, p, c, t, pos, upto_stage=_d)) for d in range(1, n_stages + 1)]

    tok = (jnp.zeros((B, cfg.num_codebooks), jnp.int32)
           if cfg.modality == "audio_stub" else jnp.zeros((B,), jnp.int32))
    # compile what the executor runs before the clock starts: depth 1 from
    # the token, each deeper depth resumed from the one before, all on the
    # committed caches (first call = compile + one step; the persistent
    # cache turns a rerun's compile into a load)
    t0 = time.perf_counter()
    pos0 = jnp.zeros((B,), jnp.int32)
    view = [CommittedCache(c) for c in cache]
    out, _ = steps[0](params, view, tok, pos0)
    for d in range(1, n_stages):
        out, _ = steps[d](params, view, DecodeFrom(out.h_final, d), pos0)
    jax.block_until_ready(out.logits[-1])
    del out
    compile_s = time.perf_counter() - t0
    print(f"{cfg.name}: d_model={cfg.d_model} layers={cfg.num_layers} "
          f"vocab={cfg.vocab_size} dtype={cfg.dtype} params={params_gb:.3f}GB "
          f"first-call (compile) {compile_s:.2f}s on "
          f"{jax.devices()[0].device_kind}")
    depth_hist = np.zeros(n_stages, np.int64)

    def advance(task, out):
        """Token transition: record depth, print, sample the next token."""
        d = task.executed
        depth_hist[d - 1] += 1
        print(f"token {task.sample:3d}: depth={d} "
              f"conf={task.last_confidence:.3f}")
        nxt = jnp.argmax(out.logits[-1], -1).astype(jnp.int32)
        if cfg.modality != "audio_stub":
            return nxt
        return jnp.broadcast_to(nxt[..., :1] if nxt.ndim > 1 else nxt[:, None],
                                (B, cfg.num_codebooks))

    svc = Service.from_spec(spec, steps=steps, params=params, cache=cache,
                            tok=tok, advance=advance)
    t0 = time.perf_counter()
    met = svc.run()
    dt = time.perf_counter() - t0
    svc.close()
    ex = svc.executor
    if args.pipeline:
        print(f"pipelined decode: {ex.speculated - ex.spec_hits} speculative "
              f"deeper steps dispatched and discarded "
              f"({ex.spec_hits} consumed); {ex.readbacks_overlapped} of "
              f"{ex.readbacks} confidence readbacks overlapped a deeper step")
    print(f"\n{args.tokens} tokens in {dt:.3f}s "
          f"({dt / max(1, args.tokens):.4f}s/token wall); depth histogram "
          f"{depth_hist.tolist()} (mean {met.mean_depth:.2f} "
          f"of {n_stages}) — stages shed: "
          f"{1 - depth_hist @ np.arange(1, n_stages+1) / (args.tokens * n_stages):.0%} compute saved")
    return met


if __name__ == "__main__":
    main()
