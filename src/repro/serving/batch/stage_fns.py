"""Batched jitted stage functions: shape buckets, padding, masks.

A serving engine cannot afford a recompile per batch size, so batches are
padded up to a small set of pre-compiled **buckets** (default
{1, 2, 4, 8, 16}): one jitted ``stage_forward`` per stage, at most
``len(buckets)`` shapes each, all compiled in ``warmup`` before the
serving clock starts.

Padding replicates the last valid sample; batch rows are independent in
every supported architecture (attention/scan mix over the sequence axis,
norms over features), so valid rows of the padded run match per-sample
runs exactly and the returned boolean mask just marks which rows are real.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import stage_forward
from repro.serving.batch.batcher import (DEFAULT_BUCKETS, BatchTimeModel,
                                         bucket_for)
from repro.serving.obs.hostspans import span


class StagingBuffers:
    """Reused per-bucket host staging for batch formation.

    ``pad_batch`` alone re-stacks the per-sample pytrees into fresh
    device arrays on every dispatch — a per-dispatch allocation (and a
    jitted concatenate) on the hot path.  A ``StagingBuffers`` instance
    instead keeps one pinned numpy buffer per (bucket, leaf-struct): rows
    are copied in place, padding rows replicate the last valid row, and
    the same buffer object is handed to the jitted stage fn every time —
    steady-state batch formation allocates nothing.

    The returned masks are cached per (bucket, n) and must be treated as
    read-only (they are shared across dispatches), as must the batched
    leaves themselves: the jitted callee copies them to device before the
    next ``stage`` call can overwrite them, which is the same lifetime
    contract jit already imposes on donated host buffers.
    """

    def __init__(self):
        self._bufs = {}    # (bucket, treedef, leafsig) -> list[np.ndarray]
        self._masks = {}   # (bucket, n) -> np.ndarray(bool)

    def mask(self, bucket: int, n: int) -> np.ndarray:
        key = (bucket, n)
        m = self._masks.get(key)
        if m is None:
            m = np.arange(bucket) < n
            m.setflags(write=False)
            self._masks[key] = m
        return m

    def stage(self, pytrees, bucket: int):
        """In-place ``pad_batch``: returns ``(batched, mask)`` backed by
        the reused per-bucket buffers."""
        n = len(pytrees)
        if not 0 < n <= bucket:
            raise ValueError(f"cannot pad {n} samples into bucket {bucket}")
        with span("repro.executor.stage_inputs", n=n, bucket=bucket):
            leaves0, treedef = jax.tree.flatten(pytrees[0])
            sig = tuple((tuple(lf.shape), np.dtype(lf.dtype))
                        for lf in leaves0)
            key = (bucket, treedef, sig)
            bufs = self._bufs.get(key)
            if bufs is None:
                bufs = [np.empty((bucket,) + tuple(lf.shape[1:]),
                                 dtype=np.dtype(lf.dtype)) for lf in leaves0]
                self._bufs[key] = bufs
            for i, tree in enumerate(pytrees):
                leaves = leaves0 if i == 0 else treedef.flatten_up_to(tree)
                for buf, leaf in zip(bufs, leaves):
                    buf[i] = np.asarray(leaf)[0]
            for buf in bufs:                   # replicate last valid row
                buf[n:] = buf[n - 1]
            return treedef.unflatten(bufs), self.mask(bucket, n)


def pad_batch(pytrees, bucket: int, staging: StagingBuffers = None):
    """Stack single-sample pytrees (leading dim 1) into a padded batch.

    Returns ``(batched, mask)`` — mask[i] is True for the len(pytrees)
    valid rows, False for the replicated padding rows.  With ``staging``,
    the batch is formed in that instance's reused per-bucket buffers
    (no per-dispatch allocation) instead of freshly stacked arrays."""
    if staging is not None:
        return staging.stage(pytrees, bucket)
    n = len(pytrees)
    if not 0 < n <= bucket:
        raise ValueError(f"cannot pad {n} samples into bucket {bucket}")
    reps = list(pytrees) + [pytrees[-1]] * (bucket - n)
    batched = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *reps)
    mask = np.arange(bucket) < n
    return batched, mask


def split_rows(tree, n: int):
    """Undo pad_batch: the first `n` rows as single-sample pytrees."""
    return [jax.tree.map(lambda x: x[i:i + 1], tree) for i in range(n)]


class BatchedStageFns:
    """Per-stage jitted batched ``stage_forward`` with bucket discipline."""

    def __init__(self, cfg, buckets=DEFAULT_BUCKETS):
        self.cfg = cfg
        self.buckets = tuple(sorted(buckets))
        self._fns = {}
        self.staging = StagingBuffers()

    def fn(self, stage: int):
        if stage not in self._fns:
            def f(params, h, _s=stage):
                return stage_forward(self.cfg, params, _s, h, mode="train")
            self._fns[stage] = jax.jit(f)
        return self._fns[stage]

    def run(self, stage: int, params, pytrees):
        """Pad, dispatch one batched stage, return (h, logits, conf, mask).

        ``pytrees``: single-sample stage inputs (raw inputs for stage 0,
        hidden states after)."""
        h, mask = pad_batch(pytrees, bucket_for(len(pytrees), self.buckets),
                            staging=self.staging)
        h_out, logits, conf = self.fn(stage)(params, h)
        return h_out, logits, conf, mask

    def warmup(self, params, sample_input):
        """Compile every (stage, bucket) shape before the clock starts."""
        for b in self.buckets:
            h = pad_batch([sample_input], b)[0]
            for s in range(self.cfg.num_stages):
                out = self.fn(s)(params, h)
                jax.block_until_ready(out[0])
                h = out[0]


def profile_batched_stages(cfg, params, fns: BatchedStageFns, sample_input, *,
                           n_runs: int = 30, percentile: float = 99.0):
    """Profile the (num_stages, num_buckets) batched-stage WCET matrix.

    Each entry is the ``percentile`` of ``n_runs`` timed runs (paper §IV:
    99% upper bound over profiling runs), one column per batch-size
    bucket; stage s+1 runs on stage s's output.  Returns
    ``(BatchTimeModel, matrix)``."""
    L = cfg.num_stages
    mat = np.zeros((L, len(fns.buckets)))
    for bi, b in enumerate(fns.buckets):
        h = pad_batch([sample_input], b)[0]
        for s in range(L):
            f = fns.fn(s)
            out = f(params, h)                     # compile
            jax.block_until_ready(out[0])
            ts = np.zeros(n_runs)
            for i in range(n_runs):
                t0 = time.perf_counter()
                out = f(params, h)
                jax.block_until_ready(out[0])
                ts[i] = time.perf_counter() - t0
            mat[s, bi] = np.percentile(ts, percentile)
            h = out[0]
    return BatchTimeModel.from_profile(mat, fns.buckets), mat
