"""Faults a cell can have, planted in the timed path to show that ``correct``
catches them.  The benchmark's runs never plant one: the CPU tests and
``perfbench/tools.py faults`` (the chip, at a cell's own size) do.

Each fault is ``plant(patch, m)``: ``patch(obj, name, value)`` replaces an
attribute (pytest's ``monkeypatch.setattr``, or :class:`Patcher`), ``m`` is
the configuration file's sizes.
"""
from __future__ import annotations

import dataclasses


class Patcher:
    """``patch(obj, name, value)`` that puts every original back on exit."""

    def __init__(self):
        self._undo = []

    def __call__(self, obj, name, value):
        self._undo.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        for obj, name, value in reversed(self._undo):
            setattr(obj, name, value)
        self._undo = []


def answer_altered(patch, m):
    """Each committed answer's prediction is moved to the next class."""
    from repro.launch.kernel import KernelDeviceExecutor
    orig = KernelDeviceExecutor.commit

    def commit(self, task, k):
        c = orig(self, task, k)
        pred, conf = self.states[task.tid][2]
        self.states[task.tid][2] = ((pred + 1) % m["vocab_size"], conf)
        return c
    patch(KernelDeviceExecutor, "commit", commit)


def prefill_half_batch(patch, m):
    """Only the first half of each batch is computed; the other rows get
    the first row's results."""
    from repro.launch.kernel import KernelStageFns
    orig = KernelStageFns.run

    def run(self, stage, params, pytrees):
        h, pred, conf, mask = orig(self, stage, params, pytrees)
        if len(pytrees) < 2:
            return h, pred, conf, mask
        half = len(pytrees) // 2
        return (h.at[half:].set(h[:1]), pred.at[half:].set(pred[0]),
                conf.at[half:].set(conf[0]), mask)
    patch(KernelStageFns, "run", run)


def _replace_last_logits(ex, fn):
    out, cache = ex.chosen
    logits = list(out.logits)
    logits[-1] = fn(logits[-1])
    ex.chosen = (dataclasses.replace(out, logits=logits), cache)
    return out


def token_altered(patch, m):
    """Each step's last logits are rolled by one: every served token moves
    to its neighbour in the vocabulary."""
    import jax.numpy as jnp
    from repro.launch.serve import DecodeExecutor
    orig = DecodeExecutor.commit

    def commit(self, task, k):
        c = orig(self, task, k)
        _replace_last_logits(self, lambda x: jnp.roll(x, 1, axis=-1))
        return c
    patch(DecodeExecutor, "commit", commit)


def decode_half_batch(patch, m):
    """Only the first half of the batch is decoded: the other rows get the
    first row's logits, and the confidence is the mean over the first half."""
    import jax.numpy as jnp
    from repro.launch.serve import DecodeExecutor
    orig = DecodeExecutor.commit

    def commit(self, task, k):
        orig(self, task, k)
        out = _replace_last_logits(
            self, lambda x: x.at[x.shape[0] // 2:].set(x[0]))
        conf = out.confidences[-1]
        return float(jnp.mean(conf[:conf.shape[0] // 2]))
    patch(DecodeExecutor, "commit", commit)


def state_unchanged(patch, m):
    """The decode state (the KV cache) is returned unchanged by each step."""
    from repro.launch.serve import TokenLoopSource
    orig = TokenLoopSource.on_retire

    def on_retire(self, task, now):
        kept = self.executor.cache
        orig(self, task, now)
        self.executor.cache = kept
    patch(TokenLoopSource, "on_retire", on_retire)


#: the faults of each path driver, by name
BY_DRIVER = {
    "service_prefill": {"answer-altered": answer_altered,
                        "half-batch": prefill_half_batch},
    "token_decode": {"token-altered": token_altered,
                     "half-batch": decode_half_batch,
                     "state-unchanged": state_unchanged},
}
