"""The ``decode`` executor's confidence readback.

``commit`` reads the step's own confidence output, whose copy to the host
``submit`` started, and averages it on the host: it must return what a
device-side mean of the same output gives, at every depth, with the
speculative next depth on or off, and count how many readbacks ran while
a deeper speculative step was in flight.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core.task import Task
from repro.launch.serve import DecodeExecutor
from repro.models import decode_step, init_decode_cache, init_params

N_TOKENS = 4
N_STAGES = 3
BATCH = 2


class _Clock:
    def now(self):
        return 0.0


@pytest.fixture(scope="module")
def decode_parts():
    cfg = dataclasses.replace(get_config("qwen3-4b").reduced(),
                              num_layers=N_STAGES,
                              stage_ends=tuple(range(1, N_STAGES + 1)))
    params = init_params(cfg, jax.random.PRNGKey(0))
    steps = [jax.jit(lambda p, c, t, pos, _d=d: decode_step(
        cfg, p, c, t, pos, upto_stage=_d)) for d in range(1, N_STAGES + 1)]
    return cfg, params, steps


@pytest.mark.parametrize("speculate", [False, True])
@pytest.mark.parametrize("depth", [1, 2, 3])
def test_commit_reads_the_steps_own_confidence(decode_parts, depth,
                                               speculate):
    """Every token runs to ``depth``; each commit returns the mean of that
    depth's confidence output, and the counters say how many readbacks
    had a deeper speculative step in flight (all but the last depth's)."""
    cfg, params, steps = decode_parts
    ex = DecodeExecutor(steps, params,
                        init_decode_cache(cfg, BATCH, N_TOKENS + 1),
                        jnp.arange(1, BATCH + 1, dtype=jnp.int32),
                        speculate=speculate)
    for sample in range(N_TOKENS):
        task = Task(arrival=0.0, deadline=math.inf,
                    stage_times=(0.0,) * N_STAGES, mandatory=1,
                    sample=sample)
        for stage in range(depth):
            ex.submit(stage, [task], 0.0)
            assert ex.complete(_Clock()) == (stage, [task])
            task.executed += 1
            got = ex.commit(task, 0)
            out, _cache = ex.chosen
            assert len(out.confidences) == stage + 1
            assert abs(got - float(jnp.mean(out.confidences[-1]))) <= 1e-6
        out, slots = ex.chosen
        ex.commit_slots(slots)
        ex.tok = jnp.argmax(out.logits[-1], -1).astype(jnp.int32)
    assert ex.readbacks == N_TOKENS * depth
    if not speculate:
        assert ex.readbacks_overlapped == ex.speculated == 0
    elif depth == N_STAGES:
        assert ex.readbacks_overlapped == ex.readbacks - N_TOKENS
    else:
        assert ex.readbacks_overlapped == ex.readbacks
    assert ex.spec_hits == (N_TOKENS * (depth - 1) if speculate else 0)
