"""Chained decode depths.

``decode_step`` resumed from a :class:`~repro.models.DecodeFrom` (the
previous depth's ``h_final``) and the previous depth's new cache must give
what the same depth gives from the embedding and the committed cache: the
same last exit and, with the stages it does not run taken from the cache
it was given, the same stage caches.  The ``decode`` executor resumes
every depth after the first that way, with the speculative next depth on
or off, serves the same tokens as a loop of plain steps, and says on each
``repro.executor.launch`` span how many resumed steps it enqueued.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_host_spans import host_spans

from repro.configs import get_config
from repro.core.task import Task
from repro.launch.serve import DecodeExecutor
from repro.models import DecodeFrom, decode_step, init_decode_cache, init_params
from repro.serving import ServeSpec, Service

N_TOKENS = 4
N_STAGES = 3
BATCH = 2
START = (3, 7)


class _Clock:
    def now(self):
        return 0.0


@pytest.fixture(scope="module")
def decode_parts():
    cfg = dataclasses.replace(get_config("qwen3-4b").reduced(),
                              num_layers=N_STAGES,
                              stage_ends=tuple(range(1, N_STAGES + 1)))
    assert cfg.dtype == "float32"
    params = init_params(cfg, jax.random.PRNGKey(0))
    steps = [jax.jit(lambda p, c, t, pos, _d=d: decode_step(
        cfg, p, c, t, pos, upto_stage=_d)) for d in range(1, N_STAGES + 1)]
    return cfg, params, steps


def _plain_tokens(cfg, params, steps, depth):
    """Greedy tokens of a loop of plain depth-``depth`` steps."""
    cache = init_decode_cache(cfg, BATCH, N_TOKENS + 1)
    tok = jnp.asarray(START, jnp.int32)
    toks = []
    for t in range(N_TOKENS):
        pos = jnp.full((BATCH,), t, jnp.int32)
        out, cache = steps[depth - 1](params, cache, tok, pos)
        tok = jnp.argmax(out.logits[-1], -1).astype(jnp.int32)
        toks.append(np.asarray(tok))
    return np.stack(toks)


def _assert_caches_equal(a, b):
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("depth", [2, 3])
def test_a_chained_step_matches_the_plain_step(decode_parts, depth):
    """At each position, depth ``depth`` resumed from depth ``depth - 1``
    gives the plain step's last exit and every stage cache leaf."""
    cfg, params, steps = decode_parts
    cache = init_decode_cache(cfg, BATCH, N_TOKENS + 1)
    tok = jnp.asarray(START, jnp.int32)
    for t in range(N_TOKENS):
        pos = jnp.full((BATCH,), t, jnp.int32)
        plain, plain_cache = steps[depth - 1](params, cache, tok, pos)
        prev, prev_cache = steps[depth - 2](params, cache, tok, pos)
        got, ran = steps[depth - 1](
            params, prev_cache, DecodeFrom(prev.h_final, depth - 1), pos)
        assert len(got.logits) == len(got.confidences) == 1
        got_cache = [c if n is None else n for c, n in zip(prev_cache, ran)]
        np.testing.assert_allclose(np.asarray(got.logits[-1]),
                                   np.asarray(plain.logits[-1]), atol=1e-5)
        np.testing.assert_allclose(np.asarray(got.confidences[-1]),
                                   np.asarray(plain.confidences[-1]),
                                   atol=1e-5)
        _assert_caches_equal(got_cache, plain_cache)
        cache = plain_cache
        tok = jnp.argmax(plain.logits[-1], -1).astype(jnp.int32)


def test_a_resumed_step_returns_only_the_caches_it_runs(decode_parts):
    """Stages before the resume point and after ``upto_stage`` come back
    as None (the caller holds them); the stage run writes its cache."""
    cfg, params, steps = decode_parts
    cache = init_decode_cache(cfg, BATCH, N_TOKENS + 1)
    h = jnp.ones((BATCH, cfg.d_model), jnp.float32)
    pos = jnp.zeros((BATCH,), jnp.int32)
    _out, new_cache = steps[1](params, cache, DecodeFrom(h, 1), pos)
    assert new_cache[0] is None and new_cache[2] is None
    assert jax.tree.structure(new_cache[1]) == jax.tree.structure(cache[1])
    assert any(not np.array_equal(x, y) for x, y in
               zip(jax.tree.leaves(new_cache[1]), jax.tree.leaves(cache[1])))


@pytest.mark.parametrize("speculate", [False, True])
@pytest.mark.parametrize("depth", [1, 2, 3])
def test_the_executor_chains_every_depth_after_the_first(decode_parts, depth,
                                                         speculate):
    """Every token runs to ``depth``: the executor serves the plain loop's
    tokens, resumes ``depth - 1`` steps a token, and each output holds one
    exit per depth."""
    cfg, params, steps = decode_parts
    steps = steps[:depth]
    ex = DecodeExecutor(steps, params,
                        init_decode_cache(cfg, BATCH, N_TOKENS + 1),
                        jnp.asarray(START, jnp.int32), speculate=speculate)
    toks = []
    for sample in range(N_TOKENS):
        task = Task(arrival=0.0, deadline=math.inf,
                    stage_times=(0.0,) * depth, mandatory=1, sample=sample)
        for stage in range(depth):
            ex.submit(stage, [task], 0.0)
            ex.complete(_Clock())
            task.executed += 1
            ex.commit(task, 0)
        out, ex.cache = ex.chosen
        assert len(out.confidences) == len(out.logits) == depth
        ex.tok = jnp.argmax(out.logits[-1], -1).astype(jnp.int32)
        toks.append(np.asarray(ex.tok))
    assert ex.chained == N_TOKENS * (depth - 1)
    np.testing.assert_array_equal(np.stack(toks),
                                  _plain_tokens(cfg, params, steps, depth))


@pytest.mark.parametrize("speculate,per_token", [(False, [0, 1, 1]),
                                                 (True, [1, 1, 0])])
def test_the_launch_span_counts_its_chained_dispatches(decode_parts,
                                                       tmp_path, speculate,
                                                       per_token):
    """Through ``Service`` under a profiler, every token at full depth:
    without speculation depths 2 and 3 each resume in their own launch;
    with it depths 1 and 2 each enqueue the next depth resumed."""
    cfg, params, steps = decode_parts
    spec = ServeSpec(
        policy="conf-target", policy_args={"target": 2.0},
        executor="decode", executor_args={"speculate": speculate},
        clock="wall", source="token-loop",
        source_args={"n_tokens": N_TOKENS, "n_stages": N_STAGES},
        batching={"mode": "none", "stage_times": [0.0] * N_STAGES})
    svc = Service.from_spec(
        spec, steps=steps, params=params,
        cache=init_decode_cache(cfg, BATCH, N_TOKENS + 1),
        tok=jnp.asarray(START, jnp.int32),
        advance=lambda task, out: jnp.argmax(out.logits[-1], -1).astype(
            jnp.int32))
    with jax.profiler.trace(str(tmp_path)):
        svc.run()
    svc.close()
    launch = [m for n, _s, _e, m in host_spans(str(tmp_path))
              if n == "repro.executor.launch"]
    assert [m["chained"] for m in launch] == per_token * N_TOKENS
    assert svc.executor.chained == sum(per_token) * N_TOKENS
