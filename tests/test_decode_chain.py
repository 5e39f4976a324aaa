"""Chained decode depths on committed caches.

``decode_step`` resumed from a :class:`~repro.models.DecodeFrom` (the
previous depth's ``h_final``) and the previous depth's new cache must give
what the same depth gives from the embedding and the committed cache: the
same last exit and, with the stages it does not run taken from the cache
it was given, the same stage caches.  The ``decode`` executor resumes
every depth after the first that way, with the speculative next depth on
or off, serves the same tokens as a loop of plain steps, and says on each
``repro.executor.launch`` span how many resumed steps it enqueued.

Its steps read the committed caches as :class:`~repro.models.CommittedCache`
and return only the token's slots, which the executor writes in place when
the token's successor is dispatched: over GQA full and ring caches, MLA
and recurrent layers it serves what plain steps under the plain commit
rule serve (a retired depth's new cache becomes the committed one), and
ends on the same committed caches.
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
from repro.models import (CommittedCache, DecodeFrom, decode_step,
                          init_decode_cache, init_params)
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
        out, slots = ex.chosen
        ex.commit_slots(slots)
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


# -- committed caches: equivalence with plain steps -------------------------

EQ_TOKENS = 10
EQ_SLOTS = EQ_TOKENS + 2

#: registry configs at their reduced widths, deepened where a stage needs
#: more than one layer for its scan group, and the cache slots of each.
#: The ring case cuts the window to 4 and the slots to 6, so that local and
#: full-attention layers both wrap inside the run (a full-attention ring's
#: write slot holds a position still inside its reach); MLA's latent cache
#: wraps at 8
EQ_CONFIGS = {
    "gqa": ("qwen3-4b", dict(num_layers=6, stage_ends=(2, 4, 6)), EQ_SLOTS),
    "gqa-ring": ("gemma3-4b", dict(num_layers=8, stage_ends=(4, 8),
                                   sliding_window=4), 6),
    "mla": ("deepseek-v3-671b", dict(num_layers=5, stage_ends=(1, 3, 5)), 8),
    "mlstm-slstm": ("xlstm-1.3b", {}, EQ_SLOTS),
    "mamba": ("jamba-1.5-large-398b", {}, EQ_SLOTS),
}


@pytest.fixture(scope="module")
def eq_parts():
    built = {}

    def get(name):
        if name not in built:
            arch, kw, slots = EQ_CONFIGS[name]
            cfg = dataclasses.replace(get_config(arch).reduced(), **kw)
            params = init_params(cfg, jax.random.PRNGKey(1))
            n = len(cfg.stage_boundaries())
            steps = [jax.jit(lambda p, c, t, pos, _d=d: decode_step(
                cfg, p, c, t, pos, upto_stage=_d)) for d in range(1, n + 1)]
            built[name] = cfg, params, steps, slots
        return built[name]
    return get


def _plain_commit_rule(cfg, params, steps, slots, target):
    """Plain steps on plain caches, the rule the executor replaces: each
    depth from the token, the first whose mean confidence reaches
    ``target`` retires, and its new cache is committed."""
    cache = init_decode_cache(cfg, BATCH, slots)
    tok = jnp.asarray(START, jnp.int32)
    toks, confs, depths = [], [], []
    for t in range(EQ_TOKENS):
        pos = jnp.full((BATCH,), t, jnp.int32)
        for d in range(1, len(steps) + 1):
            out, new_cache = steps[d - 1](params, cache, tok, pos)
            if float(np.asarray(out.confidences[-1]).mean()) >= target:
                break
        cache = new_cache
        tok = jnp.argmax(out.logits[-1], -1).astype(jnp.int32)
        toks.append(np.asarray(tok))
        confs.append(np.asarray(out.confidences[-1]))
        depths.append(d)
    return np.stack(toks), np.stack(confs), depths, cache


def _served(cfg, params, steps, slots, target, speculate):
    """The same decode through ``Service``: the ``decode`` executor on
    committed caches, the ``token-loop`` source committing its slots."""
    n = len(steps)
    spec = ServeSpec(
        policy="conf-target", policy_args={"target": target},
        executor="decode", executor_args={"speculate": speculate},
        clock="wall", source="token-loop",
        source_args={"n_tokens": EQ_TOKENS, "n_stages": n},
        batching={"mode": "none", "stage_times": [0.0] * n})
    rec = []

    def advance(task, out):
        tok = jnp.argmax(out.logits[-1], -1).astype(jnp.int32)
        rec.append((np.asarray(tok), np.asarray(out.confidences[-1]),
                    task.executed))
        return tok

    svc = Service.from_spec(
        spec, steps=steps, params=params,
        cache=init_decode_cache(cfg, BATCH, slots),
        tok=jnp.asarray(START, jnp.int32), advance=advance)
    svc.run()
    svc.close()
    toks, confs, depths = zip(*rec)
    return np.stack(toks), np.stack(confs), list(depths), svc.executor


#: (config, confidence target): 1.0 is never reached, so every token runs
#: to full depth; the low targets stop some tokens at depth 1 (the
#: reduced models' confidences lie near 1 / vocab), so deeper stages keep
#: unwritten slots
EQ_CASES = [("gqa", 1.0), ("gqa", 0.0048), ("gqa-ring", 0.0047),
            ("mla", 0.0049), ("mlstm-slstm", 0.0052), ("mamba", 0.0048)]


@pytest.mark.parametrize("speculate", [False, True])
@pytest.mark.parametrize("name,target", EQ_CASES,
                         ids=[f"{n}-{t}" for n, t in EQ_CASES])
def test_committed_caches_serve_what_plain_steps_serve(eq_parts, name, target,
                                                       speculate):
    cfg, params, steps, slots = eq_parts(name)
    toks, confs, depths, cache = _plain_commit_rule(cfg, params, steps,
                                                    slots, target)
    if target < 1.0:
        assert 1 in depths and max(depths) > 1, depths
    got_toks, got_confs, got_depths, ex = _served(cfg, params, steps, slots,
                                                  target, speculate)
    assert got_depths == depths
    np.testing.assert_array_equal(got_toks, toks)
    np.testing.assert_allclose(got_confs, confs, rtol=1e-3, atol=1e-6)
    # each retired token's stages are written, the last token's on reading
    la, ta = jax.tree.flatten(ex.cache)
    lb, tb = jax.tree.flatten(cache)
    assert ta == tb and ex.slot_writes == sum(depths)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-4, atol=1e-5)
    discarded = ex.speculated - ex.spec_hits
    assert ex.chained == sum(depths) - EQ_TOKENS + discarded
    assert speculate or discarded == 0


# -- committed caches: what a step returns and what the write consumes ------

@pytest.mark.parametrize("depth", [1, 2, 3])
def test_a_step_on_committed_caches_returns_only_slots(eq_parts, depth):
    """No output of a jitted depth step on committed caches is as large
    as one layer's K cache, and a stage it does not run comes back None,
    on the token path as on the resumed one."""
    cfg, params, steps, slots = eq_parts("gqa")
    cache = init_decode_cache(cfg, BATCH, slots)
    view = [CommittedCache(c) for c in cache]
    k_size = cache[0]["scan"][0]["k"][0].size       # one layer's K
    pos = jnp.zeros((BATCH,), jnp.int32)
    tok = (jnp.asarray(START, jnp.int32) if depth == 1 else
           DecodeFrom(jnp.ones((BATCH, cfg.d_model), jnp.float32), depth - 1))
    out, slots = jax.eval_shape(steps[depth - 1], params, view, tok, pos)
    assert max(x.size for x in jax.tree.leaves((out, slots))) < k_size
    ran = depth - 1
    assert [s is None for s in slots] == [i != ran for i in range(3)]
    assert slots[ran]["scan"][0]["k"].shape == \
        (2, BATCH, cfg.num_kv_heads, cfg.resolved_head_dim)


def test_the_commit_write_consumes_the_old_caches(eq_parts):
    """Where the backend donates, writing a retired token's slots leaves
    the caches it replaced deleted: the write was made in place."""
    cfg, params, steps, slots = eq_parts("gqa")
    ex = DecodeExecutor(steps, params, init_decode_cache(cfg, BATCH, slots),
                        jnp.asarray(START, jnp.int32))
    task = Task(arrival=0.0, deadline=math.inf, stage_times=(0.0,) * 3,
                mandatory=1, sample=0)
    for stage in range(2):
        ex.submit(stage, [task], 0.0)
        ex.complete(_Clock())
        task.executed += 1
        ex.commit(task, 0)
    old = ex.cache
    out, slots = ex.chosen
    assert slots[2] is None
    ex.commit_slots(slots)
    new = ex.cache
    assert ex.slot_writes == 2
    assert new[2] is old[2]                    # depth 2: stage 3 untouched
    if jax.default_backend() in ("cpu", "tpu", "gpu"):
        assert all(x.is_deleted() for x in jax.tree.leaves(old[:2]))
    pos = np.asarray(new[1]["scan"][0]["slot_pos"])
    assert (pos[:, :, 0] == 0).all() and (pos[:, :, 1:] == -1).all()
