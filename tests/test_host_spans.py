"""Host spans on the profiler's clock (``repro.serving.obs.span``).

A tiny per-token anytime decode runs through ``Service`` on the wall clock
under ``jax.profiler.trace``; the trace's host plane then holds the
program's ``repro.*`` spans, and the ``repro.engine.run`` span anchors the
engine's clock on the profiler's.  On the virtual clock an open profiler
session changes nothing.
"""
from __future__ import annotations

import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.launch.serve  # noqa: F401  (registers decode + token-loop)
from repro.configs import get_config
from repro.core import RTDeepIoT, Workload, make_predictor
from repro.models import decode_step, init_decode_cache, init_params
from repro.serving import ServeSpec, Service
from repro.serving.batch import BatchTimeModel

from conftest import serve_closed_loop

N_TOKENS = 5
N_STAGES = 3


def host_spans(trace_dir):
    """``(name, start_ns, end_ns, metadata)`` of every ``repro.*`` event
    on the trace's host planes, in start order."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    pd = ProfileData.from_file(files[-1])
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    s = float(e.start_ns)
                    out.append((e.name, s, s + float(e.duration_ns),
                                dict(e.stats)))
    return sorted(out, key=lambda x: x[1])


@pytest.fixture(scope="module")
def traced_decode(tmp_path_factory):
    """A traced decode: 2 rows, 3 depths, every token run to full depth
    (the confidence target cannot be met), speculation on."""
    cfg = dataclasses.replace(get_config("qwen3-4b").reduced(),
                              num_layers=N_STAGES,
                              stage_ends=tuple(range(1, N_STAGES + 1)))
    assert len(cfg.stage_boundaries()) == N_STAGES
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = 2
    cache = init_decode_cache(cfg, batch, slots=N_TOKENS + 1)
    steps = [jax.jit(lambda p, c, t, pos, _d=d: decode_step(
        cfg, p, c, t, pos, upto_stage=_d)) for d in range(1, N_STAGES + 1)]
    tok = jnp.zeros((batch,), jnp.int32)
    pos0 = jnp.zeros((batch,), jnp.int32)
    for step in steps:                  # compile before the clock starts
        jax.block_until_ready(step(params, cache, tok, pos0)[0].logits[-1])

    def advance(task, out):
        return jnp.argmax(out.logits[-1], -1).astype(jnp.int32)

    spec = ServeSpec(
        policy="conf-target", policy_args={"target": 2.0},
        executor="decode", executor_args={"speculate": True},
        clock="wall", source="token-loop",
        source_args={"n_tokens": N_TOKENS, "n_stages": N_STAGES},
        batching={"mode": "none", "stage_times": [0.0] * N_STAGES},
        trace={"enabled": True})
    svc = Service.from_spec(spec, steps=steps, params=params, cache=cache,
                            tok=tok, advance=advance)
    d = str(tmp_path_factory.mktemp("profile"))
    with jax.profiler.trace(d):
        met = svc.run()
    svc.close()
    return {"spans": host_spans(d), "obs": svc.obs, "met": met,
            "executor": svc.executor}


def test_a_traced_decode_writes_the_program_spans(traced_decode):
    spans = traced_decode["spans"]
    assert traced_decode["met"].mean_depth == N_STAGES
    names = [n for n, *_ in spans]
    [run] = [x for x in spans if x[0] == "repro.engine.run"]
    assert all(run[1] <= s and e <= run[2] for _n, s, e, _m in spans)
    launch = [x for x in spans if x[0] == "repro.executor.launch"]
    readback = [x for x in spans if x[0] == "repro.executor.readback"]
    assert len(launch) == N_STAGES * N_TOKENS
    assert len(readback) == N_STAGES * N_TOKENS
    assert [m["depth"] for *_x, m in launch] == \
        list(range(1, N_STAGES + 1)) * N_TOKENS
    # speculation dispatched depths 2 and 3 ahead of their turn
    assert [m["hit"] for *_x, m in launch] == [0, 1, 1] * N_TOKENS
    # ... so depths 1 and 2 were read back with the next depth in flight
    assert [m["depth"] for *_x, m in readback] == \
        list(range(1, N_STAGES + 1)) * N_TOKENS
    assert [m["overlapped"] for *_x, m in readback] == [1, 1, 0] * N_TOKENS
    ex = traced_decode["executor"]
    assert ex.readbacks == N_STAGES * N_TOKENS
    assert ex.readbacks_overlapped == (N_STAGES - 1) * N_TOKENS
    assert names.count("repro.executor.wait") == N_STAGES * N_TOKENS
    assert names.count("repro.source.advance") == N_TOKENS
    assert names.count("repro.engine.retire") == N_TOKENS
    assert names.count("repro.engine.admit") == N_TOKENS
    assert names.count("repro.scheduler") >= 3 * N_TOKENS


def test_the_run_span_anchors_the_tracers_clock(traced_decode):
    spans = traced_decode["spans"]
    [run] = [x for x in spans if x[0] == "repro.engine.run"]
    launches = [s for n, s, _e, _m in spans if n == "repro.executor.launch"]
    dispatched = sorted(w["t0"] for w in traced_decode["obs"].windows)
    assert len(dispatched) == len(launches)
    for t, s in zip(dispatched, launches):
        assert abs(run[1] + t * 1e9 - s) < 2e6, (t, s - run[1])


def test_an_open_profiler_leaves_the_virtual_clock_alone(tmp_path):
    rng = np.random.default_rng(0)
    conf = np.sort(rng.uniform(0.3, 1.0, (200, 3)), axis=1)
    correct = rng.uniform(size=(200, 3)) < conf
    tm = BatchTimeModel.linear((0.004, 0.007, 0.010), (1, 2, 4, 8),
                               marginal=0.15)

    def run():
        res = serve_closed_loop(
            ServeSpec(charge_overhead=True, pipeline_depth=2,
                      policy_cost=2e-4),
            RTDeepIoT(make_predictor("exp", prior_curve=conf.mean(0))),
            Workload(n_clients=12, d_lo=0.01, d_hi=0.3, n_requests=120,
                     seed=0),
            conf, correct, time_model=tm)
        d = res.to_dict(per_request=True)
        d.pop("overhead_frac")          # the policy's own measured seconds
        for row in d["per_request"]:
            row.pop("tid")              # task ids count on across runs
        return d

    plain = run()
    with jax.profiler.trace(str(tmp_path)):
        traced = run()
    assert traced == plain
    assert plain["n_dispatches"] > 0 and plain["sched_charged"] > 0
