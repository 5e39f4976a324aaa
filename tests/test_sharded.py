"""Tests for the device-sharded executor (repro.launch.sharded).

The contract under test, alongside the tests/test_runtime.py goldens:

* **No mesh fallback** — ``make_serving_mesh`` raises when the host
  lacks ``dp * tp`` devices, so a sharded result is never a silent
  single-device one; single-device CI runs the full sharded code path on
  an explicit 1x1 mesh.
* **Parity** — on the 1x1 mesh, ``executor="device-sharded"``
  must reproduce ``device-batched`` results **bit-for-bit** under the
  virtual clock, for both a stream source and a traffic scenario.
* **Pricing** — ``sharded_time_model`` scales buckets to dp-divisible
  global sizes (identity at dp=1, so golden parity is untouched) and adds
  the collective term only when dp > 1.
* **Validation** — ``ServeSpec.validate()`` rejects malformed dp/tp
  factors and mesh axis lists at spec time.
* **Hidden-state cache** — per-request state persists across stage
  dispatches and is fully evicted on retire.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

import repro.launch.serve  # noqa: F401 — registers device-sharded
from repro.launch.mesh import make_serving_mesh
from repro.launch.sharded import dp_buckets, sharded_time_model
from repro.serving import (BatchTimeModel, ServeSpec, Service,
                           closed_loop_stream)
from repro.serving.traffic import scenario_spec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

STAGE_TIMES = (0.002, 0.003, 0.004)


# ---------------------------------------------------------------------------
# mesh + pricing units
# ---------------------------------------------------------------------------

def test_make_serving_mesh_falls_back_to_1x1():
    """There is no fallback any more: a mesh larger than the host
    raises instead of degenerating to 1x1, and the 1x1 mesh is asked for
    explicitly."""
    n = len(jax.devices())
    with pytest.raises(ValueError, match="devices"):
        make_serving_mesh(n + 1, 2)
    with pytest.raises(ValueError, match="devices"):
        spec = _stream_spec("device-sharded", {"dp": n + 1, "tp": 1})
        Service.from_spec(spec, cfg=None, params={}).run([])
    assert dict(make_serving_mesh(1, 1).shape) == {"data": 1, "model": 1}
    with pytest.raises(ValueError, match=">= 1"):
        make_serving_mesh(0, 1)


def test_make_serving_mesh_axes():
    mesh = make_serving_mesh(1, 1, axes=("rows", "cols"))
    assert mesh.axis_names == ("rows", "cols")


def test_dp_buckets():
    assert dp_buckets((1, 2, 4), 1) == (1, 2, 4)
    assert dp_buckets((1, 2, 4), 2) == (2, 4, 8)
    assert dp_buckets((4, 2, 1), 2) == (2, 4, 8)   # sorts
    with pytest.raises(ValueError):
        dp_buckets((1, 2), 0)


def test_sharded_time_model_identity_at_dp1():
    tm = BatchTimeModel.linear(STAGE_TIMES, (1, 2, 4), marginal=0.15)
    assert sharded_time_model(tm, 1, collective=0.123) is tm


def test_sharded_time_model_prices_per_shard_bucket():
    tm = BatchTimeModel.linear(STAGE_TIMES, (1, 2, 4), marginal=0.15)
    c = 5e-4
    stm = sharded_time_model(tm, 4, collective=c)
    assert stm.buckets == (4, 8, 16)
    # a global batch of 4 puts 1 row per device: single-row WCET + sync
    for s in range(len(STAGE_TIMES)):
        assert stm.wcet(s, 4) == pytest.approx(tm.wcet(s, 1) + c)
        # 5 rows pad to global bucket 8 = per-shard bucket 2
        assert stm.wcet(s, 5) == pytest.approx(tm.wcet(s, 2) + c)
    assert stm.single_times() == tuple(t + c for t in tm.single_times())


# ---------------------------------------------------------------------------
# ServeSpec.validate()
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bad", [
    {"dp": 0}, {"dp": -2}, {"dp": 2.5}, {"dp": True}, {"tp": 0},
    {"tp": "2"}, {"mesh": ["data"]}, {"mesh": ["x", "x"]},
    {"mesh": "data,model"}, {"collective": -1.0}, {"bogus": 1},
    {"require": True},
])
def test_validate_rejects_bad_sharded_args(bad):
    spec = ServeSpec(executor="device-sharded", executor_args=bad)
    with pytest.raises(ValueError, match="device-sharded"):
        spec.validate()


def test_validate_accepts_sharded_args():
    ServeSpec(executor="device-sharded",
              executor_args={"dp": 4, "tp": 2, "mesh": ["data", "model"],
                             "collective": 2e-4}).validate()
    ServeSpec(executor="device-sharded").validate()   # all defaults


# ---------------------------------------------------------------------------
# 1x1-mesh parity against device-batched (the CI acceptance gate)
# ---------------------------------------------------------------------------

def _tiny_model():
    from repro.configs import get_config
    from repro.models import init_params
    cfg = get_config("anytime-classifier")
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _stream_spec(executor, executor_args):
    return ServeSpec(
        policy="rtdeepiot",
        policy_args={"predictor": "exp", "prior_curve": [0.5, 0.7, 0.85]},
        executor=executor, executor_args=executor_args,
        clock="virtual", source="stream",
        batching={"buckets": [1, 2, 4], "stage_times": list(STAGE_TIMES),
                  "marginal": 0.25})


def _response_key(responses):
    return [(r.sample, r.prediction, r.confidence, r.depth, r.missed,
             r.latency, r.deadline) for r in responses]


@pytest.fixture(scope="module")
def tiny_model():
    return _tiny_model()


def test_sharded_equals_batched_bitwise_stream(tiny_model):
    cfg, params = tiny_model
    from repro.training import DifficultyDataset
    ds = DifficultyDataset(num_classes=cfg.vocab_size, seed=0)
    test = ds.sample(30, seed=9)
    stream = closed_loop_stream(test["inputs"], test["labels"], n_clients=4,
                                d_lo=0.2, d_hi=0.5, n_requests=12, seed=1)
    runs = {}
    for ex, ea in (("device-batched", {}),
                   ("device-sharded", {"dp": 1, "tp": 1})):
        svc = Service.from_spec(_stream_spec(ex, ea), cfg=cfg, params=params)
        svc.run(list(stream))
        runs[ex] = svc
    sx = runs["device-sharded"].executor
    assert sx.dp == 1 and sx.tp == 1
    assert sx.stage_fns.buckets == (1, 2, 4)
    assert _response_key(runs["device-sharded"].responses) \
        == _response_key(runs["device-batched"].responses)


def test_sharded_traffic_scenario_bitwise_parity(tiny_model):
    """The batched traffic scenario end-to-end through the registry:
    identical per-request records on the 1x1 mesh."""
    cfg, params = tiny_model
    rng = np.random.default_rng(3)
    pool = rng.normal(size=(32, 1, 16, 32)).astype(np.float32)
    labels = rng.integers(0, cfg.vocab_size, size=32)
    base = scenario_spec(
        "steady", policy="rtdeepiot",
        policy_args={"predictor": "exp", "prior_curve": [0.5, 0.7, 0.85]},
        stage_times=STAGE_TIMES, n_requests=16, seed=0)
    base.batching = {"buckets": [1, 2, 4], "stage_times": list(STAGE_TIMES),
                     "marginal": 0.25}
    recs = {}
    for ex, ea in (("device-batched", {}), ("device-sharded", {"dp": 1})):
        spec = dataclasses.replace(base, executor=ex, executor_args=ea)
        res = Service.from_spec(
            spec, cfg=cfg, params=params, n_samples=len(pool), labels=labels,
            traffic_inputs=lambda s: {"features": pool[s]}).run()
        assert res.n_requests == 16
        recs[ex] = [(r["sample"], r["slo"], r["prediction"], r["conf"],
                     r["depth"], r["missed"], r["latency"])
                    for r in res.per_request]
    assert recs["device-sharded"] == recs["device-batched"]


def test_sharded_rejects_mismatched_stage_fns_resource(tiny_model):
    """A caller-supplied stage_fns whose bucket set does not match the
    dp-scaled global buckets must fail at build time, not at the first
    over-bucket dispatch."""
    from repro.serving import BatchedStageFns
    cfg, params = tiny_model
    svc = Service.from_spec(_stream_spec("device-sharded", {}), cfg=cfg,
                            params=params,
                            stage_fns=BatchedStageFns(cfg, (1, 2)))
    with pytest.raises(ValueError, match="bucket set"):
        svc.run([])


def test_sharded_hidden_state_cache_evicted_on_retire(tiny_model):
    cfg, params = tiny_model
    from repro.training import DifficultyDataset
    ds = DifficultyDataset(num_classes=cfg.vocab_size, seed=0)
    test = ds.sample(20, seed=5)
    stream = closed_loop_stream(test["inputs"], test["labels"], n_clients=3,
                                d_lo=0.2, d_hi=0.4, n_requests=9, seed=2)
    svc = Service.from_spec(_stream_spec("device-sharded", {}), cfg=cfg,
                            params=params)
    svc.run(list(stream))
    ex = svc.executor
    # every request's state was admitted, persisted while live, and
    # evicted exactly once at retire — nothing leaks past drain
    assert ex.cache_stats() == dict(live=0, peak=ex.peak_cached, evictions=9)
    assert ex.peak_cached >= 1
    assert ex.states == {}


# ---------------------------------------------------------------------------
# a real (non-degenerate) mesh, forced host devices — subprocess like
# tests/test_distributed.py
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_sharded_on_forced_two_device_mesh():
    """dp=2 on two forced host devices: a real two-device mesh, global
    buckets double, and results still match device-batched (row sharding
    keeps per-row math on a single device, so even bitwise holds)."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import jax, numpy as np
        import repro.launch.serve
        from repro.serving import ServeSpec, Service, closed_loop_stream
        from repro.configs import get_config
        from repro.models import init_params
        from repro.training import DifficultyDataset

        cfg = get_config("anytime-classifier")
        params = init_params(cfg, jax.random.PRNGKey(0))
        ds = DifficultyDataset(num_classes=cfg.vocab_size, seed=0)
        test = ds.sample(20, seed=9)
        stream = closed_loop_stream(test["inputs"], test["labels"],
                                    n_clients=4, d_lo=0.2, d_hi=0.5,
                                    n_requests=10, seed=1)
        runs = {}
        for ex, ea in (("device-batched", {}),
                       ("device-sharded", {"dp": 2, "tp": 1})):
            spec = ServeSpec(
                policy="rtdeepiot",
                policy_args={"predictor": "exp",
                             "prior_curve": [0.5, 0.7, 0.85]},
                executor=ex, executor_args=ea, clock="virtual",
                source="stream",
                batching={"buckets": [1, 2, 4],
                          "stage_times": [0.002, 0.003, 0.004],
                          "marginal": 0.25})
            svc = Service.from_spec(spec, cfg=cfg, params=params)
            svc.run(list(stream))
            runs[ex] = svc
        sx = runs["device-sharded"].executor
        assert sx.dp == 2 and sx.tp == 1
        assert sx.stage_fns.buckets == (2, 4, 8)
        assert sx.time_model.buckets == (2, 4, 8)
        key = lambda svc: [(r.sample, r.prediction, r.confidence, r.depth,
                            r.missed) for r in svc.responses]
        assert key(runs["device-sharded"]) == key(runs["device-batched"])
        print("OK")
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=420, env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-4000:]}"
    assert "OK" in r.stdout
