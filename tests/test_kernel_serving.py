"""Tests for the kernel-backed serving fast path (repro.launch.kernel).

Four contracts, alongside tests/test_kernels.py's per-kernel sweeps:

* **Padded-batch parity** — every Pallas kernel, run in interpret mode at
  the exact batch shapes the serving engine dispatches (the pad_batch
  buckets, padding rows replicating the last valid row), must return the
  same *valid* rows as its pure-jnp oracle on the unpadded inputs: the
  bucket discipline never contaminates real requests.
* **Fused exit-confidence exactness** — ``exit_stats_fused`` (one Pallas
  dispatch, logits never materialized) is bit-for-bit equal to the
  unfused reference on the anytime classifier (single vocab block).
* **Ragged decode exactness** — co-batched decode through the kernel
  route (per-row slot_pos) equals per-request singleton runs (preds
  exact, hidden states to float32 rounding) at ragged positions where
  the legacy jnp route (which shares row 0's slot map) is not exact.
* **Serving integration** — ``executor="device-kernel"`` matches
  ``device-batched`` predictions/depths end to end; length buckets
  gate batch formation; ``pipeline_depth >= 3`` stacks device windows;
  spec validation rejects malformed args at spec time.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.launch.serve  # noqa: F401 — registers device-kernel
from repro.core.task import Task
from repro.kernels.decode_attention import (decode_attention,
                                            decode_attention_ref)
from repro.kernels.exit_confidence import exit_confidence, exit_confidence_ref
from repro.kernels.flash_attention import flash_attention, flash_attention_ref
from repro.kernels.mlstm_chunk import mlstm_chunk, mlstm_chunk_ref
from repro.kernels.rmsnorm import rmsnorm, rmsnorm_ref
from repro.launch.kernel import (KernelStageFns, build_kernel_executor,
                                 length_bucketed_time_model)
from repro.serving import (BatchTimeModel, LengthBucketTimeModel, ServeSpec,
                           Service, closed_loop_stream)
from repro.serving.batch.batcher import StageBatcher
from repro.serving.batch.time_model import (batch_wcet, len_bucket_for,
                                            task_len_bucket)

SERVING_BUCKETS = (1, 2, 4, 8, 16)
STAGE_TIMES = (0.002, 0.003, 0.004)


def _pad_rows(x, bucket):
    """Serving-style padding: replicate the last valid row to the bucket."""
    reps = np.concatenate([x] + [x[-1:]] * (bucket - x.shape[0]), axis=0)
    return jnp.asarray(reps)


# ---------------------------------------------------------------------------
# padded-batch kernel/ref parity at serving bucket sizes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bucket", SERVING_BUCKETS)
def test_rmsnorm_padded_batch_parity(bucket):
    n = min(3, bucket)
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    x = jax.random.normal(ks[0], (n, 64))
    s = 0.1 * jax.random.normal(ks[1], (64,))
    out = rmsnorm(_pad_rows(np.asarray(x), bucket), s, block_rows=8)
    np.testing.assert_allclose(np.asarray(out[:n]),
                               np.asarray(rmsnorm_ref(x, s)),
                               atol=2e-6, rtol=2e-6)


@pytest.mark.parametrize("bucket", SERVING_BUCKETS)
def test_exit_confidence_padded_batch_parity(bucket):
    n = min(3, bucket)
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    h = jax.random.normal(ks[0], (n, 32))
    sc = 0.1 * jax.random.normal(ks[1], (32,))
    w = 0.3 * jax.random.normal(ks[2], (32, 10))
    conf, pred, m, lse = exit_confidence(_pad_rows(np.asarray(h), bucket),
                                         sc, w, block_rows=4)
    rc, rp, rm, rl = exit_confidence_ref(h, sc, w)
    np.testing.assert_allclose(np.asarray(conf[:n]), np.asarray(rc),
                               atol=2e-6, rtol=2e-6)
    assert np.array_equal(np.asarray(pred[:n]), np.asarray(rp))
    np.testing.assert_allclose(np.asarray(lse[:n]), np.asarray(rl),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("bucket", (1, 2, 4, 8))
def test_flash_attention_padded_batch_parity(bucket):
    n = min(3, bucket)
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (n, 4, 32, 16))
    k = jax.random.normal(ks[1], (n, 2, 32, 16))
    v = jax.random.normal(ks[2], (n, 2, 32, 16))
    pq, pk, pv = (_pad_rows(np.asarray(t), bucket) for t in (q, k, v))
    out = flash_attention(pq, pk, pv, causal=True, block_q=16, block_k=16)
    ref = flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out[:n]), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("bucket", (1, 2, 4, 8))
def test_decode_attention_padded_batch_parity(bucket):
    n = min(3, bucket)
    S = 24
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (n, 4, 16))
    kc = jax.random.normal(ks[1], (n, 2, S, 16))
    vc = jax.random.normal(ks[2], (n, 2, S, 16))
    sp = np.broadcast_to(np.arange(S), (n, S)).copy()
    cur = np.array([5, 11, 23][:n])
    out = decode_attention(_pad_rows(np.asarray(q), bucket),
                           _pad_rows(np.asarray(kc), bucket),
                           _pad_rows(np.asarray(vc), bucket),
                           _pad_rows(sp, bucket), _pad_rows(cur, bucket),
                           block_k=8)
    ref = decode_attention_ref(q, kc, vc, jnp.asarray(sp), jnp.asarray(cur))
    np.testing.assert_allclose(np.asarray(out[:n]), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("bucket", (1, 2, 4, 8))
def test_mlstm_chunk_padded_batch_parity(bucket):
    n = min(2, bucket)
    L, dh = 16, 16
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    q = jax.random.normal(ks[0], (n, 2, L, dh))
    k = jax.random.normal(ks[1], (n, 2, L, dh))
    v = jax.random.normal(ks[2], (n, 2, L, dh))
    ip = jax.random.normal(ks[3], (n, 2, L))
    fp = jax.random.normal(ks[4], (n, 2, L)) + 2
    C0 = jnp.zeros((n, 2, dh, dh))
    n0 = jnp.zeros((n, 2, dh))
    m0 = jnp.full((n, 2), -1e30)
    padded = [_pad_rows(np.asarray(t), bucket)
              for t in (q, k, v, ip, fp, C0, n0, m0)]
    out = mlstm_chunk(*padded)
    ref = mlstm_chunk_ref(q, k, v, ip, fp, C0, n0, m0)
    for o, r in zip(out, ref):
        np.testing.assert_allclose(np.asarray(o[:n]), np.asarray(r),
                                   atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# fused exit epilogue: bit-for-bit vs the unfused reference
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_model():
    from repro.configs import get_config
    from repro.models import init_params
    cfg = get_config("anytime-classifier")
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


@pytest.mark.parametrize("bucket", (1, 4, 16))
def test_fused_exit_stats_bitwise_equal_unfused(tiny_model, bucket):
    """Single vocab block => the kernel's online pass folds exactly once:
    conf/pred/max/lse all bit-for-bit equal to the materialized-logits
    reference — the kernel-serving figure's exactness claim."""
    from repro.models import exit_rows, exit_stats_fused, exit_stats_unfused
    cfg, params = tiny_model
    h = jax.random.normal(jax.random.PRNGKey(7), (bucket, 16, cfg.d_model))
    rows = exit_rows(cfg, h)
    for s in range(cfg.num_stages):
        scale = params["exits"][s]["ln"]
        w_out = params["exit_shared"]["w_out"]
        fused = exit_stats_fused(rows, scale, w_out, eps=cfg.norm_eps)
        ref = exit_stats_unfused(rows, scale, w_out, eps=cfg.norm_eps)
        for f, r in zip(fused, ref):
            assert np.array_equal(np.asarray(f), np.asarray(r))


def test_kernel_stage_fns_fused_outputs(tiny_model):
    """KernelStageFns returns (h, pred, conf) with pred/conf equal to the
    unfused epilogue applied to the same trunk output."""
    from repro.models import exit_rows, exit_stats_unfused, stage_trunk
    cfg, params = tiny_model
    fns = KernelStageFns(cfg, (1, 2, 4))
    x = {"features": jnp.asarray(
        np.random.default_rng(0).normal(size=(1, 16, 32)), jnp.float32)}
    h, pred, conf, mask = fns.run(0, params, [x])
    # the unfused epilogue on the *same* trunk output must agree bitwise
    # (the fused/unfused claim); the trunk itself matches the eager
    # stage_trunk up to jit fusion reassociation
    rc, rp, _m, _l = exit_stats_unfused(exit_rows(cfg, h),
                                        params["exits"][0]["ln"],
                                        params["exit_shared"]["w_out"],
                                        eps=cfg.norm_eps)
    h_ref = stage_trunk(cfg, params, 0, x, mode="train")
    np.testing.assert_allclose(np.asarray(h[:1]), np.asarray(h_ref),
                               atol=1e-5, rtol=1e-5)
    assert int(pred[0]) == int(rp[0])
    assert float(conf[0]) == float(rc[0])
    assert mask.tolist() == [True]


def test_kernel_stage_fns_rejects_audio_head():
    from repro.configs.base import ModelConfig
    cfg = ModelConfig(name="audio-x", arch_type="dense", source="test",
                      num_layers=2, d_model=32, num_heads=2, num_kv_heads=2,
                      head_dim=16, d_ff=64, vocab_size=16, period=("attn",),
                      modality="audio_stub", num_stages=1, stage_ends=(2,))
    with pytest.raises(ValueError, match="audio"):
        KernelStageFns(cfg, (1, 2))


# ---------------------------------------------------------------------------
# length-bucketed WCET pricing
# ---------------------------------------------------------------------------

def test_len_bucket_for_rounds_up():
    assert len_bucket_for(1, (16, 64)) == 16
    assert len_bucket_for(16, (16, 64)) == 16
    assert len_bucket_for(17, (16, 64)) == 64
    with pytest.raises(ValueError):
        len_bucket_for(65, (16, 64))
    with pytest.raises(ValueError):
        len_bucket_for(0, (16, 64))


def test_length_bucket_time_model_pricing():
    tm = LengthBucketTimeModel.linear(STAGE_TIMES, (1, 2, 4),
                                      len_buckets=(16, 64), len_marginal=0.5)
    # length-blind == worst case == largest length bucket
    for s in range(3):
        assert tm.wcet(s, 2) == tm.wcet(s, 2, seq_len=64)
        assert tm.wcet(s, 2, seq_len=10) < tm.wcet(s, 2, seq_len=64)
        # floor: the shortest bucket still costs len_marginal +
        # (1 - len_marginal) * 16/64 of the base
        base = BatchTimeModel.linear(STAGE_TIMES, (1, 2, 4))
        assert tm.wcet(s, 2, seq_len=16) == pytest.approx(
            base.wcet(s, 2) * (0.5 + 0.5 * 16 / 64))


def test_length_bucket_time_model_validates_base_is_max():
    tm = LengthBucketTimeModel.linear(STAGE_TIMES, (1, 2), len_buckets=(8, 32))
    with pytest.raises(ValueError, match="max over length"):
        LengthBucketTimeModel(buckets=tm.buckets,
                              times=tuple(tuple(t * 0.5 for t in row)
                                          for row in tm.times),
                              len_buckets=tm.len_buckets, times3=tm.times3)
    with pytest.raises(ValueError, match="ascending"):
        LengthBucketTimeModel(buckets=tm.buckets, times=tm.times,
                              len_buckets=(32, 8), times3=tm.times3)


def test_length_bucketed_refinement_preserves_blind_pricing():
    base = BatchTimeModel.linear(STAGE_TIMES, (1, 2, 4), marginal=0.25)
    tm = length_bucketed_time_model(base, (16, 64), len_marginal=0.25)
    assert isinstance(tm, LengthBucketTimeModel)
    assert tm.times == base.times          # length-blind consumers unchanged
    assert length_bucketed_time_model(tm, (8,)) is tm   # idempotent
    for s in range(3):
        for n in (1, 3):
            assert tm.wcet(s, n) == base.wcet(s, n)
            assert tm.wcet(s, n, seq_len=64) == base.wcet(s, n)


def test_batch_wcet_and_task_len_bucket():
    tm = LengthBucketTimeModel.linear(STAGE_TIMES, (1, 2, 4),
                                      len_buckets=(16, 64))
    mk = lambda sl: Task(arrival=0.0, deadline=1.0, stage_times=STAGE_TIMES,
                         seq_len=sl)
    short, long, blind = mk(8), mk(40), mk(None)
    assert task_len_bucket(tm, short) == 16
    assert task_len_bucket(tm, long) == 64
    assert task_len_bucket(tm, blind) is None
    # all-lengths batch prices at the max member length
    assert batch_wcet(tm, 0, [short, long]) == tm.wcet(0, 2, seq_len=40)
    # any length-blind member => conservative (worst-length) pricing
    assert batch_wcet(tm, 0, [short, blind]) == tm.wcet(0, 2)


def test_stage_batcher_filters_by_length_bucket():
    tm = LengthBucketTimeModel.linear((0.002,), (1, 2, 4),
                                      len_buckets=(16, 64))
    b = StageBatcher(tm)
    mk = lambda tid, sl: Task(arrival=0.0, deadline=10.0,
                              stage_times=(0.002,), tid=tid, seq_len=sl)
    t_short = [mk(0, 8), mk(1, 12)]
    t_long = [mk(2, 40)]
    batch = b.form(t_short[0], t_short + t_long, now=0.0)
    assert set(t.tid for t in batch) == {0, 1}      # long excluded
    batch = b.form(t_long[0], t_short + t_long, now=0.0)
    assert [t.tid for t in batch] == [2]
    # a length-blind leader batches anyone (worst-case pricing)
    blind = [mk(i + 10, None) for i in range(2)]
    batch = b.form(blind[0], blind, now=0.0)
    assert len(batch) == 2


# ---------------------------------------------------------------------------
# ragged decode batching: kernel route bitwise vs singleton runs
# ---------------------------------------------------------------------------

def _decode_cfg():
    from repro.configs.base import ModelConfig
    return ModelConfig(name="tiny-decode-test", arch_type="dense",
                       source="test", num_layers=4, d_model=64, num_heads=4,
                       num_kv_heads=2, head_dim=16, d_ff=128, vocab_size=32,
                       period=("attn",), ffn_type="swiglu", modality="text",
                       causal=True, num_stages=2, mandatory_stages=1,
                       stage_ends=(2, 4), dtype="float32")


def test_ragged_decode_batch_bitwise_equals_singletons():
    """Co-batched decode at ragged positions through the Pallas route is
    bitwise equal to a same-shape batch of each request alone — the
    exactness the per-row slot_pos map buys (the legacy jnp route shares
    row 0's).  Against the request run at batch 1, predictions are exact
    and hidden states, confidences and cache rows agree to float32
    rounding (1e-5 of the hidden scale), because XLA does not give
    batch-shape-invariant bits."""
    from repro.launch.kernel import KernelDecodeStageFns, ragged_decode_check
    from repro.launch.mesh import make_serving_mesh
    from repro.models import ParallelCtx, init_params
    cfg = _decode_cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    ctx = ParallelCtx(mesh=make_serving_mesh(1, 1), decode_attn="kernel")
    fns = KernelDecodeStageFns(cfg, (1, 2, 4), ctx)
    # three requests at ragged positions over a shared slot count
    r = ragged_decode_check(fns, params, [3, 9, 14], 16, seed=0)
    tol = 1e-5 * max(1.0, r["h_scale"])
    assert r["same_shape_equal"]
    assert r["pred_equal"]
    assert r["h_err"] <= tol, r
    assert r["cache_err"] <= tol, r
    assert r["conf_err"] <= 1e-6, r


def test_slice_concat_decode_cache_roundtrip():
    from repro.models import (concat_decode_caches, init_decode_cache,
                              slice_decode_cache)
    cfg = _decode_cfg()
    cache = init_decode_cache(cfg, 3, 8)
    rows = [slice_decode_cache(cache[0], i) for i in range(3)]
    back = concat_decode_caches(rows)
    for a, b in zip(jax.tree.leaves(cache[0]), jax.tree.leaves(back)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# serving integration: device-kernel through the registry
# ---------------------------------------------------------------------------

def _stream_spec(executor, executor_args, depth=1):
    return ServeSpec(
        policy="rtdeepiot",
        policy_args={"predictor": "exp", "prior_curve": [0.5, 0.7, 0.85]},
        executor=executor, executor_args=executor_args,
        clock="virtual", source="stream", pipeline_depth=depth,
        batching={"buckets": [1, 2, 4], "stage_times": list(STAGE_TIMES),
                  "marginal": 0.25})


def _classifier_stream(cfg, n_requests=12):
    from repro.training import DifficultyDataset
    ds = DifficultyDataset(num_classes=cfg.vocab_size, seed=0)
    test = ds.sample(30, seed=9)
    return list(closed_loop_stream(test["inputs"], test["labels"],
                                   n_clients=4, d_lo=0.2, d_hi=0.5,
                                   n_requests=n_requests, seed=1))


def test_device_kernel_matches_batched_predictions(tiny_model):
    cfg, params = tiny_model
    stream = _classifier_stream(cfg)
    runs = {}
    for ex in ("device-batched", "device-kernel"):
        svc = Service.from_spec(_stream_spec(ex, {}), cfg=cfg, params=params)
        svc.run(list(stream))
        runs[ex] = svc
    key = lambda svc: [(r.sample, r.prediction, r.depth, r.missed)
                       for r in svc.responses]
    assert key(runs["device-kernel"]) == key(runs["device-batched"])
    np.testing.assert_allclose(
        [r.confidence for r in runs["device-kernel"].responses],
        [r.confidence for r in runs["device-batched"].responses],
        rtol=1e-6)


def test_device_kernel_deep_pipeline_stacks_windows(tiny_model):
    cfg, params = tiny_model
    stream = _classifier_stream(cfg)
    svc = Service.from_spec(_stream_spec("device-kernel", {}, depth=3),
                            cfg=cfg, params=params)
    res = svc.run(list(stream))
    ex = svc.executor
    assert ex.max_inflight == 2            # pipeline_depth - 1 windows
    assert res.n_requests == 12
    assert len(ex._inflight) == 0          # fully drained
    stats = ex.device_time_stats()
    assert stats["host_time"] > 0 and stats["device_time"] > 0
    assert set(stats["stage_host_time"]) == set(stats["stage_device_time"])
    assert ex.cache_stats() == dict(live=0, peak=ex.peak_cached, evictions=12)


def test_service_metrics_surface_device_telemetry(tiny_model):
    """ServiceMetrics carries the executor's measured host/device split
    and cache lifecycle; modeled (oracle) runs report empty dicts."""
    cfg, params = tiny_model
    svc = Service.from_spec(_stream_spec("device-kernel", {}), cfg=cfg,
                            params=params)
    res = svc.run(_classifier_stream(cfg, n_requests=6))
    assert res.executor_times["host_time"] > 0
    assert res.executor_times["device_time"] > 0
    assert set(res.executor_times["stage_host_time"]) == {0, 1, 2} \
        or len(res.executor_times["stage_host_time"]) >= 1
    assert res.executor_cache == dict(live=0, peak=svc.executor.peak_cached,
                                      evictions=6)
    import json
    json.loads(res.to_json())                  # telemetry stays JSON-able
    # oracle executor: no device telemetry
    spec = ServeSpec(policy="edf", clock="virtual", source="stream",
                     batching={"mode": "none",
                               "stage_times": list(STAGE_TIMES)})
    import numpy as np_
    rng = np_.random.default_rng(0)
    conf = np_.sort(rng.uniform(0.5, 1.0, (10, 3)), axis=1)
    correct = rng.uniform(size=(10, 3)) < conf
    svc2 = Service.from_spec(spec, conf_table=conf, correct_table=correct)
    from repro.serving.engine import Request
    res2 = svc2.run([(0.0, Request(inputs=None, sample=0,
                                   rel_deadline=1.0))])
    assert res2.executor_times == {} and res2.executor_cache == {}


def test_executor_telemetry_fresh_across_repeated_runs(tiny_model):
    """The virtual clock never hides device telemetry (host/device time
    is wall-measured), and each run() on one Service rebuilds the
    executor — per-run cache stats never accumulate across runs."""
    cfg, params = tiny_model
    svc = Service.from_spec(_stream_spec("device-batched", {}), cfg=cfg,
                            params=params)
    res1 = svc.run(_classifier_stream(cfg, n_requests=6))
    res2 = svc.run(_classifier_stream(cfg, n_requests=4))
    for res, n in ((res1, 6), (res2, 4)):
        assert res.n_requests == n
        assert res.executor_times["host_time"] > 0
        assert res.executor_times["device_time"] > 0
        assert len(res.executor_times["stage_host_time"]) >= 1
        # every request's hidden state was cached and evicted this run
        assert res.executor_cache["live"] == 0
        assert res.executor_cache["evictions"] == n


def test_device_kernel_refines_time_model_with_len_buckets(tiny_model):
    cfg, params = tiny_model
    svc = Service.from_spec(
        _stream_spec("device-kernel", {"len_buckets": [16, 64]}),
        cfg=cfg, params=params)
    svc.run(_classifier_stream(cfg, n_requests=4))
    assert isinstance(svc.executor.time_model, LengthBucketTimeModel)
    assert svc.executor.time_model.len_buckets == (16, 64)


@pytest.mark.parametrize("bad", [
    {"mode": "prefill"}, {"block_rows": 0}, {"block_v": True},
    {"len_buckets": []}, {"len_buckets": [4, 4]}, {"len_buckets": [8, 2]},
    {"len_buckets": [1.5]}, {"len_marginal": 2.0}, {"bogus": 1},
])
def test_validate_rejects_bad_kernel_args(bad):
    spec = ServeSpec(executor="device-kernel", executor_args=bad)
    with pytest.raises(ValueError, match="device-kernel"):
        spec.validate()


def test_validate_accepts_kernel_args():
    ServeSpec(executor="device-kernel",
              executor_args={"mode": "decode", "interpret": True,
                             "block_rows": 8, "block_v": 512,
                             "len_buckets": [16, 64],
                             "len_marginal": 0.25}).validate()
    ServeSpec(executor="device-kernel").validate()


def test_build_kernel_executor_decode_mode_factory(tiny_model):
    """The factory seam directly: decode mode builds KernelDecodeStageFns
    over a 1x1 mesh with decode_attn='kernel' and depth-scaled windows."""
    from repro.launch.kernel import KernelDecodeStageFns
    from repro.models import init_params
    from repro.serving.registry import BuildContext
    cfg = _decode_cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    tm = BatchTimeModel.linear((0.002, 0.003), (1, 2, 4))
    ctx = BuildContext(spec=ServeSpec(pipeline_depth=3),
                       resources={"cfg": cfg, "params": params},
                       time_model=tm, max_batch=4)
    ex = build_kernel_executor({"mode": "decode", "len_buckets": [8, 16]},
                               ctx)
    assert isinstance(ex.stage_fns, KernelDecodeStageFns)
    assert ex.stage_fns.ctx.decode_attn == "kernel"
    assert ex.max_inflight == 2
    assert isinstance(ctx.time_model, LengthBucketTimeModel)
