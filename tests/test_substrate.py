"""Substrate tests: data pipeline, optimizer, checkpointing, serving engine,
confidence calibration plumbing."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_params
from repro.training import (AdamW, DifficultyDataset, checkpoint,
                            lm_token_stream, make_train_step, warmup_cosine)


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

def test_difficulty_dataset_deterministic():
    ds = DifficultyDataset(seed=3)
    a = ds.sample(32, seed=7)
    b = ds.sample(32, seed=7)
    np.testing.assert_array_equal(a["labels"], b["labels"])
    np.testing.assert_allclose(a["inputs"]["features"],
                               b["inputs"]["features"])


def test_difficulty_dataset_label_follows_chain():
    """The label must be the value at the true terminal of the pointer chain
    from cell 0 — re-derive it from the (noiseless) feature encoding."""
    ds = DifficultyDataset(seed=0, noise=0.0)
    d = ds.sample(64, seed=5)
    x = d["inputs"]["features"]
    sub = ds.feature_dim // 4
    # decode vals/ptrs from embeddings by nearest neighbour
    def nearest(block, table):
        d2 = ((block[:, :, None, :] - table[None, None]) ** 2).sum(-1)
        return d2.argmin(-1)
    vals = nearest(x[:, :, sub:2 * sub], ds.val_emb)
    ptrs = nearest(x[:, :, 2 * sub:3 * sub], ds.pos_emb)
    term = nearest(x[:, :, 3 * sub:], ds.term_emb)
    for i in range(x.shape[0]):
        cur = 0
        for _ in range(ds.seq_len + 1):
            if term[i, cur] == 1:
                break
            cur = ptrs[i, cur]
        assert vals[i, cur] == d["labels"][i]


def test_difficulty_bands_cover_spread():
    ds = DifficultyDataset(seed=0)
    d = ds.sample(512, seed=1)
    lens = d["difficulty"]
    assert lens.min() <= 2 and lens.max() >= 8


def test_lm_stream_learnable_structure():
    gen = lm_token_stream(vocab=64, seed=0)
    b = gen(4, 32, step_seed=1)
    assert b["inputs"]["tokens"].shape == (4, 32)
    assert b["labels"].shape == (4, 32)
    # next token is drawn from <= branching options given context
    assert b["labels"].max() < 64


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_adamw_descends_quadratic():
    opt = AdamW(learning_rate=0.1, weight_decay=0.0)
    params = {"w": jnp.array([3.0, -2.0])}
    state = opt.init(params)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}
        upd, state = opt.update(grads, state, params)
        params = jax.tree.map(lambda p, u: p + u, params, upd)
    assert float(jnp.abs(params["w"]).max()) < 1e-2


def test_adamw_clips_gradients():
    opt = AdamW(learning_rate=1.0, clip_norm=1e-3, weight_decay=0.0)
    params = {"w": jnp.zeros(4)}
    state = opt.init(params)
    upd, _ = opt.update({"w": jnp.full(4, 1e6)}, state, params)
    assert float(jnp.abs(upd["w"]).max()) < 10.0


def test_warmup_cosine_shape():
    sched = warmup_cosine(1.0, 10, 100)
    assert float(sched(jnp.array(0))) == pytest.approx(0.0)
    assert float(sched(jnp.array(10))) == pytest.approx(1.0, abs=0.02)
    assert float(sched(jnp.array(100))) == pytest.approx(0.1, abs=0.02)


def test_bf16_state_dtype():
    opt = AdamW(learning_rate=0.1, state_dtype="bfloat16")
    params = {"w": jnp.zeros((4,), jnp.bfloat16)}
    state = opt.init(params)
    assert state.mu["w"].dtype == jnp.bfloat16
    upd, state2 = opt.update({"w": jnp.ones(4)}, state, params)
    assert state2.nu["w"].dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path, rng):
    cfg = get_config("anytime-classifier")
    params = init_params(cfg, rng)
    path = os.path.join(tmp_path, "x.ckpt")
    checkpoint.save(path, params, {"step": 7})
    restored, meta = checkpoint.load(path, params)
    assert meta["step"] == 7
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_checkpoint_shape_mismatch_raises(tmp_path, rng):
    cfg = get_config("anytime-classifier")
    params = init_params(cfg, rng)
    path = os.path.join(tmp_path, "x.ckpt")
    checkpoint.save(path, params)
    bad = jax.tree.map(lambda x: jnp.zeros((*x.shape, 2), x.dtype), params)
    with pytest.raises(ValueError):
        checkpoint.load(path, bad)


# ---------------------------------------------------------------------------
# training decreases loss on the real pipeline
# ---------------------------------------------------------------------------

def test_train_decreases_loss(rng):
    cfg = get_config("anytime-classifier")
    ds = DifficultyDataset(num_classes=cfg.vocab_size, seed=0)
    params = init_params(cfg, rng)
    opt = AdamW(learning_rate=2e-3)
    opt_state = opt.init(params)
    step = jax.jit(make_train_step(cfg, opt))
    losses = []
    for i in range(30):
        b = ds.sample(64, seed=100 + i)
        params, opt_state, m = step(params, opt_state,
                                    {"inputs": b["inputs"],
                                     "labels": b["labels"]})
        losses.append(float(m["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.1


# ---------------------------------------------------------------------------
# serving engine (wall clock, real stage fns)
# ---------------------------------------------------------------------------

@pytest.mark.slow                    # jax compile dominates; no 20x repeat
@pytest.mark.wallclock
def test_serving_engine_end_to_end(rng):
    from repro.serving import (BatchedStageFns, ServeSpec, Service,
                               closed_loop_stream, profile_batched_stages)

    cfg = get_config("anytime-classifier")
    params = init_params(cfg, rng)
    ds = DifficultyDataset(num_classes=cfg.vocab_size, seed=0)
    test = ds.sample(40, seed=9)
    fns = BatchedStageFns(cfg, (1,))
    sample = jax.tree.map(lambda x: x[:1], test["inputs"])
    _, mat = profile_batched_stages(cfg, params, fns, sample, n_runs=5)
    wcet = mat[:, 0]
    spec = ServeSpec(policy="rtdeepiot",
                     policy_args={"predictor": "exp",
                                  "prior_curve": [.5, .7, .85]},
                     executor="device-batched", clock="wall", source="stream",
                     batching={"mode": "none",
                               "stage_times": [float(x) for x in wcet]})
    svc = Service.from_spec(spec, cfg=cfg, params=params, stage_fns=fns)
    # paper-like ratio: relative deadlines are many multiples of one stage
    # (their GPU stages ~10-25ms vs 10-300ms deadlines); our CPU stages are
    # ~1ms so host dispatch is a visible fraction — scale accordingly
    stream = closed_loop_stream(test["inputs"], test["labels"], n_clients=3,
                                d_lo=float(8 * wcet.max()),
                                d_hi=float(25 * wcet.max()), n_requests=12)
    svc.run(stream)
    responses = svc.responses
    assert len(responses) == 12
    done = [r for r in responses if not r.missed]
    assert len(done) >= 7            # generous deadlines: most complete
    for r in done:
        assert 1 <= r.depth <= cfg.num_stages
        assert 0.0 <= r.confidence <= 1.0


@pytest.mark.slow                    # jax compile dominates; no 20x repeat
@pytest.mark.wallclock
def test_serving_engine_tight_deadlines_shed_stages(rng):
    from repro.serving import (BatchedStageFns, ServeSpec, Service,
                               closed_loop_stream, profile_batched_stages)

    cfg = get_config("anytime-classifier")
    params = init_params(cfg, rng)
    ds = DifficultyDataset(num_classes=cfg.vocab_size, seed=0)
    test = ds.sample(40, seed=9)
    fns = BatchedStageFns(cfg, (1,))
    sample = jax.tree.map(lambda x: x[:1], test["inputs"])
    _, mat = profile_batched_stages(cfg, params, fns, sample, n_runs=5)
    wcet = mat[:, 0]
    spec = ServeSpec(policy="rtdeepiot",
                     policy_args={"predictor": "exp",
                                  "prior_curve": [.5, .7, .85]},
                     executor="device-batched", clock="wall", source="stream",
                     batching={"mode": "none",
                               "stage_times": [float(x) for x in wcet]})
    svc = Service.from_spec(spec, cfg=cfg, params=params, stage_fns=fns)
    stream = closed_loop_stream(test["inputs"], test["labels"], n_clients=6,
                                d_lo=float(3.5 * wcet.max()),
                                d_hi=float(7 * wcet.max()), n_requests=18)
    svc.run(stream)
    depths = [r.depth for r in svc.responses if not r.missed]
    assert depths and np.mean(depths) < cfg.num_stages  # shedding happened


def test_unbatched_device_matches_direct_stage_calls(rng):
    """``device-batched`` under ``batching={"mode": "none"}`` dispatches
    singleton batches whose exits equal direct jitted ``stage_forward``
    calls, stage by stage (a depth cap per request picks the exit)."""
    from repro.models import stage_forward
    from repro.serving import BatchedStageFns, Request, ServeSpec, Service

    cfg = get_config("anytime-classifier")
    params = init_params(cfg, rng)
    L = cfg.num_stages
    test = DifficultyDataset(num_classes=cfg.vocab_size,
                             seed=0).sample(2 * L, seed=9)
    sizes = []

    class CountingStageFns(BatchedStageFns):
        def run(self, stage, params, pytrees):
            sizes.append(len(pytrees))
            return super().run(stage, params, pytrees)

    def inputs(i):
        return jax.tree.map(lambda x: x[i:i + 1], test["inputs"])

    spec = ServeSpec(policy="edf", executor="device-batched",
                     clock="virtual", source="stream",
                     batching={"mode": "none", "stage_times": [0.001] * L},
                     slo_classes={f"d{d}": {"rel_deadline": 1.0,
                                            "depth_cap": d}
                                  for d in range(1, L + 1)})
    svc = Service.from_spec(spec, cfg=cfg, params=params,
                            stage_fns=CountingStageFns(cfg, (1,)))
    svc.run([(0.0, Request(inputs(i), sample=i, slo=f"d{i % L + 1}"))
             for i in range(2 * L)])
    assert sizes and set(sizes) == {1}
    assert len(svc.responses) == 2 * L
    direct = [jax.jit(lambda p, h, _s=s: stage_forward(cfg, p, _s, h,
                                                       mode="train"))
              for s in range(L)]
    for r in svc.responses:
        assert not r.missed and r.depth == r.sample % L + 1
        h = inputs(r.sample)
        for s in range(r.depth):
            h, logits, conf = direct[s](params, h)
        assert r.prediction == int(np.argmax(np.asarray(logits)[0]))
        assert r.confidence == float(np.max(np.asarray(conf)[0]))
