"""End-to-end behaviour tests for the paper's system: train a tiny anytime
model, verify confidence/utility structure, and validate the headline
scheduling claim (RTDeepIoT >= baselines) on the resulting oracle tables."""

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import EDF, LCF, RR, RTDeepIoT, Workload, make_predictor
from repro.models import init_params
from repro.serving import ServeSpec
from repro.training import (AdamW, DifficultyDataset, eval_exit_metrics,
                            make_train_step, warmup_cosine)

from conftest import serve_closed_loop


@pytest.fixture(scope="module")
def trained():
    """A quickly-trained anytime classifier + its oracle tables."""
    cfg = get_config("anytime-classifier")
    ds = DifficultyDataset(num_classes=cfg.vocab_size, seed=0)
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = AdamW(learning_rate=warmup_cosine(3e-3, 20, 250))
    opt_state = opt.init(params)
    step = jax.jit(make_train_step(cfg, opt, exit_weights=(0.2, 0.3, 0.5)))
    for i in range(250):
        b = ds.sample(128, seed=50_000 + i)
        params, opt_state, m = step(params, opt_state,
                                    {"inputs": b["inputs"],
                                     "labels": b["labels"]})
    test = ds.sample(600, seed=123_456)
    metrics = eval_exit_metrics(cfg, params, test)
    return cfg, params, test, metrics


def test_training_learns_task(trained):
    _, _, _, m = trained
    assert m["correct"][:, -1].mean() > 0.35      # >> 10% chance


def test_confidence_correlates_with_correctness(trained):
    """The utility metric must be informative: mean confidence of correct
    predictions exceeds that of incorrect ones at every stage."""
    _, _, _, m = trained
    for s in range(m["correct"].shape[1]):
        c, conf = m["correct"][:, s], m["confidence"][:, s]
        if c.all() or (~c).any() is False:
            continue
        assert conf[c].mean() > conf[~c].mean() + 0.02


def test_difficulty_drives_depth_utility(trained):
    """Easy samples (short chains) are solved earlier than hard ones —
    the paper's core data-dependence premise."""
    _, _, test, m = trained
    easy = test["difficulty"] <= 2
    hard = test["difficulty"] >= 7
    # stage-1 accuracy gap between easy and hard inputs
    assert m["correct"][easy, 0].mean() > m["correct"][hard, 0].mean() + 0.1


def test_rtdeepiot_dominates_baselines_on_trained_tables(trained):
    _, _, _, m = trained
    conf, correct = m["confidence"], m["correct"]
    wl = Workload(n_clients=20, d_lo=0.01, d_hi=0.2, n_requests=400)
    times = (0.007, 0.007, 0.007)
    accs = {}
    for name, pol in [
        ("rtdeepiot", RTDeepIoT(make_predictor("exp",
                                               prior_curve=conf.mean(0)))),
        ("edf", EDF()), ("lcf", LCF()), ("rr", RR()),
    ]:
        spec = ServeSpec(batching={"mode": "none", "stage_times": times})
        accs[name] = serve_closed_loop(spec, pol, wl, conf, correct).accuracy
    assert accs["rtdeepiot"] >= max(accs["edf"], accs["lcf"],
                                    accs["rr"]) - 1e-9


def test_oracle_tables_artifact_consistency():
    """If the shipped artifact exists it must be structurally valid."""
    import os
    path = os.path.join(os.path.dirname(__file__), "..", "artifacts",
                        "oracle_tables.npz")
    if not os.path.exists(path):
        pytest.skip("artifact not built yet")
    z = np.load(path)
    conf, correct = z["confidence"], z["correct"]
    assert conf.shape == correct.shape and conf.shape[1] == 3
    assert (conf >= 0).all() and (conf <= 1).all()
    # deeper final stage must beat stage 1 on the shipped model
    assert correct[:, -1].mean() >= correct[:, 0].mean()
