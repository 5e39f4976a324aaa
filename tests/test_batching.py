"""Tests for the continuous stage-level micro-batching subsystem
(repro.serving.batch): padded batched stage functions match per-sample
outputs, batch formation never violates a member's deadline, admission
control, the closed-loop reissue semantics, and a deterministic
micro-batched closed-loop run that strictly beats unbatched serving."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import EDF, LCF, RTDeepIoT, Task, Workload, make_predictor
from repro.models import init_params, stage_forward
from repro.serving import ServeSpec
from repro.serving.batch import (AdmissionController, BatchedPolicy,
                                 BatchTimeModel, StageBatcher,
                                 as_batch_policy, bucket_for, pad_batch)
from repro.serving.batch.stage_fns import BatchedStageFns, split_rows

from conftest import make_inputs, oracle_tables, serve_closed_loop


def mk_task(deadline, times=(0.004, 0.007, 0.010), executed=0, mandatory=1,
            now=0.0, confs=()):
    t = Task(arrival=now, deadline=deadline, stage_times=tuple(times),
             mandatory=mandatory)
    t.executed = executed
    t.assigned_depth = t.num_stages
    t.confidences = list(confs)
    return t


# ---------------------------------------------------------------------------
# BatchTimeModel / buckets
# ---------------------------------------------------------------------------

def test_bucket_rounding_and_wcet_monotone():
    tm = BatchTimeModel.linear((0.004, 0.007, 0.010), (1, 2, 4, 8),
                               marginal=0.2)
    assert tm.bucket_for(1) == 1 and tm.bucket_for(3) == 4
    assert tm.bucket_for(8) == 8
    with pytest.raises(ValueError):
        tm.bucket_for(9)
    for s in range(3):
        ws = [tm.wcet(s, b) for b in (1, 2, 4, 8)]
        assert all(a < b for a, b in zip(ws, ws[1:]))       # bigger = longer
        pi = [tm.per_item(s, b) for b in (1, 2, 4, 8)]
        assert all(a > b for a, b in zip(pi, pi[1:]))       # but cheaper/item
    assert tm.single_times() == (0.004, 0.007, 0.010)


def test_time_model_from_profile_roundtrip():
    mat = np.array([[1.0, 1.5], [2.0, 2.5]])                # (L=2, buckets=2)
    tm = BatchTimeModel.from_profile(mat, (1, 4))
    assert tm.wcet(0, 1) == 1.0 and tm.wcet(0, 3) == 1.5
    assert tm.wcet(1, 4) == 2.5 and tm.num_stages == 2


# ---------------------------------------------------------------------------
# StageBatcher: deadline invariant
# ---------------------------------------------------------------------------

def test_batcher_never_violates_member_deadline():
    """Randomized sweep: every formed batch's (bucket-rounded) WCET meets
    every member's deadline whenever the leader was feasible alone."""
    rng = np.random.default_rng(7)
    tm = BatchTimeModel.linear((0.004, 0.007, 0.010), (1, 2, 4, 8, 16),
                               marginal=0.15)
    batcher = StageBatcher(tm)
    for trial in range(200):
        now = float(rng.uniform(0, 1))
        stage = int(rng.integers(0, 3))
        tasks = [mk_task(now + float(rng.uniform(0.001, 0.08)),
                         executed=int(rng.integers(0, 3)))
                 for _ in range(int(rng.integers(1, 24)))]
        leaders = [t for t in tasks if t.executed == stage]
        if not leaders or not leaders[0].fits_batch(now, tm.wcet(stage, 1)):
            continue
        batch = batcher.form(leaders[0], tasks, now)
        w = tm.wcet(stage, len(batch))
        assert len(batch) <= tm.max_batch
        for m in batch:
            assert m.executed == stage
            assert m.fits_batch(now, w), \
                f"trial {trial}: member deadline violated by batch of " \
                f"{len(batch)} (wcet {w})"
        assert len(set(id(m) for m in batch)) == len(batch)


def test_batcher_growth_respects_bucket_jump():
    """Crossing a bucket boundary re-prices the whole batch: a member that
    fits at bucket 2 but not at bucket 4 blocks growth past 2."""
    st = (0.010,)
    tm = BatchTimeModel.linear(st, (1, 2, 4), marginal=1.0)  # 2x per item
    batcher = StageBatcher(tm)
    now = 0.0
    # bucket WCETs: b=1 -> 10ms, b=2 -> 20ms, b=4 -> 40ms
    leader = mk_task(0.025, times=st)
    tight = mk_task(0.021, times=st)         # fits 20ms, not 40ms
    loose1 = mk_task(0.100, times=st)
    loose2 = mk_task(0.200, times=st)
    batch = batcher.form(leader, [tight, loose1, loose2], now)
    # tight joins at size 2 (20ms); growing to 3 would price at bucket 4
    # (40ms), killing tight AND the leader (25ms) -> growth stops
    assert tight in batch and len(batch) == 2


def test_batcher_dp2_defers_tail_to_dp_multiple():
    """dp-aware formation (sharded seating): a fill that would round up
    to the next bucket trims back to the largest dp multiple when that
    lowers the priced bucket; otherwise the batch is left alone."""
    tm = BatchTimeModel.linear((0.010,), (1, 2, 4, 8), marginal=0.1)
    now = 0.0
    loose = lambda: mk_task(1.0, times=(0.010,))
    # n=5 prices at bucket 8; deferring one task to n=4 prices at bucket 4
    batch = StageBatcher(tm, dp=2).form(loose(), [loose() for _ in range(4)],
                                        now)
    assert len(batch) == 4
    # n=7 -> bucket 8, and n=6 still prices at bucket 8: no gain, no trim
    batch = StageBatcher(tm, dp=2).form(loose(), [loose() for _ in range(6)],
                                        now)
    assert len(batch) == 7
    # n=3 -> bucket 4; n=2 prices at bucket 2: defer one
    batch = StageBatcher(tm, dp=2).form(loose(), [loose() for _ in range(2)],
                                        now)
    assert len(batch) == 2
    # exact bucket hit (n=4, dp=3): no padding rows exist, so no trim
    batch = StageBatcher(tm, dp=3).form(loose(), [loose() for _ in range(3)],
                                        now)
    assert len(batch) == 4
    # the leader is never deferred even when n < dp
    batch = StageBatcher(tm, dp=4).form(loose(), [loose() for _ in range(2)],
                                        now)
    assert len(batch) == 3
    # dp=1 is the identity: the same fill keeps all 5 members
    batch = StageBatcher(tm, dp=1).form(loose(), [loose() for _ in range(4)],
                                        now)
    assert len(batch) == 5


def test_infeasible_leader_runs_solo():
    tm = BatchTimeModel.linear((0.010,), (1, 2), marginal=0.5)
    batcher = StageBatcher(tm)
    leader = mk_task(0.005, times=(0.010,))      # cannot even run alone
    other = mk_task(1.0, times=(0.010,))
    assert batcher.form(leader, [other], 0.0) == [leader]


def test_batched_policy_ranks_by_base_preference():
    """LCF batches lowest-confidence co-runners first when the bucket is
    scarce; EDF picks the earliest deadlines."""
    st = (0.001, 0.001, 0.001)
    tm = BatchTimeModel.linear(st, (1, 2), marginal=0.1)     # room for 2
    now = 0.0
    def tasks():
        a = mk_task(0.5, times=st, executed=1, confs=[0.9])
        b = mk_task(0.4, times=st, executed=1, confs=[0.2])
        c = mk_task(0.3, times=st, executed=1, confs=[0.6])
        return [a, b, c]
    ts = tasks()
    _, batch = as_batch_policy(LCF(), tm).next_batch(ts, now)
    assert [t.confidences[0] for t in batch] == [0.2, 0.6]   # low conf first
    ts = tasks()
    _, batch = as_batch_policy(EDF(), tm).next_batch(ts, now)
    assert [t.deadline for t in batch] == [0.3, 0.4]         # EDF order


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------

def test_admission_rejects_mandatory_infeasible():
    tm = BatchTimeModel.linear((0.010, 0.010, 0.010), (1, 2), marginal=0.5)
    adm = AdmissionController(tm, mode="reject")
    t = mk_task(0.005)                           # mandatory needs 10ms
    dec = adm.apply([], t, 0.0)
    assert not dec.admitted and dec.reason == "mandatory-infeasible"
    assert t.dropped and adm.rejected == 1


def test_admission_caps_depth_to_feasible():
    tm = BatchTimeModel.linear((0.010, 0.010, 0.010), (1, 2), marginal=0.5)
    adm = AdmissionController(tm, mode="depth_cap")
    t = mk_task(0.025)                           # 2 stages fit, 3 don't
    dec = adm.apply([], t, 0.0)
    assert dec.admitted and t.depth_cap == 2
    # policies clamp against the cap
    EDF().on_arrival([t], t, 0.0)
    assert t.assigned_depth == 2


def test_admission_off_is_noop():
    tm = BatchTimeModel.linear((0.010,), (1,))
    t = mk_task(0.001, times=(0.010,))
    dec = AdmissionController(tm, mode="off").apply([], t, 0.0)
    assert dec.admitted and t.depth_cap is None


# ---------------------------------------------------------------------------
# padded batched stage_forward == per-sample stage_forward
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def anytime_model(rng):
    cfg = get_config("anytime-classifier")
    return cfg, init_params(cfg, rng)


def test_padded_batch_matches_per_sample(anytime_model, rng):
    cfg, params = anytime_model
    n_valid, bucket = 3, 4
    inputs = make_inputs(cfg, jax.random.PRNGKey(3), n_valid, 12)
    singles = split_rows(inputs, n_valid)
    fns = BatchedStageFns(cfg, buckets=(1, bucket))

    # reference: per-sample unbatched stage chain
    ref = []
    for x in singles:
        h = x
        outs = []
        for s in range(cfg.num_stages):
            h, lg, cf = stage_forward(cfg, params, s, h, mode="train")
            outs.append((np.asarray(lg), np.asarray(cf)))
        ref.append(outs)

    # batched: padded to `bucket`, valid rows must match exactly
    hs = singles
    for s in range(cfg.num_stages):
        h_out, logits, conf, mask = fns.run(s, params, hs)
        assert mask.sum() == n_valid and mask.shape == (bucket,)
        logits, conf = np.asarray(logits), np.asarray(conf)
        for i in range(n_valid):
            lg_ref, cf_ref = ref[i][s]
            np.testing.assert_allclose(logits[i], lg_ref[0],
                                       rtol=1e-4, atol=1e-4)
            np.testing.assert_allclose(conf[i], cf_ref[0],
                                       rtol=1e-4, atol=1e-4)
        hs = split_rows(h_out, n_valid)


def test_pad_batch_shapes_and_mask():
    xs = [{"a": np.full((1, 2), i, np.float32)} for i in range(3)]
    batched, mask = pad_batch(xs, 8)
    assert batched["a"].shape == (8, 2)
    assert list(mask) == [True] * 3 + [False] * 5
    assert np.all(np.asarray(batched["a"][2:]) == 2)         # pad = last row
    assert bucket_for(3, (1, 2, 4, 8)) == 4
    with pytest.raises(ValueError):
        bucket_for(9, (1, 2, 4, 8))


def test_staging_buffers_match_legacy_pad_batch():
    # regression: staged batch formation must produce identical shapes,
    # masks, and values to the legacy re-stacking path
    from repro.serving.batch import StagingBuffers
    st = StagingBuffers()
    for n in (1, 3, 4):
        xs = [{"a": np.full((1, 2), i, np.float32),
               "b": np.full((1, 3, 2), 10 + i, np.int32)} for i in range(n)]
        legacy, lm = pad_batch(xs, 4)
        staged, sm = pad_batch(xs, 4, staging=st)
        assert np.array_equal(lm, sm)
        for k in ("a", "b"):
            assert staged[k].shape == np.asarray(legacy[k]).shape
            assert staged[k].dtype == np.asarray(legacy[k]).dtype
            assert np.array_equal(np.asarray(legacy[k]), staged[k])


def test_staging_buffers_reuse_no_realloc():
    # steady state: same bucket + leaf structure -> the very same numpy
    # buffers and the very same (cached, read-only) mask every dispatch
    from repro.serving.batch import StagingBuffers
    st = StagingBuffers()
    mk = lambda v: {"a": np.full((1, 2), v, np.float32)}
    b1, m1 = st.stage([mk(0), mk(1)], 4)
    b2, m2 = st.stage([mk(5), mk(6)], 4)
    assert b1["a"] is b2["a"] and m1 is m2
    assert not m1.flags.writeable
    assert np.all(b2["a"][:2] == [[5, 5], [6, 6]])
    assert np.all(b2["a"][2:] == 6)                          # pad = last row
    # a different valid count re-pads in place with a fresh cached mask
    b3, m3 = st.stage([mk(9)], 4)
    assert b3["a"] is b1["a"] and m3 is not m1
    assert np.all(b3["a"] == 9)


def test_batched_stage_fns_staging_results_stable(anytime_model):
    # BatchedStageFns.run with its built-in staging gives bitwise-identical
    # outputs dispatch after dispatch (buffer reuse must not leak rows)
    cfg, params = anytime_model
    inputs = make_inputs(cfg, jax.random.PRNGKey(7), 2, 12)
    singles = split_rows(inputs, 2)
    fns = BatchedStageFns(cfg, buckets=(1, 4))
    outs = []
    for _ in range(2):
        h, lg, cf, mask = fns.run(0, params, singles)
        outs.append((np.asarray(lg), np.asarray(cf), mask.copy()))
    assert np.array_equal(outs[0][0], outs[1][0])
    assert np.array_equal(outs[0][1], outs[1][1])
    assert np.array_equal(outs[0][2], outs[1][2])


# ---------------------------------------------------------------------------
# closed-loop semantics (satellite: reissue at completion, not deadline)
# ---------------------------------------------------------------------------

def test_closed_loop_reissues_at_completion():
    """One client, huge deadlines: request i+1 must be issued right when
    request i completes, not when its deadline would have expired."""
    conf, correct = oracle_tables(600)
    wl = Workload(n_clients=1, d_lo=1.0, d_hi=1.0, n_requests=5, seed=3)
    st = (0.004, 0.007, 0.010)
    res = serve_closed_loop(
        ServeSpec(batching={"mode": "none", "stage_times": st}), EDF(), wl,
        conf, correct)
    assert res.miss_rate == 0.0
    arrivals = sorted(f["arrival"] for f in res.per_request)
    gaps = np.diff(arrivals)
    # EDF runs every stage: turnaround = 21ms << the 1s deadline
    assert np.all(gaps < 0.1), f"client waited for its deadline: {gaps}"
    assert np.allclose(gaps, sum(st), atol=1e-6)


# ---------------------------------------------------------------------------
# closed loop: batching strictly beats unbatched serving
# ---------------------------------------------------------------------------

def test_batched_sim_beats_unbatched_throughput():
    """Deterministic overload run: the batched path sustains >= 3x the
    goodput of the unbatched path at no-worse miss rate and accuracy."""
    conf, correct = oracle_tables(600)
    st = (0.004, 0.007, 0.010)
    tm = BatchTimeModel.linear(st, (1, 2, 4, 8, 16), marginal=0.15)
    wl = Workload(n_clients=64, d_lo=0.01, d_hi=0.3, n_requests=500, seed=0)

    def policy():
        return RTDeepIoT(make_predictor("exp", prior_curve=conf.mean(0)))

    res_u = serve_closed_loop(
        ServeSpec(batching={"mode": "none", "stage_times": st}), policy(),
        wl, conf, correct)
    res_b = serve_closed_loop(ServeSpec(), policy(), wl, conf, correct,
                              time_model=tm)
    assert res_b.throughput >= 3.0 * res_u.throughput, \
        f"batched {res_b.throughput:.1f} req/s vs unbatched " \
        f"{res_u.throughput:.1f} req/s"
    assert res_b.miss_rate <= res_u.miss_rate
    assert res_b.accuracy >= res_u.accuracy - 0.01


def test_batched_sim_respects_wrapped_policy_depth():
    """Batched EDF still serves every request to full depth when load is
    light — batching must not change *what* is computed, only how."""
    conf, correct = oracle_tables(600)
    st = (0.001, 0.001, 0.001)
    tm = BatchTimeModel.linear(st, (1, 2, 4), marginal=0.1)
    wl = Workload(n_clients=2, d_lo=0.5, d_hi=0.5, n_requests=20, seed=1)
    res = serve_closed_loop(ServeSpec(), EDF(), wl, conf, correct,
                            time_model=tm)
    assert res.miss_rate == 0.0
    assert res.mean_depth == pytest.approx(3.0)


def test_oracle_executor_rejects_stage_count_mismatch():
    conf, correct = oracle_tables(600)
    tm = BatchTimeModel.linear((0.004, 0.007), (1, 2))
    wl = Workload(n_clients=2, n_requests=4, seed=0)
    with pytest.raises(ValueError, match="time model has 2 stages"):
        serve_closed_loop(ServeSpec(), EDF(), wl, conf, correct,
                          time_model=tm)


def test_batched_sim_admission_reduces_wasted_work():
    """Under overload, rejecting infeasible arrivals must not hurt goodput
    and every rejected request is accounted as a miss."""
    conf, correct = oracle_tables(600)
    st = (0.004, 0.007, 0.010)
    tm = BatchTimeModel.linear(st, (1, 2, 4, 8, 16), marginal=0.15)
    wl = Workload(n_clients=64, d_lo=0.01, d_hi=0.3, n_requests=400, seed=0)
    adm = AdmissionController(tm, mode="reject", headroom=1.0)
    res = serve_closed_loop(ServeSpec(), EDF(), wl, conf, correct,
                            time_model=tm, admission=adm)
    n_rej = sum(1 for f in res.per_request if f.get("rejected"))
    assert n_rej == adm.rejected
    for f in res.per_request:
        if f.get("rejected"):
            assert f["missed"] and f["depth"] == 0
    assert res.n_requests == wl.n_requests


def test_wrapped_policy_telemetry_passthrough():
    conf, _ = oracle_tables(600)
    tm = BatchTimeModel.linear((0.004, 0.007, 0.010), (1, 2, 4))
    base = RTDeepIoT(make_predictor("exp", prior_curve=conf.mean(0)))
    pol = as_batch_policy(base, tm)
    assert isinstance(pol, BatchedPolicy)
    assert pol.name == f"batched-{base.name}"
    assert pol.sched_time == base.sched_time
    assert as_batch_policy(pol, tm) is pol                   # idempotent
