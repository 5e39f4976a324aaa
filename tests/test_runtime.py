"""Tests for the unified serving runtime (repro.serving.runtime).

Golden parity: fixed-seed closed-loop results on the oracle executor
(accuracy, miss rate, mean depth, mean confidence, makespan, throughput),
unbatched and micro-batched, must equal the values the original
discrete-event simulators produced (PR 1 tree) on exactly this workload,
for RTDeepIoT, EDF, LCF and RR — with the policy passed as an instance
and with the policy built by the registry from a JSON spec.

Plus: unified host-cost accounting on both dispatch disciplines, the
pipelined dispatch deadline-safety invariant, the pipelined-vs-synchronous
overhead claim on a deterministic cost model, and wall-clock engine smoke
via the runtime.
"""
import numpy as np
import pytest

from repro.core import (EDF, LCF, RR, RTDeepIoT, Task, Workload,
                        make_predictor)
from repro.serving import ServeSpec, Service
from repro.serving.batch import BatchTimeModel
from repro.serving.runtime import OracleExecutor

from conftest import oracle_tables, serve_closed_loop

STAGE_TIMES = (0.004, 0.007, 0.010)


def time_model():
    return BatchTimeModel.linear(STAGE_TIMES, (1, 2, 4, 8, 16), marginal=0.15)


def mk_policy(name, conf):
    if name == "rtdeepiot":
        return RTDeepIoT(make_predictor("exp", prior_curve=conf.mean(0)))
    return {"edf": EDF, "lcf": LCF, "rr": RR}[name]()


def golden_workload():
    return Workload(n_clients=24, d_lo=0.01, d_hi=0.3, n_requests=300, seed=0)


def unbatched():
    return {"mode": "none", "stage_times": list(STAGE_TIMES)}


# ---------------------------------------------------------------------------
# golden parity: runtime == the original simulators, bit for bit
# ---------------------------------------------------------------------------

# (accuracy, miss_rate, mean_depth, mean_conf, makespan, throughput) —
# recorded from the original loops at the fixed-seed workload above
GOLDEN = {
    ("rtdeepiot", "sim"): (0.5, 0.0, 1.3033333333333332, 0.5391706063341832,
                           1.8434826559500153, 162.7354610751132),
    ("rtdeepiot", "batched"): (0.6666666666666666, 0.02, 1.989795918367347,
                               0.675604053149701, 0.8953826559500192,
                               328.3512340185549),
    ("edf", "sim"): (0.21333333333333335, 0.5833333333333334, 1.384,
                     0.5355863704120423, 2.103482655950014,
                     59.425258224221096),
    ("edf", "batched"): (0.5533333333333333, 0.15666666666666668,
                         1.901185770750988, 0.6378603449394006,
                         2.004632655950016, 126.2076616626305),
    ("lcf", "sim"): (0.5833333333333334, 0.0, 1.33, 0.5507444192248545,
                     1.9854826559500154, 151.09676183822202),
    ("lcf", "batched"): (0.77, 0.01, 2.4175084175084174, 0.7558069680225269,
                         1.3820326559500191, 214.90085543299995),
    ("rr", "sim"): (0.5033333333333333, 0.14333333333333334,
                    1.4980544747081712, 0.5755582324746783,
                    2.0644826559500116, 124.486393363153),
    ("rr", "batched"): (0.74, 0.12333333333333334, 2.722433460076046,
                        0.787734774223797, 1.4284326559500187,
                        184.1178853651204),
}


@pytest.mark.parametrize("policy_name,kind", sorted(GOLDEN))
def test_golden_parity(policy_name, kind):
    """The policy *instance* rides into the Service as a resource."""
    conf, correct = oracle_tables(600)
    pol = mk_policy(policy_name, conf)
    if kind == "sim":
        res = serve_closed_loop(ServeSpec(batching=unbatched()), pol,
                                golden_workload(), conf, correct)
    else:
        res = serve_closed_loop(ServeSpec(), pol, golden_workload(), conf,
                                correct, time_model=time_model())
    acc, miss, depth, mconf, makespan, thr = GOLDEN[(policy_name, kind)]
    assert res.accuracy == pytest.approx(acc, rel=1e-12)
    assert res.miss_rate == pytest.approx(miss, rel=1e-12)
    assert res.mean_depth == pytest.approx(depth, rel=1e-12)
    assert res.mean_conf == pytest.approx(mconf, rel=1e-12)
    assert res.makespan == pytest.approx(makespan, rel=1e-12)
    assert res.throughput == pytest.approx(thr, rel=1e-12)
    assert res.n_requests == 300


@pytest.mark.parametrize("policy_name,kind", sorted(GOLDEN))
def test_golden_parity_via_servespec(policy_name, kind):
    """The same original constants, bit for bit, when the engine is
    declared as a ServeSpec (registry-built policy included) and run
    through the Service facade — for all four policies on both
    discrete-event paths.  The spec round-trips through JSON en route."""
    conf, correct = oracle_tables(600)
    pargs = {"predictor": "exp"} if policy_name == "rtdeepiot" else {}
    if kind == "sim":
        batching = unbatched()
    else:
        batching = {"buckets": [1, 2, 4, 8, 16], "marginal": 0.15,
                    "stage_times": list(STAGE_TIMES)}
    spec = ServeSpec(policy=policy_name, policy_args=pargs,
                     executor="oracle", clock="virtual",
                     source="closed-loop", batching=batching)
    spec = ServeSpec.from_json(spec.to_json())
    res = Service.from_spec(spec, workload=golden_workload(),
                            conf_table=conf, correct_table=correct).run()
    acc, miss, depth, mconf, makespan, thr = GOLDEN[(policy_name, kind)]
    assert res.accuracy == pytest.approx(acc, rel=1e-12)
    assert res.miss_rate == pytest.approx(miss, rel=1e-12)
    assert res.mean_depth == pytest.approx(depth, rel=1e-12)
    assert res.mean_conf == pytest.approx(mconf, rel=1e-12)
    assert res.makespan == pytest.approx(makespan, rel=1e-12)
    assert res.throughput == pytest.approx(thr, rel=1e-12)
    assert res.n_requests == 300


def test_runtime_native_equals_shims():
    """A policy instance passed as a resource and the same policy built by
    the registry from a JSON spec give the same schedule."""
    conf, correct = oracle_tables(600)
    tm = time_model()
    r1 = serve_closed_loop(ServeSpec(), mk_policy("edf", conf),
                           golden_workload(), conf, correct, time_model=tm)
    spec = ServeSpec.from_json(ServeSpec(policy="edf").to_json())
    r2 = Service.from_spec(spec, workload=golden_workload(), time_model=tm,
                           conf_table=conf, correct_table=correct).run()
    assert r1.accuracy == r2.accuracy and r1.makespan == r2.makespan
    # identical retirement sequence (tids are a global counter — compare
    # the schedule-relevant fields instead)
    key = lambda f: (f["arrival"], f["deadline"], f["depth"], f["missed"])  # noqa: E731
    assert [key(f) for f in r1.per_request] == \
        [key(f) for f in r2.per_request]


# ---------------------------------------------------------------------------
# unified host-cost accounting
# ---------------------------------------------------------------------------

def test_charged_time_accounting_parity():
    """With a per-dispatch overhead, BOTH dispatch disciplines must report
    the charged host time.  At max_batch=1 the unbatched and the bucketed
    spec run the identical schedule, so dispatch counts (and the
    deterministic overhead component) agree."""
    conf, correct = oracle_tables(600)
    tm1 = BatchTimeModel.linear(STAGE_TIMES, (1,))
    do = 1e-3
    r_u = serve_closed_loop(
        ServeSpec(batching=unbatched(), dispatch_overhead=do),
        mk_policy("edf", conf), golden_workload(), conf, correct)
    r_b = serve_closed_loop(
        ServeSpec(batching={"max_batch": 1}, dispatch_overhead=do),
        mk_policy("edf", conf), golden_workload(), conf, correct,
        time_model=tm1)
    assert r_u.n_dispatches == r_b.n_dispatches > 0
    # same schedule → same results
    assert r_u.accuracy == r_b.accuracy
    assert r_u.makespan == r_b.makespan
    # the charged accounting includes every dispatch's overhead on BOTH paths
    assert r_u.sched_charged >= r_u.n_dispatches * do
    assert r_b.sched_charged >= r_b.n_dispatches * do
    # synchronous dispatch: every charged second serialized
    assert r_u.host_serial == pytest.approx(r_u.sched_charged)
    assert r_b.host_serial == pytest.approx(r_b.sched_charged)
    assert r_b.host_overhead_frac > 0.0


def test_charge_overhead_advances_virtual_time():
    """charge_overhead=True must stretch the timeline by the charged host
    time on the batched path too (it did only on the unbatched one)."""
    conf, correct = oracle_tables(600)
    tm = time_model()
    wl = golden_workload()
    base = serve_closed_loop(ServeSpec(dispatch_overhead=1e-3),
                             mk_policy("edf", conf), wl, conf, correct,
                             time_model=tm)
    charged = serve_closed_loop(
        ServeSpec(dispatch_overhead=1e-3, charge_overhead=True),
        mk_policy("edf", conf), wl, conf, correct, time_model=tm)
    assert charged.makespan > base.makespan


# ---------------------------------------------------------------------------
# pipelined async dispatch
# ---------------------------------------------------------------------------

class InvariantCheckingExecutor(OracleExecutor):
    """Asserts the PR-1 deadline-safety invariant at every submit: no
    co-runner admitted into a batch may be pushed past its deadline by the
    batch's bucket-rounded WCET (the leader keeps the legacy
    dispatch-anyway singleton semantics), and every member runs its actual
    next stage."""

    def __init__(self, time_model, conf_table):
        super().__init__(time_model, conf_table)
        self.checked = 0

    def submit(self, stage, tasks, now):
        w = self.time_model.wcet(stage, len(tasks))
        for i, t in enumerate(tasks):
            assert t.executed == stage
            assert t.executed < t.assigned_depth
            if i > 0:
                assert t.fits_batch(now, w), \
                    f"co-runner past deadline: slack={t.slack(now)} w={w}"
        self.checked += 1
        super().submit(stage, tasks, now)


def test_pipelined_dispatch_keeps_deadline_invariant():
    """Overloaded closed loop, pipeline_depth=2: every dispatched batch —
    pre-selected, re-validated, topped off — satisfies the batching
    deadline invariant at TRUE dispatch time, and pre-selection actually
    gets used.  The checking executor rides into the Service as a
    component-instance resource."""
    conf, correct = oracle_tables(600)
    tm = time_model()
    wl = Workload(n_clients=48, d_lo=0.01, d_hi=0.25, n_requests=400, seed=2)
    ex = InvariantCheckingExecutor(tm, conf)
    spec = ServeSpec(policy="rtdeepiot", policy_args={"predictor": "exp"},
                     executor="oracle", clock="virtual", source="closed-loop",
                     pipeline_depth=2, dispatch_overhead=1e-4,
                     policy_cost=5e-4, charge_overhead=True)
    res = Service.from_spec(spec, executor=ex, time_model=tm, workload=wl,
                            conf_table=conf, correct_table=correct).run()
    assert ex.checked == res.n_dispatches > 0
    assert res.presel_hits > 0
    assert res.n_requests == 400
    assert res.host_serial < res.sched_charged   # some host work was hidden


def test_pipelined_strictly_lower_host_overhead():
    """The async-figure claim, deterministically (modeled host costs):
    pipeline_depth=2 shows a strictly lower charged host-overhead fraction
    than synchronous batched dispatch at equal-or-better accuracy and miss
    rate, K >= 16."""
    conf, correct = oracle_tables(600)
    tm = time_model()
    for k in (16, 64):
        wl = Workload(n_clients=k, d_lo=0.01, d_hi=0.3, n_requests=600,
                      seed=0)
        kw = dict(charge_overhead=True, dispatch_overhead=1e-4,
                  policy_cost=5e-4)
        r_sync = serve_closed_loop(ServeSpec(pipeline_depth=1, **kw),
                                   mk_policy("rtdeepiot", conf), wl, conf,
                                   correct, time_model=tm)
        r_async = serve_closed_loop(ServeSpec(pipeline_depth=2, **kw),
                                    mk_policy("rtdeepiot", conf), wl, conf,
                                    correct, time_model=tm)
        assert r_async.host_overhead_frac < r_sync.host_overhead_frac, k
        assert r_async.accuracy >= r_sync.accuracy, k
        assert r_async.miss_rate <= r_sync.miss_rate, k
        # goodput stays within noise of synchronous (fewer misses, but a
        # slightly longer makespan can trade off completed-requests/s)
        assert r_async.throughput >= 0.97 * r_sync.throughput, k


def test_pipelined_noop_without_host_cost():
    """With zero modeled host cost the pipelined schedule cannot be worse
    than synchronous on goodput-relevant metrics (same device model; the
    only difference is when the policy looks at the queue)."""
    conf, correct = oracle_tables(600)
    tm = time_model()
    wl = Workload(n_clients=16, d_lo=0.02, d_hi=0.3, n_requests=300, seed=1)
    r_s = serve_closed_loop(ServeSpec(pipeline_depth=1, policy_cost=0.0),
                            mk_policy("edf", conf), wl, conf, correct,
                            time_model=tm)
    r_a = serve_closed_loop(ServeSpec(pipeline_depth=2, policy_cost=0.0),
                            mk_policy("edf", conf), wl, conf, correct,
                            time_model=tm)
    assert r_a.miss_rate <= r_s.miss_rate + 0.02
    assert r_a.accuracy >= r_s.accuracy - 0.02


# ---------------------------------------------------------------------------
# wall-clock engines through the runtime (real model, real stage fns)
# ---------------------------------------------------------------------------

@pytest.mark.slow                    # jax compile dominates; no 20x repeat
@pytest.mark.wallclock
@pytest.mark.parametrize("pipelined", [False, True])
def test_wall_clock_batched_engine_serves_all(pipelined):
    import jax
    from repro.configs import get_config
    from repro.models import init_params
    from repro.serving import closed_loop_stream
    from repro.training import DifficultyDataset

    cfg = get_config("anytime-classifier")
    params = init_params(cfg, jax.random.PRNGKey(0))
    ds = DifficultyDataset(num_classes=cfg.vocab_size, seed=0)
    test = ds.sample(30, seed=9)
    # analytic time model: scheduling decisions only need plausible prices
    tm = BatchTimeModel.linear((0.002, 0.003, 0.004), (1, 2, 4),
                               marginal=0.25)
    spec = ServeSpec(policy="rtdeepiot",
                     policy_args={"predictor": "exp",
                                  "prior_curve": [.5, .7, .85]},
                     executor="device-batched", clock="wall", source="stream",
                     pipeline_depth=2 if pipelined else 1)
    svc = Service.from_spec(spec, cfg=cfg, params=params, time_model=tm)
    stream = closed_loop_stream(test["inputs"], test["labels"], n_clients=4,
                                d_lo=0.2, d_hi=0.5, n_requests=10, seed=1)
    svc.run(stream)
    responses = svc.responses
    assert len(responses) == 10
    done = [r for r in responses if not r.missed]
    assert len(done) >= 7            # generous deadlines: most complete
    for r in done:
        assert 1 <= r.depth <= cfg.num_stages
        assert 0.0 <= r.confidence <= 1.0


# ---------------------------------------------------------------------------
# custom single-shot source injected into the Service as a resource
# ---------------------------------------------------------------------------

def test_engine_core_drains_unfinished_tasks_at_deadline():
    """A task the policy never schedules (infeasible) retires at its
    deadline and extends the makespan — Fig. 2 drain semantics."""
    conf, correct = oracle_tables(4)
    tm = BatchTimeModel.linear((0.2, 0.2, 0.2), (1,))

    class OneShotSource:
        def __init__(self):
            self.sent = False

        def has_pending(self):
            return not self.sent

        def next_time(self):
            return 0.0 if not self.sent else np.inf

        def pop(self, now):
            self.sent = True
            return Task(arrival=now, deadline=now + 0.1,
                        stage_times=(0.2, 0.2, 0.2), mandatory=1, sample=0)

        def on_retire(self, task, now):
            pass

    spec = ServeSpec(policy="rtdeepiot",
                     policy_args={"predictor": "exp",
                                  "prior_curve": [0.5, 0.7, 0.9]},
                     executor="oracle", clock="virtual", source="stream",
                     batching={"max_batch": 1})
    res = Service.from_spec(spec, source=OneShotSource(), time_model=tm,
                            conf_table=conf, correct_table=correct).run()
    assert res.n_requests == 1
    assert res.per_request[0]["missed"]
    assert res.makespan == pytest.approx(0.1)
