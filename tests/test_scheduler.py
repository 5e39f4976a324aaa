"""Core scheduler tests: Algorithm 1 FPTAS, Eq. 7 greedy, EDF dispatch,
utility predictors — including hypothesis property tests against the
exhaustive optimum (Theorem 1's (1-ε) bound)."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (EDF, LCF, RR, DepthPlanner, RTDeepIoT, Task,
                        Workload, brute_force_plan, greedy_update,
                        make_predictor)
from repro.core.utility import ExpIncrease, LinIncrease, MaxIncrease
from repro.serving import ServeSpec

from conftest import serve_closed_loop

PRIOR = [0.5, 0.75, 0.875]


def mk_task(deadline, times, executed=0, confs=(), mandatory=1, sample=0):
    t = Task(arrival=0.0, deadline=deadline, stage_times=tuple(times),
             mandatory=mandatory, sample=sample)
    t.executed = executed
    t.confidences = list(confs)
    return t


# ---------------------------------------------------------------------------
# utility predictors
# ---------------------------------------------------------------------------

def test_exp_predictor_halves_distance():
    p = ExpIncrease(PRIOR)
    t = mk_task(1.0, [0.1] * 4, executed=2, confs=[0.4, 0.6])
    assert p.predict(t, 2) == pytest.approx(0.6)
    assert p.predict(t, 3) == pytest.approx(0.8)      # 0.6 + 0.5*0.4
    assert p.predict(t, 4) == pytest.approx(0.9)


def test_max_predictor_jumps_to_one():
    p = MaxIncrease(PRIOR)
    t = mk_task(1.0, [0.1] * 3, executed=1, confs=[0.3])
    assert p.predict(t, 2) == 1.0
    assert p.predict(t, 3) == 1.0
    assert p.predict(t, 1) == pytest.approx(0.3)


def test_lin_predictor_time_proportional():
    p = LinIncrease(PRIOR)
    t = mk_task(1.0, [0.1, 0.1, 0.2], executed=1, confs=[0.4])
    assert p.predict(t, 2) == pytest.approx(0.8)      # 0.4 * 0.2/0.1
    assert p.predict(t, 3) == pytest.approx(1.0)      # capped


def test_predictor_curves_monotone():
    for name in ("exp", "max", "lin"):
        p = make_predictor(name, prior_curve=PRIOR)
        t = mk_task(1.0, [0.1] * 3, executed=1, confs=[0.5])
        c = p.curve(t)
        assert all(c[i] <= c[i + 1] + 1e-9 for i in range(len(c) - 1))


# ---------------------------------------------------------------------------
# Algorithm 1 (DP / FPTAS)
# ---------------------------------------------------------------------------

def test_dp_single_task_runs_to_max_reward():
    p = make_predictor("exp", prior_curve=PRIOR)
    t = mk_task(deadline=1.0, times=[0.1, 0.1, 0.1])
    plan = DepthPlanner(delta=0.01).plan([t], 0.0, p)
    assert plan[t.tid] == 3


def test_dp_respects_deadline():
    p = make_predictor("exp", prior_curve=PRIOR)
    t = mk_task(deadline=0.15, times=[0.1, 0.1, 0.1])
    plan = DepthPlanner(delta=0.01).plan([t], 0.0, p)
    assert plan[t.tid] == 1


def test_dp_infeasible_task_dropped():
    p = make_predictor("exp", prior_curve=PRIOR)
    t = mk_task(deadline=0.05, times=[0.1, 0.1, 0.1])
    plan = DepthPlanner(delta=0.01).plan([t], 0.0, p)
    assert plan[t.tid] == 0


def test_dp_prefers_high_value_under_contention():
    """Two tasks, time for only one to go deep: the one with more headroom
    (lower current confidence under Exp) gets the stages."""
    p = make_predictor("exp", prior_curve=PRIOR)
    # time for exactly ONE extra stage across both tasks (EDF: a before b)
    a = mk_task(0.16, [0.15, 0.15, 0.15], executed=1, confs=[0.95], sample=0)
    b = mk_task(0.16, [0.15, 0.15, 0.15], executed=1, confs=[0.30], sample=1)
    plan = DepthPlanner(delta=0.01).plan([a, b], 0.0, p)
    # b's next stage is worth +0.35; a's only +0.025
    assert plan[b.tid] == 2
    assert plan[a.tid] == 1


def test_dp_edf_prefix_feasibility():
    """Chosen depths must be schedulable as EDF prefixes."""
    p = make_predictor("exp", prior_curve=PRIOR)
    rng = np.random.default_rng(42)
    tasks = [mk_task(float(rng.uniform(0.05, 0.5)),
                     rng.uniform(0.01, 0.08, 3), sample=i)
             for i in range(8)]
    plan = DepthPlanner(delta=0.05).plan(tasks, 0.0, p)
    cum = 0.0
    for t in sorted(tasks, key=lambda t: t.deadline):
        d = plan[t.tid]
        if d > 0:
            cum += t.cum_time(d)
            assert cum <= t.deadline + 1e-9


@settings(max_examples=60, deadline=None)
@given(st.data())
def test_fptas_bound_property(data):
    """Theorem 1: with Δ = εR/N the DP achieves >= (1-ε) of the exhaustive
    optimum (exact rewards, random instances, random partial execution)."""
    n = data.draw(st.integers(1, 4))
    eps = data.draw(st.sampled_from([0.05, 0.1, 0.25]))
    rng_seed = data.draw(st.integers(0, 10**6))
    rng = np.random.default_rng(rng_seed)
    p = make_predictor("exp", prior_curve=PRIOR)
    tasks = []
    for i in range(n):
        L = int(rng.integers(1, 4))
        t = mk_task(float(rng.uniform(0.02, 0.6)),
                    rng.uniform(0.01, 0.1, L), sample=i)
        if rng.uniform() < 0.4 and L >= 1:
            t.executed = 1
            t.confidences = [float(rng.uniform(0.2, 0.9))]
        tasks.append(t)
    delta = eps * 1.0 / n
    plan = DepthPlanner(delta=delta).plan(tasks, 0.0, p)
    reward = sum(p.predict(t, plan[t.tid]) for t in tasks if plan[t.tid] > 0)
    opt, _ = brute_force_plan(tasks, 0.0, p)
    assert reward >= (1 - eps) * opt - 1e-9


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 10**6))
def test_dp_incremental_matches_fresh(seed):
    """Incremental row reuse (Algorithm 1's from-k update) must equal a
    from-scratch plan."""
    rng = np.random.default_rng(seed)
    p = make_predictor("exp", prior_curve=PRIOR)
    planner = DepthPlanner(delta=0.1)
    tasks = []
    for i in range(6):
        tasks.append(mk_task(float(rng.uniform(0.05, 0.5)),
                             rng.uniform(0.01, 0.08, 3), sample=i))
        inc = planner.plan(tasks, 0.0, p)
        fresh = DepthPlanner(delta=0.1).plan(tasks, 0.0, p)
        assert inc == fresh


# ---------------------------------------------------------------------------
# greedy reassignment (Eq. 7)
# ---------------------------------------------------------------------------

def test_greedy_swaps_when_other_task_gains_more():
    p = make_predictor("exp", prior_curve=PRIOR)
    cur = mk_task(0.2, [0.05] * 3, executed=1, confs=[0.96])
    cur.assigned_depth = 3                          # 2 stages remaining = 0.1
    other = mk_task(0.4, [0.05] * 3, executed=1, confs=[0.3])
    other.assigned_depth = 1
    assert greedy_update(cur, [other], p)
    assert cur.assigned_depth == 1                  # stopped early
    assert other.assigned_depth >= 2                # got the budget


def test_greedy_keeps_plan_when_current_best():
    p = make_predictor("exp", prior_curve=PRIOR)
    cur = mk_task(0.2, [0.05] * 3, executed=1, confs=[0.3])
    cur.assigned_depth = 3
    other = mk_task(0.4, [0.05] * 3, executed=1, confs=[0.96])
    other.assigned_depth = 1
    assert not greedy_update(cur, [other], p)
    assert cur.assigned_depth == 3


def test_greedy_budget_constraint():
    """Swap target must fit within the freed budget (Eq. 7 s.t. clause)."""
    p = make_predictor("exp", prior_curve=PRIOR)
    cur = mk_task(0.2, [0.01, 0.01, 0.01], executed=1, confs=[0.9])
    cur.assigned_depth = 2                          # budget = 0.01
    other = mk_task(0.4, [0.01, 0.5, 0.5], executed=1, confs=[0.1])
    other.assigned_depth = 1                        # next stage costs 0.5
    assert not greedy_update(cur, [other], p)


# ---------------------------------------------------------------------------
# policies + simulator
# ---------------------------------------------------------------------------

def _oracle(n_samples=150, L=3, seed=0):
    rng = np.random.default_rng(seed)
    base = rng.uniform(0.3, 0.9, (n_samples, 1))
    conf = np.clip(base + rng.uniform(0.02, 0.3, (n_samples, L)).cumsum(1),
                   0, 1)
    correct = rng.uniform(size=(n_samples, L)) < conf
    return conf, correct


def unbatched_spec(times):
    return ServeSpec(batching={"mode": "none", "stage_times": times})


def test_simulator_no_load_no_misses():
    """With generous deadlines everything completes at full depth."""
    conf, correct = _oracle()
    wl = Workload(n_clients=2, d_lo=1.0, d_hi=2.0, n_requests=40)
    res = serve_closed_loop(unbatched_spec([0.01] * 3), EDF(), wl, conf,
                            correct)
    assert res.miss_rate == 0.0
    assert res.mean_depth == pytest.approx(3.0)


def test_rtdeepiot_beats_edf_under_overload():
    conf, correct = _oracle()
    wl = Workload(n_clients=12, d_lo=0.02, d_hi=0.15, n_requests=400)
    times = [0.02] * 3
    r_rt = serve_closed_loop(
        unbatched_spec(times),
        RTDeepIoT(make_predictor("exp", prior_curve=conf.mean(0))), wl, conf,
        correct)
    r_edf = serve_closed_loop(unbatched_spec(times), EDF(), wl, conf, correct)
    assert r_rt.accuracy > r_edf.accuracy
    assert r_rt.miss_rate < r_edf.miss_rate


def test_oracle_upper_bounds_heuristics():
    conf, correct = _oracle(seed=3)
    wl = Workload(n_clients=10, d_lo=0.02, d_hi=0.2, n_requests=400, seed=1)
    times = [0.02] * 3
    accs = {}
    for name in ("exp", "max", "lin", "oracle"):
        pred = make_predictor(name, prior_curve=conf.mean(0),
                              oracle_table=conf if name == "oracle" else None)
        accs[name] = serve_closed_loop(unbatched_spec(times), RTDeepIoT(pred),
                                       wl, conf, correct).accuracy
    assert accs["oracle"] >= max(accs["exp"], accs["lin"]) - 0.03


def test_policies_never_run_past_deadline_start():
    """No stage is *dispatched* for a task whose deadline has passed."""
    conf, correct = _oracle()
    wl = Workload(n_clients=8, d_lo=0.01, d_hi=0.1, n_requests=200)
    for pol in (EDF(), LCF(), RR(),
                RTDeepIoT(make_predictor("exp", prior_curve=conf.mean(0)))):
        res = serve_closed_loop(unbatched_spec([0.02] * 3), pol, wl, conf,
                                correct)
        for f in res.per_request:
            assert f["depth"] <= 3


def test_stage_counts_monotone_with_load():
    """More clients -> lower mean depth under RTDeepIoT (shedding kicks in)."""
    conf, correct = _oracle()
    times = [0.02] * 3
    depths = []
    for k in (2, 20):
        wl = Workload(n_clients=k, d_lo=0.02, d_hi=0.2, n_requests=300)
        pred = make_predictor("exp", prior_curve=conf.mean(0))
        depths.append(serve_closed_loop(unbatched_spec(times), RTDeepIoT(pred),
                                        wl, conf, correct).mean_depth)
    assert depths[1] <= depths[0] + 1e-9


# ---------------------------------------------------------------------------
# weighted accuracy (paper §II-A: "trivial to extend to weighted accuracy")
# ---------------------------------------------------------------------------

def test_weighted_task_wins_contention():
    """Under contention, a 3x-important task gets the depth budget."""
    p = make_predictor("exp", prior_curve=PRIOR)
    a = mk_task(0.16, [0.15] * 3, executed=1, confs=[0.5], sample=0)
    b = mk_task(0.16, [0.15] * 3, executed=1, confs=[0.5], sample=1)
    b.weight = 3.0
    plan = DepthPlanner(delta=0.01).plan([a, b], 0.0, p)
    assert plan[b.tid] == 2 and plan[a.tid] == 1


def test_weighted_fptas_bound_vs_bruteforce():
    """FPTAS bound still holds with weights (brute force sees them via the
    predictor curve x weight in the DP options)."""
    import numpy as np
    rng = np.random.default_rng(5)
    p = make_predictor("exp", prior_curve=PRIOR)
    tasks = []
    for i in range(4):
        t = mk_task(float(rng.uniform(0.05, 0.4)),
                    rng.uniform(0.01, 0.08, 3), sample=i)
        t.weight = float(rng.choice([1.0, 2.0]))
        tasks.append(t)
    plan = DepthPlanner(delta=0.02).plan(tasks, 0.0, p)
    reward = sum(t.weight * p.predict(t, plan[t.tid])
                 for t in tasks if plan[t.tid] > 0)
    # exhaustive search with weights
    import itertools
    best = 0.0
    choice_sets = []
    for t in tasks:
        opts = [(0, 0.0, 0.0)]
        for l in range(1, 4):
            opts.append((l, t.cum_time(l), t.weight * p.predict(t, l)))
        choice_sets.append(opts)
    for combo in itertools.product(*choice_sets):
        cum, rew, ok = 0.0, 0.0, True
        for t, (d, c, r) in zip(sorted(tasks, key=lambda t: t.deadline),
                                [combo[sorted(tasks, key=lambda t: t.deadline).index(t)] for t in sorted(tasks, key=lambda t: t.deadline)]):
            if d > 0:
                cum += c
                if cum > t.deadline:
                    ok = False
                    break
            rew += r if d > 0 else 0.0
        if ok:
            best = max(best, rew)
    assert reward >= (1 - 0.15) * best - 1e-9


# ---------------------------------------------------------------------------
# simulator invariants
# ---------------------------------------------------------------------------

def test_simulator_work_conserving_and_causal():
    """No request finishes before its arrival; every returned depth is
    consistent with the virtual time available."""
    conf, correct = _oracle(seed=11)
    wl = Workload(n_clients=10, d_lo=0.02, d_hi=0.2, n_requests=300, seed=2)
    pred = make_predictor("exp", prior_curve=conf.mean(0))
    res = serve_closed_loop(unbatched_spec([0.01, 0.02, 0.03]),
                            RTDeepIoT(pred), wl, conf, correct)
    for f in res.per_request:
        assert f["deadline"] > f["arrival"]
        # a request can never execute more stages than fit in its window
        max_possible = 0
        t = 0.0
        for st in (0.01, 0.02, 0.03):
            t += st
            if t <= (f["deadline"] - f["arrival"]) + 1e-9:
                max_possible += 1
        assert f["depth"] <= 3
    assert res.n_requests == 300
