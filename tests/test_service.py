"""Tests for the public serving API (repro.serving.service / .registry).

* ServeSpec round trip (dict + JSON) and validation errors
* registry: custom policy registered and served end-to-end without
  touching core modules; component-instance resources skip the registry
* SLO classes, ResponseHandle futures (result / stages / cancel) in both
  virtual-buffered and wall-clock live modes
* ServiceMetrics superset (per-class, admission counts, to_json) and
  SimResult.to_dict
* AdmissionController decision boundaries and StreamSource zero-slack /
  simultaneous-arrival ordering (previously untested edges)
"""
import json
from concurrent.futures import CancelledError

import numpy as np
import pytest

from repro.core import EDF, Task, Workload
from repro.core.schedulers import Policy
from repro.serving import (AdmissionController, BatchTimeModel, Request,
                           ServeSpec, Service)
from repro.serving.registry import available, register_policy, resolve
from repro.serving.runtime.sources import StreamSource

from conftest import oracle_tables, serve_closed_loop

STAGE_TIMES = (0.004, 0.007, 0.010)


def base_spec(**overrides):
    kw = dict(policy="edf", executor="oracle", clock="virtual",
              source="closed-loop",
              batching={"mode": "none", "stage_times": list(STAGE_TIMES)})
    kw.update(overrides)
    return ServeSpec(**kw)


# ---------------------------------------------------------------------------
# ServeSpec round trip + validation
# ---------------------------------------------------------------------------

def test_servespec_round_trips_dict_and_json():
    spec = ServeSpec(
        policy="rtdeepiot", policy_args={"predictor": "exp", "delta": 0.05},
        executor="oracle", clock="virtual", source="closed-loop",
        batching={"buckets": [1, 2, 4], "marginal": 0.2,
                  "stage_times": list(STAGE_TIMES)},
        admission={"mode": "depth_cap", "headroom": 1.2},
        slo_classes={"gold": {"rel_deadline": 0.5, "utility_weight": 2.0},
                     "bronze": {"rel_deadline": 0.05, "depth_cap": 1}},
        default_slo="gold", pipeline_depth=2, dispatch_overhead=1e-4,
        policy_cost=5e-4, charge_overhead=True, host_overhead=1e-5)
    d = spec.to_dict()
    assert ServeSpec.from_dict(d) == spec
    assert ServeSpec.from_json(spec.to_json()) == spec
    assert json.loads(spec.to_json())["slo_classes"]["bronze"]["depth_cap"] == 1


def test_servespec_rejects_unknown_keys_and_bad_values():
    with pytest.raises(ValueError, match="unknown ServeSpec keys"):
        ServeSpec.from_dict({"policyy": "edf"})
    with pytest.raises(KeyError, match="no policy registered"):
        base_spec(policy="definitely-not-registered").validate()
    with pytest.raises(ValueError, match="pipeline_depth"):
        base_spec(pipeline_depth=0).validate()
    with pytest.raises(ValueError, match="admission mode"):
        base_spec(admission={"mode": "maybe"}).validate()
    with pytest.raises(ValueError, match="rel_deadline"):
        base_spec(slo_classes={"gold": {"rel_deadline": -1}}).validate()
    with pytest.raises(ValueError, match="default_slo"):
        base_spec(default_slo="gold").validate()


def test_registry_resolve_errors_list_available():
    with pytest.raises(KeyError, match="available"):
        resolve("executor", "nope")
    assert "oracle" in available("executor")
    assert {"rtdeepiot", "edf", "lcf", "rr"} <= set(available("policy"))


# ---------------------------------------------------------------------------
# registry: custom policy end-to-end (no core modules touched)
# ---------------------------------------------------------------------------

def test_registry_custom_policy_end_to_end():
    class DeepestFirst(Policy):
        """Always finish the most-advanced task first."""
        name = "deepest-first"

        def next_task(self, active, now):
            r = self._runnable(active, now)
            return max(r, key=lambda t: (t.executed, -t.tid)) if r else None

    register_policy("test-deepest-first", lambda args, ctx: DeepestFirst())
    conf, correct = oracle_tables(120)
    wl = Workload(n_clients=6, d_lo=0.05, d_hi=0.3, n_requests=40, seed=3)
    spec = base_spec(policy="test-deepest-first")
    res = Service.from_spec(spec, workload=wl, conf_table=conf,
                            correct_table=correct).run()
    assert res.n_requests == 40
    assert res.miss_rate < 1.0
    assert res.components["policy"] == "test-deepest-first"


# ---------------------------------------------------------------------------
# SLO classes + futures (virtual-buffered live mode)
# ---------------------------------------------------------------------------

SLO_SPEC = dict(
    policy="edf", executor="oracle", clock="virtual", source="live",
    batching={"mode": "none", "stage_times": list(STAGE_TIMES)},
    slo_classes={"gold": {"rel_deadline": 0.5, "utility_weight": 2.0},
                 "bronze": {"rel_deadline": 0.05, "depth_cap": 1}},
    default_slo="gold")


def test_slo_classes_and_futures_virtual():
    conf, correct = oracle_tables(120)
    svc = Service.from_spec(ServeSpec(**SLO_SPEC), conf_table=conf,
                            correct_table=correct)
    h_gold = svc.submit(Request(None, sample=3), at=0.0)
    h_bronze = svc.submit(Request(None, sample=7), slo="bronze", at=0.0)
    assert not h_gold.done()
    met = svc.drain()
    r_gold, r_bronze = h_gold.result(), h_bronze.result()
    # gold: generous deadline, full depth, weight applied to the task
    assert r_gold.depth == 3 and r_gold.slo == "gold"
    assert h_gold._task.weight == 2.0
    # bronze: depth-capped at 1 by its SLO class
    assert r_bronze.depth == 1 and r_bronze.slo == "bronze"
    assert h_bronze._task.depth_cap == 1
    # stages(): one StageExit per in-time anytime exit, in depth order
    exits = list(h_gold.stages())
    assert [e.depth for e in exits] == [1, 2, 3]
    assert all(0.0 <= e.confidence <= 1.0 for e in exits)
    assert [e.depth for e in h_bronze.stages()] == [1]
    # per-class metrics
    assert met.per_class["gold"]["n"] == 1
    assert met.per_class["bronze"]["mean_depth"] == 1.0
    assert met.components["source"] == "live"


def test_submit_unknown_slo_rejected_and_cancel():
    conf, correct = oracle_tables(120)
    svc = Service.from_spec(ServeSpec(**SLO_SPEC), conf_table=conf,
                            correct_table=correct)
    with pytest.raises(KeyError, match="unknown SLO class"):
        svc.submit(Request(None, sample=0), slo="platinum")
    h1 = svc.submit(Request(None, sample=1), at=0.0)
    h2 = svc.submit(Request(None, sample=2), at=0.0)
    assert h2.cancel() and h2.cancelled()
    assert not h2.cancel()                      # already cancelled
    met = svc.drain()
    assert h1.result().depth == 3
    with pytest.raises(CancelledError):
        h2.result()
    assert not h1.cancel()                      # already resolved
    assert met.n_requests == 1 and met.cancelled == 1
    svc.close()
    with pytest.raises(RuntimeError, match="closed"):
        svc.submit(Request(None, sample=3))


def test_run_refuses_live_source_and_submit_refuses_batch_source():
    conf, correct = oracle_tables(120)
    svc = Service.from_spec(ServeSpec(**SLO_SPEC), conf_table=conf,
                            correct_table=correct)
    with pytest.raises(RuntimeError, match="submit"):
        svc.run()
    svc2 = Service.from_spec(base_spec(), conf_table=conf,
                             correct_table=correct)
    with pytest.raises(RuntimeError, match="live"):
        svc2.submit(Request(None, sample=0))


# ---------------------------------------------------------------------------
# wall-clock live mode (background engine thread, oracle executor)
# ---------------------------------------------------------------------------

@pytest.mark.wallclock
def test_live_wall_clock_service_serves_submissions():
    conf, correct = oracle_tables(120)
    spec = ServeSpec(
        policy="edf", executor="oracle", clock="wall", source="live",
        batching={"mode": "none", "stage_times": [0.002, 0.002, 0.002]},
        slo_classes={"gold": {"rel_deadline": 0.5}}, default_slo="gold")
    with Service.from_spec(spec, conf_table=conf,
                           correct_table=correct) as svc:
        handles = [svc.submit(Request(None, sample=i)) for i in range(6)]
        results = [h.result(timeout=10.0) for h in handles]
        assert all(r.depth == 3 and not r.missed for r in results)
        # streaming exits landed for every request
        assert all([e.depth for e in h.stages()] == [1, 2, 3]
                   for h in handles)
        met = svc.drain()
    assert met.n_requests == 6
    assert met.miss_rate == 0.0
    assert met.makespan > 0.0


@pytest.mark.wallclock
def test_live_engine_failure_fans_out_to_handles():
    """An engine-thread crash must not strand result() waiters: every
    outstanding handle unblocks with the error, and drain() re-raises."""
    from repro.serving import OracleExecutor

    class ExplodingExecutor(OracleExecutor):
        def submit(self, stage, tasks, now):
            raise RuntimeError("boom")

    conf, correct = oracle_tables(120)
    tm = BatchTimeModel.linear(STAGE_TIMES, (1,))
    spec = ServeSpec(
        policy="edf", executor="oracle", clock="wall", source="live",
        slo_classes={"gold": {"rel_deadline": 0.5}}, default_slo="gold")
    svc = Service.from_spec(spec, executor=ExplodingExecutor(tm, conf),
                            time_model=tm, conf_table=conf,
                            correct_table=correct)
    h = svc.submit(Request(None, sample=0))
    with pytest.raises(RuntimeError, match="engine failed"):
        h.result(timeout=10.0)
    with pytest.raises(RuntimeError, match="failed while live"):
        svc.drain()


def test_submit_without_any_deadline_fails_fast():
    conf, correct = oracle_tables(120)
    spec = ServeSpec(
        policy="edf", executor="oracle", clock="virtual", source="live",
        batching={"mode": "none", "stage_times": list(STAGE_TIMES)})
    svc = Service.from_spec(spec, conf_table=conf, correct_table=correct)
    with pytest.raises(ValueError, match="no rel_deadline"):
        svc.submit(Request(None, sample=0))      # no SLO classes defined


# ---------------------------------------------------------------------------
# metrics: superset structure + JSON export
# ---------------------------------------------------------------------------

def test_service_metrics_superset_and_json():
    conf, correct = oracle_tables(120)
    wl = Workload(n_clients=16, d_lo=0.01, d_hi=0.1, n_requests=60, seed=1)
    spec = base_spec(
        batching={"buckets": [1, 2, 4], "stage_times": list(STAGE_TIMES)},
        admission={"mode": "reject"})
    met = Service.from_spec(spec, workload=wl, conf_table=conf,
                            correct_table=correct).run()
    assert met.rejected > 0                    # overloaded: reject mode bites
    assert met.row()["accuracy"] == met.accuracy     # SimResult surface
    d = json.loads(met.to_json())
    assert d["components"] == dict(policy="edf", executor="oracle",
                                   clock="virtual", source="closed-loop")
    assert d["rejected"] == met.rejected
    assert "per_request" not in d
    full = met.to_dict(per_request=True)
    assert len(full["per_request"]) == met.n_requests


def test_simresult_to_dict():
    conf, correct = oracle_tables(20)
    wl = Workload(n_clients=2, n_requests=6, seed=0)
    res = serve_closed_loop(base_spec(), EDF(), wl, conf, correct)
    d = res.to_dict()
    assert d["accuracy"] == res.accuracy and "per_request" not in d
    assert set(d) >= {"miss_rate", "makespan", "throughput", "sched_charged"}


# ---------------------------------------------------------------------------
# AdmissionController decision boundaries (satellite)
# ---------------------------------------------------------------------------

def adm_tm():
    return BatchTimeModel.linear(STAGE_TIMES, (1,))


def mk_task(deadline, *, now=0.0, mandatory=1):
    return Task(arrival=now, deadline=deadline, stage_times=STAGE_TIMES,
                mandatory=mandatory)


def test_admission_mandatory_infeasible_boundary():
    adm = AdmissionController(adm_tm(), mode="reject")
    # mandatory part solo = 0.004: just below is rejected ...
    dec = adm.decide([], mk_task(0.0039), 0.0)
    assert not dec.admitted and dec.reason == "mandatory-infeasible"
    # ... exactly equal is admitted (deadline met with zero slack)
    dec = adm.decide([], mk_task(0.004), 0.0)
    assert dec.admitted and dec.reason == "ok"


def test_admission_overload_reject_vs_depth_cap_boundary():
    # two active tasks owe their mandatory stage: backlog = 2 * 0.004 at
    # the best amortized rate; own mandatory = 0.004 -> pressure = 0.012
    active = [mk_task(1.0), mk_task(1.0)]
    t_in = mk_task(0.012)       # deadline == pressure: NOT overloaded (>)
    t_out = mk_task(0.0119)     # strictly inside: overloaded
    rej = AdmissionController(adm_tm(), mode="reject")
    cap = AdmissionController(adm_tm(), mode="depth_cap")
    dec = rej.decide(active, t_out, 0.0)
    assert not dec.admitted and dec.reason == "overload"
    dec = cap.decide(active, t_out, 0.0)
    assert dec.admitted and dec.depth_cap == t_out.mandatory
    assert dec.reason == "overload-capped"
    dec = rej.decide(active, t_in, 0.0)
    assert dec.admitted
    # headroom > 1 shifts the boundary: the equality case now rejects
    dec = AdmissionController(adm_tm(), mode="reject",
                              headroom=1.01).decide(active, t_in, 0.0)
    assert not dec.admitted and dec.reason == "overload"


def test_admission_depth_cap_solo_feasibility():
    cap = AdmissionController(adm_tm(), mode="depth_cap")
    # 0.004 / 0.011 / 0.021 cumulative: deadline 0.012 -> depth 2 only
    dec = cap.decide([], mk_task(0.012), 0.0)
    assert dec.admitted and dec.depth_cap == 2
    assert dec.reason == "deadline-capped"
    # deadline covers the full pipeline -> uncapped
    dec = cap.decide([], mk_task(0.021), 0.0)
    assert dec.admitted and dec.depth_cap is None and dec.reason == "ok"


def test_admission_apply_mutates_task_and_counters():
    cap = AdmissionController(adm_tm(), mode="depth_cap")
    t = mk_task(0.012)
    dec = cap.apply([], t, 0.0)
    assert dec.admitted and t.depth_cap == 2 and cap.capped == 1
    rej = AdmissionController(adm_tm(), mode="reject")
    t2 = mk_task(0.001)
    dec = rej.apply([], t2, 0.0)
    assert not dec.admitted and t2.dropped and rej.rejected == 1


# ---------------------------------------------------------------------------
# StreamSource edges (satellite)
# ---------------------------------------------------------------------------

def test_stream_source_simultaneous_arrivals_preserve_insertion_order():
    reqs = [(0.0, Request(None, 0.5, sample=10)),
            (0.0, Request(None, 0.5, sample=11)),
            (0.0, Request(None, 0.5, sample=12))]
    src = StreamSource(reqs, lambda req, now: req)
    assert src.has_pending() and src.next_time() == 0.0
    popped = [src.pop(0.0).sample for _ in range(3)]
    assert popped == [10, 11, 12]          # stable sort: insertion order
    assert not src.has_pending()
    assert src.next_time() == np.inf


def test_stream_source_zero_slack_request_is_counted_as_miss():
    """A request whose deadline equals its arrival (zero slack after the
    §II-B adjustment) must still flow through admit -> expire -> retire as
    a depth-0 miss, not be dropped silently; simultaneous arrivals keep
    insertion order in the task ids."""
    conf, correct = oracle_tables(120)
    spec = base_spec(source="stream")
    reqs = [(0.0, Request(None, 0.0, sample=1)),       # zero slack: miss
            (0.0, Request(None, 0.5, sample=2)),
            (0.01, Request(None, 0.5, sample=3))]
    res = Service.from_spec(spec, conf_table=conf,
                            correct_table=correct).run(reqs)
    assert res.n_requests == 3
    by_sample = {r["sample"]: r for r in res.per_request}
    assert by_sample[1]["missed"] and by_sample[1]["depth"] == 0
    assert not by_sample[2]["missed"] and not by_sample[3]["missed"]
    # the two t=0 arrivals were admitted in insertion order
    assert by_sample[1]["tid"] < by_sample[2]["tid"] < by_sample[3]["tid"]
