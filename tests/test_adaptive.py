"""Tests for the adaptive-control subsystem (repro.serving.adaptive).

* offset extraction from every arrival record the repo produces (arrays,
  per_request rows, trace JSONL, journal directories)
* workload fits: each ArrivalProcess kind's parameters are recovered
  within tolerance from its own traces; ``fit_report`` identifies the
  generating kind for all four kinds (property tests ride hypothesis)
* OnlineCurveEstimator: converges to the oracle mean table, stays
  monotone-in-depth under arbitrary observations, per-key isolation,
  decayed forgetting, JSON round trip
* AdaptivePredictor honors the UtilityPredictor contract (measured
  prefix, monotone learned suffix); ``rtdeepiot-adaptive`` runs through
  the Service facade and warms a shared estimator resource
* PredictiveAdmissionController: forecast-capped / forecast-overload
  decisions carry the numbers behind the rule into the obs audit log;
  spec-level ``admission["forecast"]`` wiring and validation
* TrafficDriver: wall-clock pacing into Service.submit() [wallclock],
  materialization determinism vs the virtual-clock traffic source
"""
import dataclasses
import json

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.task import Task
from repro.serving import ServeSpec, Service, record_trace
from repro.serving.adaptive import (AdaptivePredictor, AdaptiveRTDeepIoT,
                                    OnlineCurveEstimator,
                                    PredictiveAdmissionController,
                                    extract_offsets, fit_arrival_process,
                                    fit_diurnal, fit_flash_crowd, fit_mmpp,
                                    fit_poisson, fit_report,
                                    predictive_admission)
from repro.serving.adaptive.driver import TrafficDriver
from repro.serving.batch import BatchTimeModel
from repro.serving.registry import available
from repro.serving.traffic import load_trace, make_arrival_process
from repro.serving.traffic.scenarios import scenario_spec

from conftest import oracle_tables

STAGE_TIMES = (0.004, 0.007, 0.010)

ARRIVAL_CONFIGS = {
    "poisson": dict(rate=80.0),
    "mmpp": dict(rate_on=300.0, rate_off=40.0, mean_on=0.4, mean_off=1.2),
    "diurnal": dict(base_rate=40.0, peak_rate=200.0, period=4.0),
    "flash-crowd": dict(base_rate=60.0, spike_rate=400.0, spike_at=1.0,
                        spike_len=1.0),
}


def sample_offsets(kind, seed=0, n=2000):
    p = make_arrival_process(kind, **ARRIVAL_CONFIGS[kind])
    return p.sample(np.random.default_rng(seed), n=n)


def mk_task(deadline, times=STAGE_TIMES, mandatory=1, now=0.0, model=None):
    t = Task(arrival=now, deadline=deadline, stage_times=tuple(times),
             mandatory=mandatory, model=model)
    t.assigned_depth = t.num_stages
    return t


# ---------------------------------------------------------------------------
# extract_offsets: one reader for every arrival record
# ---------------------------------------------------------------------------

def test_extract_offsets_sorts_plain_arrays():
    got = extract_offsets([0.3, 0.1, 0.2])
    assert got.tolist() == [0.1, 0.2, 0.3]


def test_extract_offsets_per_request_rows_prefer_offset():
    rows = [{"offset": 0.2, "arrival": 9.0}, {"arrival": 0.1}]
    assert extract_offsets(rows).tolist() == [0.1, 0.2]


def test_extract_offsets_from_recorded_trace(tmp_path):
    conf, correct = oracle_tables(200)
    spec = scenario_spec("steady", stage_times=STAGE_TIMES, n_requests=40,
                         seed=1)
    res = Service.from_spec(spec, conf_table=conf,
                            correct_table=correct).run()
    path = str(tmp_path / "trace.jsonl")
    record_trace(res, path, source="traffic", spec=spec)
    offs = extract_offsets(path)
    want = np.sort([r["offset"] for r in res.per_request])
    assert np.allclose(offs, want)
    # the in-memory event list reads the same
    _, events = load_trace(path)
    assert np.allclose(extract_offsets(events), want)


def test_extract_offsets_journal_dir_counts_submits_only(tmp_path):
    from repro.serving.plane import Journal
    d = str(tmp_path / "wal")
    with Journal(d) as j:
        for i in range(10):
            j.append("SUBMIT", offset=0.01 * i, sample=i,
                     request_id=f"r{i}", rel_deadline=0.2)
            j.append("RETIRE", offset=0.01 * i + 0.005, sample=i,
                     request_id=f"r{i}")
    offs = extract_offsets(d)
    assert len(offs) == 10                      # RETIREs are not arrivals
    assert np.allclose(offs, 0.01 * np.arange(10))


def test_fit_needs_enough_arrivals(tmp_path):
    with pytest.raises(ValueError, match="need >="):
        fit_poisson([0.0, 1.0])
    with pytest.raises(ValueError, match="span zero"):
        fit_poisson([1.0] * 20)
    with pytest.raises(ValueError, match="no wal-"):
        extract_offsets(tmp_path)               # a dir with no segments


# ---------------------------------------------------------------------------
# workload fits: parameter recovery per kind
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_fit_poisson_recovers_rate(seed):
    f = fit_poisson(sample_offsets("poisson", seed, n=1500))
    assert f["kind"] == "poisson"
    assert f["rate"] == pytest.approx(80.0, rel=0.10)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_fit_mmpp_recovers_state_rates_and_dwells(seed):
    f = fit_mmpp(sample_offsets("mmpp", seed, n=2500))
    cfg = ARRIVAL_CONFIGS["mmpp"]
    assert f["rate_on"] == pytest.approx(cfg["rate_on"], rel=0.15)
    assert f["rate_off"] == pytest.approx(cfg["rate_off"], rel=0.30)
    # dwell means are the hard part (few on/off cycles per trace): accept
    # a factor-2.5 band, but the on/off ordering must be unambiguous
    assert cfg["mean_on"] / 2.5 < f["mean_on"] < cfg["mean_on"] * 2.5
    assert cfg["mean_off"] / 2.5 < f["mean_off"] < cfg["mean_off"] * 2.5
    assert f["rate_on"] > 2 * f["rate_off"]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_fit_diurnal_recovers_period_and_peak(seed):
    f = fit_diurnal(sample_offsets("diurnal", seed, n=2500))
    cfg = ARRIVAL_CONFIGS["diurnal"]
    assert f["period"] == pytest.approx(cfg["period"], rel=0.10)
    assert f["peak_rate"] == pytest.approx(cfg["peak_rate"], rel=0.15)
    assert f["base_rate"] < f["peak_rate"]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_fit_flash_crowd_recovers_spike(seed):
    f = fit_flash_crowd(sample_offsets("flash-crowd", seed, n=2000))
    cfg = ARRIVAL_CONFIGS["flash-crowd"]
    assert f["base_rate"] == pytest.approx(cfg["base_rate"], rel=0.10)
    assert f["spike_rate"] == pytest.approx(cfg["spike_rate"], rel=0.20)
    assert abs(f["spike_at"] - cfg["spike_at"]) < 0.15
    assert f["spike_len"] == pytest.approx(cfg["spike_len"], rel=0.20)


def test_fit_flash_crowd_without_spike_degenerates_to_base():
    f = fit_flash_crowd(np.linspace(0.0, 10.0, 400))   # perfectly flat
    assert f["spike_len"] == 0.0
    assert f["spike_rate"] == f["base_rate"]


@pytest.mark.parametrize("kind", sorted(ARRIVAL_CONFIGS))
@pytest.mark.parametrize("seed", [0, 1])
def test_fit_report_identifies_the_generating_kind(kind, seed):
    rep = fit_report(sample_offsets(kind, seed))
    assert rep["best"] == kind, rep["scores"]
    assert set(rep["fits"]) == set(ARRIVAL_CONFIGS)
    assert set(rep["scores"]) == set(ARRIVAL_CONFIGS)
    assert rep["n_arrivals"] == 2000
    # every fitted dict round-trips through the generator factory
    for f in rep["fits"].values():
        make_arrival_process(**f)


def test_fit_arrival_process_returns_best_process():
    p = fit_arrival_process(sample_offsets("diurnal", 0))
    assert p.to_dict()["kind"] == "diurnal"
    assert p.mean_rate == pytest.approx(120.0, rel=0.15)   # (40+200)/2


@given(rate=st.floats(min_value=20.0, max_value=300.0),
       seed=st.integers(min_value=0, max_value=2**31 - 1))
@settings(max_examples=15, deadline=None)
def test_fit_poisson_recovery_property(rate, seed):
    """Property: any homogeneous rate is recovered within 15% from 1200
    arrivals (MLE conditioning on the first arrival)."""
    p = make_arrival_process("poisson", rate=rate)
    offs = p.sample(np.random.default_rng(seed), n=1200)
    assert fit_poisson(offs)["rate"] == pytest.approx(rate, rel=0.15)


@given(period=st.floats(min_value=2.0, max_value=6.0),
       seed=st.integers(min_value=0, max_value=2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_fit_diurnal_period_recovery_property(period, seed):
    """Property: the Rayleigh scan recovers any period that fits >= ~2
    observed cycles, within 15%."""
    p = make_arrival_process("diurnal", base_rate=40.0, peak_rate=200.0,
                             period=period)
    offs = p.sample(np.random.default_rng(seed), n=2500)
    if (offs[-1] - offs[0]) / period < 2.0:    # under-observed cycle
        return
    assert fit_diurnal(offs)["period"] == pytest.approx(period, rel=0.15)


# ---------------------------------------------------------------------------
# OnlineCurveEstimator
# ---------------------------------------------------------------------------

def test_estimator_converges_to_oracle_mean():
    oracle, _ = oracle_tables(600)
    est = OnlineCurveEstimator(num_stages=3, prior_weight=0.0)
    for row in oracle:
        est.observe_exits(row)
    learned = est.curve()
    assert np.abs(learned - oracle.mean(0)).max() < 0.05
    assert np.all(np.diff(learned) >= 0)
    assert est.n_observed == oracle.size


def test_estimator_unseen_key_falls_back_to_prior():
    prior = [0.4, 0.6, 0.8]
    est = OnlineCurveEstimator(num_stages=3, prior=prior)
    assert est.curve("never-seen").tolist() == prior
    assert est.weight("never-seen").tolist() == [0.0] * 3


def test_estimator_keys_are_isolated():
    est = OnlineCurveEstimator(num_stages=2, prior=[0.5, 0.5],
                               prior_weight=0.0)
    for _ in range(50):
        est.observe_exits([0.2, 0.3], key="a")
        est.observe_exits([0.8, 0.9], key="b")
    assert est.curve("a")[1] < 0.4 < 0.8 <= est.curve("b")[1]
    assert sorted(est.keys()) == ["a", "b"]


def test_estimator_decay_forgets_the_old_regime():
    est = OnlineCurveEstimator(num_stages=1, prior=[0.5], decay=0.1,
                               prior_weight=0.0)
    for _ in range(200):
        est.observe(1, 0.9)
    for _ in range(100):
        est.observe(1, 0.3)            # regime shift
    assert est.curve()[0] < 0.35       # ~10-obs window: old regime gone


def test_estimator_curve_is_prior_blended_pseudo_count():
    est = OnlineCurveEstimator(num_stages=1, prior=[0.5], decay=0.0,
                               prior_weight=4.0)
    est.observe(1, 1.0)
    # (1.0 + 4 * 0.5) / (1 + 4)
    assert est.curve()[0] == pytest.approx(3.0 / 5.0)


def test_estimator_round_trips_through_json():
    est = OnlineCurveEstimator(num_stages=3, prior=[0.4, 0.6, 0.8])
    for _ in range(30):
        est.observe_exits([0.5, 0.7, 0.9])            # global (None) key
        est.observe_exits([0.3, 0.5, 0.6], key="llm")
    d = json.loads(json.dumps(est.to_dict()))
    back = OnlineCurveEstimator.from_dict(d)
    for key in (None, "llm"):
        assert np.allclose(back.curve(key), est.curve(key))
        assert np.allclose(back.weight(key), est.weight(key))


def test_estimator_validates_inputs():
    with pytest.raises(ValueError, match="num_stages"):
        OnlineCurveEstimator(num_stages=0)
    with pytest.raises(ValueError, match="decay"):
        OnlineCurveEstimator(num_stages=2, decay=1.0)
    with pytest.raises(ValueError, match="entries"):
        OnlineCurveEstimator(num_stages=2, prior=[0.5])
    est = OnlineCurveEstimator(num_stages=2)
    with pytest.raises(ValueError, match="depth"):
        est.observe(3, 0.5)


@given(st.lists(st.tuples(st.integers(min_value=1, max_value=4),
                          st.floats(min_value=0.0, max_value=1.0)),
                max_size=60))
@settings(max_examples=25, deadline=None)
def test_estimator_curve_always_monotone_in_unit_interval(obs):
    """Property: whatever (depth, conf) sequence is observed, every
    readable curve stays monotone non-decreasing inside [0, 1] — the
    shape the FPTAS utility tables require."""
    est = OnlineCurveEstimator(num_stages=4, decay=0.05)
    for depth, conf in obs:
        est.observe(depth, conf)
    c = est.curve()
    assert np.all((0.0 <= c) & (c <= 1.0))
    assert np.all(np.diff(c) >= 0)


# ---------------------------------------------------------------------------
# AdaptivePredictor / AdaptiveRTDeepIoT
# ---------------------------------------------------------------------------

def test_adaptive_predictor_measured_prefix_wins():
    est = OnlineCurveEstimator(num_stages=3, prior=[0.4, 0.6, 0.8])
    pred = AdaptivePredictor(est)
    t = mk_task(deadline=1.0)
    t.executed, t.confidences = 2, [0.33, 0.44]
    assert pred.predict(t, 1) == pytest.approx(0.33)
    assert pred.predict(t, 2) == pytest.approx(0.44)


def test_adaptive_predictor_suffix_is_monotone_and_anchored():
    est = OnlineCurveEstimator(num_stages=3, prior=[0.4, 0.6, 0.8],
                               prior_weight=1.0)
    pred = AdaptivePredictor(est)
    t = mk_task(deadline=1.0)
    t.executed, t.confidences = 1, [0.9]      # task runs hot vs the curve
    p2, p3 = pred.predict(t, 2), pred.predict(t, 3)
    assert 0.9 <= p2 <= p3 <= 1.0             # never below last measured
    # fresh task with no measurements reads the curve directly
    t2 = mk_task(deadline=1.0)
    assert pred.predict(t2, 3) == pytest.approx(est.curve()[2])


def test_adaptive_policy_is_registered_and_learns_through_service():
    assert "rtdeepiot-adaptive" in available("policy")
    conf, correct = oracle_tables(200)
    est = OnlineCurveEstimator(num_stages=3, prior=conf.mean(0))
    spec = scenario_spec("steady", policy="rtdeepiot-adaptive",
                         stage_times=STAGE_TIMES, n_requests=60, seed=2)
    res = Service.from_spec(spec, conf_table=conf, correct_table=correct,
                            curve_estimator=est).run()
    assert res.n_requests == 60
    assert est.n_observed > 0                 # stage exits fed the tables
    w1 = est.n_observed
    # the same resource keeps its warmth across a rebuild
    res2 = Service.from_spec(spec, conf_table=conf, correct_table=correct,
                             curve_estimator=est).run()
    assert res2.n_requests == 60
    assert est.n_observed > w1


def test_adaptive_scheduler_observes_before_replanning():
    est = OnlineCurveEstimator(num_stages=3, prior=[0.4, 0.6, 0.8],
                               prior_weight=0.0)
    sched = AdaptiveRTDeepIoT(est, key_fn=lambda t: t.model)
    t = mk_task(deadline=1.0, model="llm")
    t.executed, t.confidences = 1, [0.77]
    sched.on_stage_done([t], t, now=0.01)
    assert est.weight("llm")[0] == pytest.approx(1.0)
    assert est.curve("llm")[0] == pytest.approx(0.77)


# ---------------------------------------------------------------------------
# PredictiveAdmissionController
# ---------------------------------------------------------------------------

def _tm():
    return BatchTimeModel.linear(STAGE_TIMES, (1,))


def test_predictive_without_process_matches_reactive_base():
    tm = _tm()
    base = PredictiveAdmissionController(tm, mode="depth_cap")
    t = mk_task(deadline=0.5)
    dec = base.decide([], t, 0.0)
    assert dec.admitted and dec.reason in ("ok", "deadline-capped")
    assert base.forecasted == 0


def test_forecast_below_capacity_changes_nothing():
    proc = make_arrival_process("poisson", rate=1.0)   # way under capacity
    ctl = PredictiveAdmissionController(_tm(), mode="depth_cap",
                                        process=proc)
    dec = ctl.decide([], mk_task(deadline=5.0), 0.0)
    assert dec.reason == "ok" and ctl.forecasted == 0


def test_forecast_capped_pins_to_mandatory_with_detail():
    nominal = 1.0 / sum(STAGE_TIMES)
    proc = make_arrival_process("poisson", rate=nominal * 3)
    ctl = PredictiveAdmissionController(_tm(), mode="depth_cap",
                                        process=proc, horizon=0.2)
    t = mk_task(deadline=5.0, mandatory=1)
    dec = ctl.decide([], t, 0.0)
    assert dec.admitted and dec.depth_cap == 1
    assert dec.reason == "forecast-capped"
    for k in ("forecast_rate", "capacity", "margin", "horizon", "slack"):
        assert k in dec.detail
    assert dec.detail["forecast_rate"] == pytest.approx(nominal * 3)
    assert ctl.forecasted == 1


def test_forecast_overload_rejects_when_slack_cannot_absorb_burst():
    nominal = 1.0 / sum(STAGE_TIMES)
    proc = make_arrival_process("poisson", rate=nominal * 20)
    ctl = PredictiveAdmissionController(_tm(), mode="reject",
                                        process=proc, horizon=0.5)
    tight = mk_task(deadline=0.08, mandatory=2)
    dec = ctl.decide([], tight, 0.0)
    assert not dec.admitted and dec.reason == "forecast-overload"
    assert dec.detail["expected_work"] > 0
    # a very lax deadline absorbs the same burst
    lax = mk_task(deadline=50.0, mandatory=2)
    assert ctl.decide([], lax, 0.0).admitted


def test_forecast_margin_gates_the_rule():
    nominal = 1.0 / sum(STAGE_TIMES)
    proc = make_arrival_process("poisson", rate=nominal * 1.5)
    loose = PredictiveAdmissionController(_tm(), mode="depth_cap",
                                          process=proc, margin=2.0)
    assert loose.decide([], mk_task(deadline=5.0), 0.0).reason == "ok"
    tight = PredictiveAdmissionController(_tm(), mode="depth_cap",
                                         process=proc, margin=1.0)
    assert tight.decide([], mk_task(deadline=5.0),
                        0.0).reason == "forecast-capped"


def test_forecast_rate_mmpp_falls_back_to_mean_rate():
    proc = make_arrival_process("mmpp", **ARRIVAL_CONFIGS["mmpp"])
    ctl = PredictiveAdmissionController(_tm(), process=proc)
    assert ctl.forecast_rate(0.0) == pytest.approx(proc.mean_rate)


def test_forecast_rate_leads_a_flash_crowd():
    proc = make_arrival_process("flash-crowd", base_rate=10.0,
                                spike_rate=500.0, spike_at=1.0,
                                spike_len=0.5)
    ctl = PredictiveAdmissionController(_tm(), process=proc, horizon=0.3)
    assert ctl.forecast_rate(0.2) == pytest.approx(10.0)
    assert ctl.forecast_rate(0.85) > 100.0    # sees the spike coming


def test_from_config_parses_spec_dict_and_defaults_capacity():
    fc = {"process": {"kind": "poisson", "rate": 9.0}, "horizon": 0.4,
          "margin": 1.25}
    ctl = PredictiveAdmissionController.from_config(
        _tm(), {"mode": "reject", "forecast": fc})
    assert ctl.mode == "reject"
    assert (ctl.horizon, ctl.margin) == (0.4, 1.25)
    assert ctl.capacity == pytest.approx(1.0 / sum(STAGE_TIMES))
    assert ctl.process.mean_rate == pytest.approx(9.0)


def test_predictive_admission_factory_composes_with_the_zoo():
    from repro.serving.zoo import ZooAdmissionController
    fc = {"process": {"kind": "poisson", "rate": 9.0}}
    ctl = predictive_admission(_tm(), {"mode": "depth_cap", "forecast": fc},
                               base_cls=ZooAdmissionController)
    assert isinstance(ctl, PredictiveAdmissionController)
    assert isinstance(ctl, ZooAdmissionController)


def test_spec_validates_forecast_shape():
    base = scenario_spec("steady", stage_times=STAGE_TIMES, n_requests=8)
    bad = dataclasses.replace(base, admission={"forecast": {"horizon": 1}})
    with pytest.raises(ValueError, match="forecast"):
        bad.validate()
    worse = dataclasses.replace(
        base, admission={"forecast": {"process": {"kind": "nope"}}})
    with pytest.raises(ValueError, match="forecast"):
        worse.validate()
    ok = dataclasses.replace(
        base,
        admission={"forecast": {"process": {"kind": "poisson", "rate": 5}}})
    ok.validate()


def test_forecast_decisions_reach_the_audit_log():
    """End-to-end: a flash-crowd run with a fitted forecast leaves
    forecast-capped rows in the obs audit log, numbers attached."""
    conf, correct = oracle_tables(200)
    nominal = 1.0 / sum(STAGE_TIMES)
    fc = {"process": {"kind": "flash-crowd", "base_rate": 0.2 * nominal,
                      "spike_rate": 5.0 * nominal, "spike_at": 0.3,
                      "spike_len": 0.5},
          "horizon": 0.25}
    spec = scenario_spec("flash-crowd", stage_times=STAGE_TIMES,
                         n_requests=120, seed=3,
                         admission={"mode": "depth_cap", "forecast": fc},
                         trace={"enabled": True})
    svc = Service.from_spec(spec, conf_table=conf, correct_table=correct)
    res = svc.run()
    assert res.capped > 0
    rows = [r for r in svc.obs.audit_log if r["rule"] == "forecast-capped"]
    assert rows, "forecast rule never fired during the spike"
    for r in rows:
        assert r["detail"]["forecast_rate"] > r["detail"]["capacity"]
        assert r["detail"]["horizon"] == pytest.approx(0.25)


def test_forecast_only_admission_defaults_to_depth_cap():
    conf, correct = oracle_tables(200)
    nominal = 1.0 / sum(STAGE_TIMES)
    fc = {"process": {"kind": "poisson", "rate": 3.0 * nominal}}
    spec = scenario_spec("steady", stage_times=STAGE_TIMES, n_requests=40,
                         seed=0, admission={"forecast": fc})
    res = Service.from_spec(spec, conf_table=conf,
                            correct_table=correct).run()
    assert res.capped == res.n_requests       # every admit forecast-capped


# ---------------------------------------------------------------------------
# TrafficDriver (wall-clock)
# ---------------------------------------------------------------------------

def _live_spec():
    return ServeSpec(
        policy="edf", executor="oracle", clock="wall", source="live",
        batching={"mode": "none", "stage_times": [0.001, 0.001, 0.001]},
        slo_classes={"gold": {"rel_deadline": 2.0}}, default_slo="gold")


def test_driver_materialization_matches_the_virtual_source():
    """Same (arrival, mix, seed) -> the driver's pre-materialized stream
    carries exactly the offsets the virtual-clock source would."""
    arrival = {"kind": "poisson", "rate": 50.0}
    svc = object()                               # never submitted to
    drv = TrafficDriver(svc, arrival=dict(arrival), n_samples=32,
                        n_requests=24, seed=7)
    proc = make_arrival_process(**arrival)
    want = proc.sample(np.random.default_rng(7), n=24)
    got = [off for off, _req in drv.stream]
    assert np.allclose(got, want)
    # and twice the same seed -> identical requests
    drv2 = TrafficDriver(svc, arrival=dict(arrival), n_samples=32,
                         n_requests=24, seed=7)
    assert [r.sample for _o, r in drv.stream] \
        == [r.sample for _o, r in drv2.stream]


def test_driver_argument_validation():
    svc = object()
    with pytest.raises(ValueError, match="speed"):
        TrafficDriver(svc, offsets=[0.0], n_samples=4, speed=0.0)
    with pytest.raises(ValueError, match="arrival"):
        TrafficDriver(svc, n_samples=4)
    with pytest.raises(ValueError, match="n_requests"):
        TrafficDriver(svc, arrival={"kind": "poisson", "rate": 5.0},
                      n_samples=4)
    with pytest.raises(ValueError, match="n_samples"):
        TrafficDriver(svc, offsets=[0.0, 0.1])


@pytest.mark.wallclock
def test_driver_paces_submissions_into_a_live_service():
    from conftest import wait_until
    conf, correct = oracle_tables(200)
    with Service.from_spec(_live_spec(), conf_table=conf,
                           correct_table=correct) as svc:
        drv = TrafficDriver(svc, arrival={"kind": "poisson", "rate": 200.0},
                            n_samples=conf.shape[0], n_requests=25, seed=1,
                            speed=4.0).start()
        assert drv.join(timeout=30.0)
        assert drv.submitted == 25
        wait_until(lambda: all(h.done() for h in drv.handles),
                   desc="all driver handles resolved")
        met = svc.drain()
    assert met.n_requests == 25


@pytest.mark.wallclock
def test_driver_stop_aborts_pacing_quickly():
    conf, correct = oracle_tables(200)
    with Service.from_spec(_live_spec(), conf_table=conf,
                           correct_table=correct) as svc:
        # 10 rps unscaled: the full stream would take ~2s; stop instead
        drv = TrafficDriver(svc, arrival={"kind": "poisson", "rate": 10.0},
                            n_samples=conf.shape[0], n_requests=20,
                            seed=0).start()
        drv.stop()
        assert drv.join(timeout=10.0)
        assert drv.submitted < 20
        svc.drain()


@pytest.mark.wallclock
def test_driver_replays_a_recorded_trace_live(tmp_path):
    conf, correct = oracle_tables(200)
    spec = scenario_spec("steady", stage_times=STAGE_TIMES, n_requests=12,
                         seed=5)
    res = Service.from_spec(spec, conf_table=conf,
                            correct_table=correct).run()
    path = str(tmp_path / "t.jsonl")
    record_trace(res, path, source="traffic", spec=spec)
    _, events = load_trace(path)
    from repro.serving.traffic.scenarios import SLO_CLASSES
    live = dataclasses.replace(_live_spec(),
                               slo_classes=dict(SLO_CLASSES))
    with Service.from_spec(live, conf_table=conf,
                           correct_table=correct) as svc:
        drv = TrafficDriver.from_trace(svc, events, speed=8.0)
        assert drv.run() == 12
        met = svc.drain()
    assert met.n_requests == 12
    assert sorted(r["sample"] for r in met.per_request) \
        == sorted(r["sample"] for r in res.per_request)


# ---------------------------------------------------------------------------
# the loop closed: record -> fit -> forecast beats reactive on the replay
# ---------------------------------------------------------------------------

def test_fitted_forecast_arms_admission_from_yesterdays_trace():
    """The adaptive story end to end on the virtual clock: record a
    flash-crowd day, fit it, arm admission with the fit, and replay a
    different seed of the same process — the forecast rule fires."""
    conf, correct = oracle_tables(200)
    # enough requests that the spike *ends* inside the trace — on a
    # spike-truncated record an on/off MMPP explains the data just as well
    rec_spec = scenario_spec("flash-crowd", stage_times=STAGE_TIMES,
                             n_requests=600, seed=11)
    rec = Service.from_spec(rec_spec, conf_table=conf,
                            correct_table=correct).run()
    fit = fit_report([r["offset"] for r in rec.per_request])
    assert fit["best"] == "flash-crowd"
    spec = scenario_spec(
        "flash-crowd", stage_times=STAGE_TIMES, n_requests=150, seed=12,
        admission={"mode": "depth_cap",
                   "forecast": {"process": fit["fits"][fit["best"]],
                                "horizon": 0.25}},
        trace={"enabled": True})
    svc = Service.from_spec(spec, conf_table=conf, correct_table=correct)
    res = svc.run()
    assert res.n_requests == 150
    assert any(r["rule"] == "forecast-capped" for r in svc.obs.audit_log)
