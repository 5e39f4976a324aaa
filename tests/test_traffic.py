"""Tests for the traffic subsystem (repro.serving.traffic).

* arrival-generator determinism (same seed => identical sequence, all
  kinds) and empirical rates vs configured means
* per-class request mixes (shares, SLO stamping, deadline ranges)
* open-loop TrafficSource end-to-end through the Service facade
  (arrival schedule independent of completions)
* trace record/replay: JSONL round trip; replay reproduces arrival order
  and admission decisions bit-for-bit under the virtual clock
* overload control: bounded live intake with reject / shed-optional
  backpressure; windowed metrics streaming (flash-crowd transient)
* live-mode cancellation after admission (deadline pull-in) [satellite]
* rtdeepiot-weighted: gold-class requests win utility under overload
  [satellite]
* StreamSource tolerates unsorted input (property test) [satellite]
"""
import dataclasses
import json

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.serving import (Request, ServeSpec, Service, record_trace,
                           scenario_spec, verify_replay)
from repro.serving.runtime.sources import StreamSource
from repro.serving.traffic import (ARRIVAL_KINDS, SCENARIOS, RequestMix,
                                   TraceRecorder, TrafficSource, load_trace,
                                   make_arrival_process, nominal_rate)

from conftest import oracle_tables

STAGE_TIMES = (0.004, 0.007, 0.010)


# ---------------------------------------------------------------------------
# generators: determinism + empirical rates (satellite)
# ---------------------------------------------------------------------------

ARRIVAL_CONFIGS = {
    "poisson": dict(rate=120.0),
    "mmpp": dict(rate_on=300.0, rate_off=40.0, mean_on=0.4, mean_off=1.2),
    "diurnal": dict(base_rate=40.0, peak_rate=200.0, period=4.0),
    "flash-crowd": dict(base_rate=60.0, spike_rate=400.0, spike_at=1.0,
                        spike_len=1.0),
}


def test_every_registered_kind_has_a_config_under_test():
    assert set(ARRIVAL_CONFIGS) == set(ARRIVAL_KINDS)


@pytest.mark.parametrize("kind", sorted(ARRIVAL_CONFIGS))
def test_same_seed_same_arrival_sequence(kind):
    p = make_arrival_process(kind, **ARRIVAL_CONFIGS[kind])
    a = p.sample(np.random.default_rng(7), n=200)
    b = p.sample(np.random.default_rng(7), n=200)
    c = p.sample(np.random.default_rng(8), n=200)
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)
    assert len(a) == 200
    assert np.all(np.diff(a) >= 0) and np.all(a >= 0)


@pytest.mark.parametrize("kind", ["poisson", "mmpp", "flash-crowd",
                                  "diurnal"])
def test_empirical_rate_within_tolerance_of_mean(kind):
    """Long-horizon empirical arrivals/second ~ the configured mean rate.

    flash-crowd's mean is defined over [0, spike_at + 2*spike_len], so it
    is sampled over exactly that window; the others average out over a
    long horizon.
    """
    p = make_arrival_process(kind, **ARRIVAL_CONFIGS[kind])
    if kind == "flash-crowd":
        horizon = p.spike_at + 2 * p.spike_len
    else:
        horizon = 60.0
    counts = [len(p.sample(np.random.default_rng(seed), horizon=horizon))
              for seed in range(5 if kind == "flash-crowd" else 3)]
    emp = np.mean(counts) / horizon
    assert emp == pytest.approx(p.mean_rate, rel=0.12)


def test_horizon_and_n_bounds_respected():
    p = make_arrival_process("poisson", rate=100.0)
    t = p.sample(np.random.default_rng(0), horizon=2.0)
    assert np.all(t < 2.0)
    t = p.sample(np.random.default_rng(0), n=50, horizon=1000.0)
    assert len(t) == 50
    with pytest.raises(ValueError, match="n and/or horizon"):
        p.sample(np.random.default_rng(0))
    with pytest.raises(KeyError, match="available"):
        make_arrival_process("fractal")


# ---------------------------------------------------------------------------
# request mixes
# ---------------------------------------------------------------------------

def test_mix_shares_slo_and_deadline_ranges():
    mix = RequestMix([{"slo": "gold", "share": 3.0},
                      {"slo": "bronze", "share": 1.0,
                       "rel_range": [0.05, 0.1]}], n_samples=50)
    rng = np.random.default_rng(0)
    reqs = [r for _, r in mix.stream(rng, np.linspace(0, 1, 400))]
    gold = [r for r in reqs if r.slo == "gold"]
    bronze = [r for r in reqs if r.slo == "bronze"]
    assert len(gold) + len(bronze) == 400
    assert 0.65 <= len(gold) / 400 <= 0.85          # ~0.75 share
    assert all(r.rel_deadline is None for r in gold)   # SLO class supplies
    assert all(0.05 <= r.rel_deadline <= 0.1 for r in bronze)
    assert all(0 <= r.sample < 50 for r in reqs)
    with pytest.raises(ValueError, match="share"):
        RequestMix([{"share": 0.0}], n_samples=5)


# ---------------------------------------------------------------------------
# open-loop source end-to-end
# ---------------------------------------------------------------------------

def test_traffic_source_is_open_loop():
    """Arrival offsets are a pure function of (arrival, mix, seed) — the
    engine's completions cannot shift them (unlike ClosedLoopSource)."""
    p = make_arrival_process("poisson", rate=200.0)
    expect = p.sample(np.random.default_rng(5), n=40)
    mix = RequestMix([{"slo": "gold"}], n_samples=10)
    src = TrafficSource(p, mix, lambda req, now: req, n_requests=40, seed=5)
    assert np.allclose(src.offsets, expect)


def test_traffic_scenario_through_service():
    conf, correct = oracle_tables(200)
    spec = scenario_spec("steady", policy="edf", stage_times=STAGE_TIMES,
                         n_requests=60, seed=2)
    assert ServeSpec.from_json(spec.to_json()) == spec    # JSON round trip
    res = Service.from_spec(spec, conf_table=conf,
                            correct_table=correct).run()
    assert res.n_requests == 60
    assert res.components["source"] == "traffic"
    # the three-tier mix showed up in the per-class breakdown
    assert set(res.per_class) <= {"gold", "silver", "bronze"}
    assert sum(c["n"] for c in res.per_class.values()) == 60
    # steady 0.6x load: nearly everything should be served in time
    assert res.miss_rate < 0.1


def test_traffic_source_requires_sizing_args():
    conf, correct = oracle_tables(200)
    spec = scenario_spec("steady", stage_times=STAGE_TIMES)
    spec.source_args.pop("n_requests")
    with pytest.raises(ValueError, match="n_requests"):
        Service.from_spec(spec, conf_table=conf, correct_table=correct).run()


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_scenario_catalog_builds_and_validates(name):
    spec = scenario_spec(name, stage_times=STAGE_TIMES, n_requests=10)
    spec.validate()
    args = spec.source_args
    nom = nominal_rate(STAGE_TIMES)
    # rates are scaled by the nominal service rate; durations stay put
    assert any(v >= 0.3 * nom for k, v in args["arrival"].items()
               if k.endswith("rate") or k == "rate")


# ---------------------------------------------------------------------------
# trace record/replay (tentpole acceptance: bit-for-bit under virtual clock)
# ---------------------------------------------------------------------------

def _replay_of(spec, metrics, conf, correct, tmp_path):
    path = str(tmp_path / "trace.jsonl")
    record_trace(metrics, path, source=spec.source, spec=spec)
    header, events = load_trace(path)
    assert header["n_events"] == len(events) == metrics.n_requests
    rspec = dataclasses.replace(spec, source="replay",
                                source_args={"path": path})
    res = Service.from_spec(rspec, conf_table=conf,
                            correct_table=correct).run()
    return header, res


def test_replay_reproduces_overloaded_run_bitwise(tmp_path):
    conf, correct = oracle_tables(200)
    spec = scenario_spec("flash-crowd", policy="rtdeepiot",
                         admission={"mode": "reject"},
                         stage_times=STAGE_TIMES, n_requests=150, seed=3)
    orig = Service.from_spec(spec, conf_table=conf,
                             correct_table=correct).run()
    assert orig.rejected > 0             # the run has admission decisions
    header, rep = _replay_of(spec, orig, conf, correct, tmp_path)
    v = verify_replay(orig.per_request, rep.per_request)
    assert v == {"arrival_order": True, "admission_decisions": True,
                 "bitwise": True}
    # headline aggregates carry over exactly
    assert rep.miss_rate == orig.miss_rate
    assert rep.rejected == orig.rejected
    assert rep.accuracy == orig.accuracy
    # the stored spec round-trips for later regression runs
    assert ServeSpec.from_dict(header["spec"]) == spec


def test_trace_jsonl_schema(tmp_path):
    conf, correct = oracle_tables(200)
    spec = scenario_spec("steady", policy="edf", stage_times=STAGE_TIMES,
                         n_requests=12, seed=0)
    res = Service.from_spec(spec, conf_table=conf,
                            correct_table=correct).run()
    path = str(tmp_path / "t.jsonl")
    record_trace(res, path, source="traffic")
    lines = [json.loads(x) for x in open(path)]
    assert lines[0]["type"] == "header" and lines[0]["n_events"] == 12
    ev = lines[1]
    assert {"offset", "sample", "client", "slo", "rel_deadline",
            "outcome"} <= set(ev)
    assert {"depth", "missed", "rejected", "latency",
            "deadline"} <= set(ev["outcome"])
    offsets = [e["offset"] for e in lines[1:]]
    assert offsets == sorted(offsets)        # admission order == arrival order


def test_replay_source_needs_a_trace():
    conf, correct = oracle_tables(200)
    spec = scenario_spec("steady", stage_times=STAGE_TIMES, n_requests=5)
    spec = dataclasses.replace(spec, source="replay", source_args={})
    with pytest.raises(KeyError, match="trace"):
        Service.from_spec(spec, conf_table=conf, correct_table=correct).run()


def test_trace_capture_of_closed_loop_run_replays_load_shape(tmp_path):
    """Closed-loop traces carry the effective (already adjusted) slack —
    replay is not bit-exact (the factory re-adjusts), but every arrival
    must survive the round trip in order."""
    from repro.core import Workload
    conf, correct = oracle_tables(200)
    spec = ServeSpec(policy="edf", executor="oracle", clock="virtual",
                     source="closed-loop",
                     batching={"mode": "none",
                               "stage_times": list(STAGE_TIMES)})
    wl = Workload(n_clients=4, d_lo=0.05, d_hi=0.3, n_requests=30, seed=1)
    res = Service.from_spec(spec, workload=wl, conf_table=conf,
                            correct_table=correct).run()
    rec = TraceRecorder(source="closed-loop")
    rec.capture(res)
    assert len(rec.events) == 30
    assert all(ev.rel_deadline is not None and ev.rel_deadline > 0
               for ev in rec.events)
    rspec = dataclasses.replace(spec, source="replay")
    rep = Service.from_spec(rspec, conf_table=conf, correct_table=correct,
                            trace=rec.events).run()
    assert rep.n_requests == 30


# ---------------------------------------------------------------------------
# overload control: bounded intake backpressure
# ---------------------------------------------------------------------------

def live_spec(**source_args):
    return ServeSpec(
        policy="edf", executor="oracle", clock="virtual", source="live",
        source_args=source_args,
        batching={"mode": "none", "stage_times": list(STAGE_TIMES)},
        slo_classes={"gold": {"rel_deadline": 0.5, "utility_weight": 2.0}},
        default_slo="gold")


def test_backpressure_reject_fails_fast():
    conf, correct = oracle_tables(200)
    svc = Service.from_spec(live_spec(bound=2, overflow="reject"),
                            conf_table=conf, correct_table=correct)
    handles = [svc.submit(Request(None, sample=i), at=0.0) for i in range(5)]
    # over-bound submissions resolve immediately, rejected, no engine trip
    assert [h.done() for h in handles] == [False, False, True, True, True]
    for h in handles[2:]:
        r = h.result()
        assert r.rejected and r.missed and r.depth == 0 and r.slo == "gold"
    met = svc.drain()
    assert met.n_requests == 2                 # only the admitted ones ran
    assert met.rejected == 3
    assert met.per_class["gold"]["rejected"] == 3
    assert met.per_class["gold"]["n"] == 2
    assert handles[0].result().depth == 3


def test_backpressure_shed_optional_drops_depth_not_requests():
    conf, correct = oracle_tables(200)
    svc = Service.from_spec(live_spec(bound=1, overflow="shed-optional"),
                            conf_table=conf, correct_table=correct)
    h1 = svc.submit(Request(None, sample=1), at=0.0)
    h2 = svc.submit(Request(None, sample=2), at=0.0)
    h3 = svc.submit(Request(None, sample=3), at=0.0)
    met = svc.drain()
    assert met.n_requests == 3 and met.rejected == 0
    assert h1.result().depth == 3              # under bound: untouched
    assert h2.result().depth == 1              # shed to mandatory
    assert h3.result().depth == 1
    assert not h2.result().missed
    assert met.capped == 2


def test_shed_pin_survives_admission_depth_cap():
    """Admission control must only ever *tighten* an existing depth cap:
    a shed-optional request pinned to mandatory stays at mandatory even
    when admission's own solo-feasibility cap would allow deeper."""
    conf, correct = oracle_tables(200)
    spec = dataclasses.replace(live_spec(bound=1, overflow="shed-optional"),
                               admission={"mode": "depth_cap"},
                               slo_classes={"gold": {"rel_deadline": 0.035}})
    svc = Service.from_spec(spec, conf_table=conf, correct_table=correct)
    h1 = svc.submit(Request(None, sample=1), at=0.0)
    h2 = svc.submit(Request(None, sample=2), at=0.0)   # over bound: shed
    svc.drain()
    # 0.035s slack allows ~depth 2 solo (admission would cap there), but
    # the shed pin to mandatory (depth 1) must win
    assert h1.result().depth >= 1
    assert h2.result().depth == 1 and not h2.result().missed


def test_slo_depth_cap_survives_admission_depth_cap():
    """Same invariant for SLO-class caps: bronze pinned to depth 1 must
    not be re-opened by admission's deadline-capped decision."""
    conf, correct = oracle_tables(200)
    spec = ServeSpec(
        policy="edf", executor="oracle", clock="virtual", source="live",
        batching={"mode": "none", "stage_times": list(STAGE_TIMES)},
        admission={"mode": "depth_cap"},
        slo_classes={"bronze": {"rel_deadline": 0.035, "depth_cap": 1}},
        default_slo="bronze")
    svc = Service.from_spec(spec, conf_table=conf, correct_table=correct)
    h = svc.submit(Request(None, sample=4), at=0.0)
    svc.drain()
    assert h.result().depth == 1


def test_backpressure_spec_validation():
    with pytest.raises(ValueError, match="overflow"):
        live_spec(bound=2, overflow="explode").validate()
    with pytest.raises(ValueError, match="bound"):
        live_spec(bound=0).validate()
    with pytest.raises(ValueError, match="metrics_interval"):
        dataclasses.replace(live_spec(), metrics_interval=-1.0).validate()


# ---------------------------------------------------------------------------
# overload control: windowed metrics streaming
# ---------------------------------------------------------------------------

def test_metrics_streaming_captures_flash_crowd_transient():
    conf, correct = oracle_tables(200)
    snaps = []
    spec = scenario_spec("flash-crowd", policy="edf",
                         stage_times=STAGE_TIMES, n_requests=150, seed=1,
                         metrics_interval=0.5)
    svc = Service.from_spec(spec, conf_table=conf, correct_table=correct,
                            on_metrics=snaps.append)
    res = svc.run()
    assert snaps and svc.snapshots == snaps
    ts = [s.t for s in snaps]
    assert ts == sorted(ts)
    assert sum(s.n for s in snaps) == res.n_requests
    for s in snaps:
        assert 0.0 <= s.utilization <= 1.0
        assert s.queue_depth >= 0
        assert s.accuracy is None or 0.0 <= s.accuracy <= 1.0
    # the spike (t in [2.0, 3.5]) must be visible as a windowed transient
    # even though it is invisible in steady pre-spike windows
    pre = [s for s in snaps if s.t <= 2.0]
    spike = [s for s in snaps if 2.0 < s.t <= 4.5]
    assert spike, "no snapshot windows covered the spike"
    assert max(s.miss_rate for s in spike) > max(
        (s.miss_rate for s in pre), default=0.0)


def test_streaming_requires_positive_interval():
    from repro.serving.traffic import MetricsStreamer
    with pytest.raises(ValueError, match="interval"):
        MetricsStreamer(0.0, None)


# ---------------------------------------------------------------------------
# live-mode cancellation after admission (satellite)
# ---------------------------------------------------------------------------

@pytest.mark.wallclock
def test_cancel_after_admission_sheds_optional_stages():
    conf, correct = oracle_tables(200)
    spec = ServeSpec(
        policy="edf", executor="oracle", clock="wall", source="live",
        batching={"mode": "none", "stage_times": [0.04, 0.04, 0.04]},
        slo_classes={"gold": {"rel_deadline": 5.0}}, default_slo="gold")
    with Service.from_spec(spec, conf_table=conf,
                           correct_table=correct) as svc:
        h = svc.submit(Request(None, sample=3))
        first = next(h.stages(timeout=10.0))     # admitted + one exit landed
        assert first.depth == 1
        assert h.cancel()                        # post-admission: pull-in
        res = h.result(timeout=10.0)
        met = svc.drain()
    # the anytime contract survives: a partial (not cancelled) result,
    # short of the full 3 stages (one in-flight stage may still commit)
    assert not h.cancelled()
    assert not res.missed
    assert 1 <= res.depth < 3
    assert met.cancelled == 1
    assert met.n_requests == 1


# ---------------------------------------------------------------------------
# rtdeepiot-weighted: gold wins utility under overload (satellite)
# ---------------------------------------------------------------------------

def test_weighted_policy_favors_gold_under_overload():
    conf, correct = oracle_tables(200)
    rate = 2.0 * nominal_rate(STAGE_TIMES)
    spec = ServeSpec(
        policy="rtdeepiot-weighted",
        policy_args={"predictor": "exp"},
        executor="oracle", clock="virtual", source="traffic",
        source_args={"arrival": {"kind": "poisson", "rate": rate},
                     "mix": [{"slo": "gold", "share": 0.5},
                             {"slo": "bronze", "share": 0.5}],
                     "n_requests": 250, "seed": 4},
        batching={"mode": "none", "stage_times": list(STAGE_TIMES)},
        # same deadline, different importance: depth is pure contention
        slo_classes={"gold": {"rel_deadline": 0.12, "utility_weight": 4.0},
                     "bronze": {"rel_deadline": 0.12,
                                "utility_weight": 1.0}})
    res = Service.from_spec(spec, conf_table=conf,
                            correct_table=correct).run()
    gold, bronze = res.per_class["gold"], res.per_class["bronze"]
    assert res.components["policy"] == "rtdeepiot-weighted"
    # contended optional stages go to the heavy class
    assert gold["mean_depth"] > bronze["mean_depth"]
    assert gold["miss_rate"] <= bronze["miss_rate"]


# ---------------------------------------------------------------------------
# StreamSource tolerates unsorted input (satellite)
# ---------------------------------------------------------------------------

def _run_stream(reqs, conf, correct):
    spec = ServeSpec(policy="edf", executor="oracle", clock="virtual",
                     source="stream",
                     batching={"mode": "none",
                               "stage_times": list(STAGE_TIMES)})
    return Service.from_spec(spec, conf_table=conf,
                             correct_table=correct).run(reqs)


def test_stream_source_shuffled_offsets_match_sorted():
    conf, correct = oracle_tables(200)
    rng = np.random.default_rng(0)
    offs = np.cumsum(rng.uniform(0.001, 0.02, 40))
    reqs = [(float(t), Request(None, 0.15, sample=i))
            for i, t in enumerate(offs)]
    shuffled = [reqs[i] for i in rng.permutation(len(reqs))]
    r_sorted = _run_stream(reqs, conf, correct)
    r_shuffled = _run_stream(shuffled, conf, correct)
    key = lambda recs: sorted((r["sample"], r["offset"], r["depth"],  # noqa: E731
                               r["missed"], r["latency"])
                              for r in recs)
    assert key(r_sorted.per_request) == key(r_shuffled.per_request)
    assert r_sorted.miss_rate == r_shuffled.miss_rate


@given(st.permutations(list(range(12))))
@settings(max_examples=20, deadline=None)
def test_stream_source_property_any_order_sorts(perm):
    """Property: whatever order (offset, request) pairs arrive in, the
    source admits them in offset order (stable for equal offsets)."""
    offs = [round(0.01 * (i // 2), 6) for i in range(12)]   # ties included
    reqs = [(offs[i], Request(None, 0.5, sample=i)) for i in range(12)]
    src = StreamSource([reqs[i] for i in perm], lambda req, now: req)
    popped = [src.pop(0.0) for _ in range(12)]
    assert [r.arrival for r in popped] == sorted(offs)
    # stability: among equal offsets, the *input* order of the shuffled
    # stream is preserved
    for off in set(offs):
        got = [r.sample for r in popped if r.arrival == off]
        expect = [reqs[i][1].sample for i in perm if reqs[i][0] == off]
        assert got == expect
