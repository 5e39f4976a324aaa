"""Tests for the observability layer (repro.serving.obs).

The two load-bearing properties:

* **Passivity** — a traced run schedules bit-for-bit identically to an
  untraced one on the virtual clock (all four policies): the Tracer only
  appends engine-computed timestamps, never charges host time.
* **Attributability** — every rejected / shed / depth-capped request in
  the 2x-overload scenario has an audit-log entry naming the rule that
  fired and the numbers behind it, for every rejection path (admission
  reasons, intake bound, intake shed, tenant quota).

Plus: span typing/ordering, time-split bookkeeping, Chrome trace_event
schema validity, JSONL round trip + planectl subcommands, the metrics
registry feeding ServiceSnapshot, per-request emit-only-when-set
fields, the per-run counter-reset regression, and a wall-clock
device-batched smoke.
"""
import json
import os
import subprocess
import sys

import pytest

from repro.core import Workload
from repro.serving import ServeSpec, Service
from repro.serving.engine import Request
from repro.serving.obs import (MetricsRegistry, Tracer, load_obs,
                               validate_chrome_trace)
from repro.serving.traffic import scenario_spec

from conftest import oracle_tables

STAGE_TIMES = [0.004, 0.007, 0.010]


def _spec(policy, trace, **kw):
    args = {}
    if policy == "rtdeepiot":
        args = {"delta": 0.3}
    base = dict(policy=policy, policy_args=args,
                batching={"stage_times": STAGE_TIMES,
                          "buckets": [1, 2, 4, 8], "marginal": 0.15},
                source_args={"n_clients": 12, "d_lo": 0.01, "d_hi": 0.25,
                             "n_requests": 200},
                trace=trace)
    base.update(kw)
    return ServeSpec(**base)


def _run(spec):
    conf, correct = oracle_tables(600)
    svc = Service.from_spec(spec, conf_table=conf, correct_table=correct)
    return svc, svc.run()


# per-request keys only the tracer adds — excluded from the parity diff
OBS_KEYS = ("queue_wait", "host_time", "device_time", "decision")


def _strip(rows):
    # tid is a process-global counter, so runs are compared by row order,
    # not by tid
    out = []
    for r in rows:
        d = {k: v for k, v in r.items() if k not in OBS_KEYS and k != "tid"}
        out.append(d)
    return out


# ---------------------------------------------------------------------------
# passivity: tracing on == tracing off, bit for bit (virtual clock)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", ["rtdeepiot", "edf", "lcf", "rr"])
def test_tracing_is_bitwise_invisible(policy):
    _, off = _run(_spec(policy, {}, admission={"mode": "depth_cap"}))
    svc, on = _run(_spec(policy, {"enabled": True},
                         admission={"mode": "depth_cap"}))
    assert (on.accuracy, on.miss_rate, on.mean_depth, on.mean_conf,
            on.makespan, on.throughput) == \
        (off.accuracy, off.miss_rate, off.mean_depth, off.mean_conf,
         off.makespan, off.throughput)
    assert on.n_dispatches == off.n_dispatches
    assert _strip(on.per_request) == _strip(off.per_request)
    # ... and the traced run actually recorded everything
    assert len(svc.obs.traces) == on.n_requests


def test_trace_disabled_by_default():
    svc, _ = _run(_spec("edf", {}))
    assert svc.obs is None
    svc2, res = _run(_spec("edf", {"enabled": False, "export": "/nope"}))
    assert svc2.obs is None
    assert "decision" not in res.per_request[0]


# ---------------------------------------------------------------------------
# span typing, ordering, time splits
# ---------------------------------------------------------------------------

def test_span_ordering_and_time_splits():
    svc, res = _run(_spec("rtdeepiot", {"enabled": True}))
    assert len(svc.obs.traces) == res.n_requests
    for tr in svc.obs.traces.values():
        names = tr.span_names()
        assert names[0] == "queued"
        assert names[-1] in ("retire", "expire")
        # chronological, with the typed tie-break order
        ts = [s.t0 for s in tr.spans]
        assert ts == sorted(ts)
        if not tr.rejected:
            assert "admitted" in names
        # every dispatch seat has its batched twin and vice versa
        assert names.count("batched") == names.count("dispatch")
        # served requests rode exactly depth device windows
        if not tr.missed and not tr.rejected:
            assert names.count("device-window") >= tr.depth
            assert names.count("stage-exit") == tr.depth
        # time splits: non-negative and bounded by latency
        assert tr.queue_wait >= 0 and tr.device_time >= 0 \
            and tr.host_time >= 0
        assert tr.queue_wait + tr.device_time + tr.host_time \
            <= tr.latency + 1e-9
    # device windows carry seating: bucket >= n for every closed window
    assert svc.obs.windows
    for w in svc.obs.windows:
        assert w["bucket"] >= w["n"] >= 1
        assert w["t1"] >= w["t0"]


def test_per_request_rows_emit_only_when_set():
    """Traced rows gain queue_wait/host_time/device_time/decision;
    untraced rows don't carry the keys at all (Record-style emit-only-
    when-set, so existing trace JSON keeps loading)."""
    _, off = _run(_spec("edf", {}))
    for r in off.per_request:
        assert not any(k in r for k in OBS_KEYS)
    svc, on = _run(_spec("edf", {"enabled": True}))
    for r in on.per_request:
        assert all(k in r for k in OBS_KEYS)
        assert r["decision"] == "admitted"   # no admission controller
    # rows stay JSON-serializable (the Record codec contract)
    json.dumps(on.per_request)


# ---------------------------------------------------------------------------
# audit log: every rejection path names its rule and inputs
# ---------------------------------------------------------------------------

def test_audit_covers_every_shed_request_at_2x_overload():
    conf, correct = oracle_tables(600)
    for mode, rules in (("reject", {"overload", "mandatory-infeasible"}),
                        ("depth_cap", {"overload-capped",
                                       "deadline-capped",
                                       "mandatory-infeasible"})):
        spec = scenario_spec("2x-overload", stage_times=STAGE_TIMES,
                             n_requests=300, admission={"mode": mode},
                             trace={"enabled": True})
        svc = Service.from_spec(spec, conf_table=conf, correct_table=correct)
        svc.run()
        audited = {row["tid"] for row in svc.obs.audit_log}
        degraded = [tr for tr in svc.obs.traces.values()
                    if tr.rejected or tr.depth_cap is not None]
        assert degraded, "overload scenario must shed something"
        for tr in degraded:
            assert tr.tid in audited, \
                f"request {tr.tid} ({tr.decision}) has no audit entry"
        for row in svc.obs.audit_log:
            assert row["rule"] in rules
            assert "slack" in row["detail"]   # the numbers behind the rule
            if row["rule"] in ("overload", "overload-capped"):
                assert "backlog" in row["detail"]


def test_audit_reason_intake_bound_and_shed():
    conf, correct = oracle_tables(600)

    def live_spec(overflow):
        return ServeSpec(policy="edf", source="live",
                         batching={"stage_times": STAGE_TIMES,
                                   "buckets": [1, 2, 4], "marginal": 0.15},
                         source_args={"bound": 2, "overflow": overflow},
                         trace={"enabled": True})

    for overflow, rule, kindcount in (("reject", "intake-bound", 3),
                                      ("shed-optional", "intake-shed", 3)):
        svc = Service.from_spec(live_spec(overflow), conf_table=conf,
                                correct_table=correct)
        for i in range(5):
            svc.submit(Request(inputs=None, rel_deadline=0.5, sample=i,
                               client=0, arrival=0.0), at=0.001 * i,
                       request_id=f"q{i}")
        svc.drain()
        rows = [r for r in svc.obs.audit_log if r["rule"] == rule]
        assert len(rows) == kindcount
        for r in rows:
            assert r["detail"]["bound"] == 2
            assert r["detail"]["intake_depth"] >= 2
            assert r["request_id"].startswith("q")
        # counted exactly once in the registry
        reg = svc.obs.registry
        key = "requests_rejected" if rule == "intake-bound" \
            else "requests_capped"
        assert reg.counter(key).value == kindcount


def test_audit_reason_tenant_quota():
    from repro.serving.plane import FrontDoor
    conf, correct = oracle_tables(600)
    spec = ServeSpec(policy="edf", source="frontdoor",
                     batching={"stage_times": STAGE_TIMES,
                               "buckets": [1, 2, 4], "marginal": 0.15},
                     tenants={"a": {"rate": 1.0, "burst": 1.0},
                              "b": {"weight": 1.0}},
                     trace={"enabled": True})
    svc = Service.from_spec(spec, conf_table=conf, correct_table=correct)
    fd = FrontDoor(svc)
    # burst 1, rate 1/s: the second same-instant submission breaks quota
    for i in range(3):
        fd.submit(Request(inputs=None, rel_deadline=0.5, sample=i,
                          client=0, arrival=0.0), tenant="a", at=0.0,
                  request_id=f"a{i}")
    fd.submit(Request(inputs=None, rel_deadline=0.5, sample=3, client=0,
                      arrival=0.0), tenant="b", at=0.0, request_id="b0")
    svc.drain()
    rows = [r for r in svc.obs.audit_log if r["rule"] == "tenant-quota"]
    assert len(rows) == 2 and all(r["tenant"] == "a" for r in rows)
    for r in rows:
        assert r["detail"]["rate"] == 1.0 and r["detail"]["burst"] == 1.0
    # exactly one audit row + one registry count per quota reject
    assert svc.obs.registry.counter("requests_rejected").value == 2


def test_audit_cancel_pullin():
    conf, correct = oracle_tables(600)
    spec = ServeSpec(policy="edf", source="live",
                     batching={"stage_times": [0.05, 0.05, 0.05],
                               "buckets": [1, 2], "marginal": 0.2},
                     trace={"enabled": True})
    svc = Service.from_spec(spec, conf_table=conf, correct_table=correct)
    h = svc.submit(Request(inputs=None, rel_deadline=1.0, sample=0,
                           client=0, arrival=0.0), at=0.0)
    h2 = svc.submit(Request(inputs=None, rel_deadline=1.0, sample=1,
                            client=0, arrival=0.0), at=0.0)
    assert h is not None and h2.cancel() is not None
    svc.drain()
    # the buffered-live cancel path resolves before the engine runs, so a
    # pull-in row appears only when the cancel raced an admitted task;
    # either way the log stays consistent with the registry counter
    pullins = [r for r in svc.obs.audit_log if r["rule"] == "cancel-pullin"]
    assert len(pullins) == svc.obs.registry.counter("pullins").value


# ---------------------------------------------------------------------------
# exports: JSONL round trip + Chrome trace_event schema
# ---------------------------------------------------------------------------

def test_jsonl_round_trip_and_chrome_schema(tmp_path):
    out = tmp_path / "obs.jsonl"
    chrome = tmp_path / "trace.json"
    svc, res = _run(_spec("rtdeepiot",
                          {"enabled": True, "export": str(out),
                           "chrome": str(chrome)},
                          admission={"mode": "depth_cap"}))
    obs = load_obs(str(out))
    assert obs["header"]["obs_version"] == 1
    assert len(obs["traces"]) == res.n_requests == obs["header"]["n_traces"]
    assert len(obs["audit"]) == len(svc.obs.audit_log)
    assert len(obs["windows"]) == len(svc.obs.windows)
    assert obs["metrics"]["requests_admitted"]["value"] == res.n_requests
    # histograms survive with their explicit buckets
    h = obs["metrics"]["latency"]
    assert h["type"] == "histogram" and h["n"] == res.n_requests \
        and sum(h["counts"]) == h["n"]
    doc = json.loads(chrome.read_text())
    assert validate_chrome_trace(doc) == []
    kinds = {e["ph"] for e in doc["traceEvents"]}
    assert {"X", "M"} <= kinds
    # per-device-window lanes: every window event lives on a named lane
    lanes = {e["tid"] for e in doc["traceEvents"]
             if e["ph"] == "X" and e["pid"] == 1}
    named = {e["tid"] for e in doc["traceEvents"]
             if e["ph"] == "M" and e["pid"] == 1
             and e["name"] == "thread_name"}
    assert lanes and lanes <= named
    # lanes never overlap (the Perfetto-lane invariant)
    per_lane = {}
    for e in doc["traceEvents"]:
        if e["ph"] == "X" and e["pid"] == 1:
            per_lane.setdefault(e["tid"], []).append(
                (e["ts"], e["ts"] + e["dur"]))
    for spans in per_lane.values():
        spans.sort()
        for (a0, a1), (b0, b1) in zip(spans, spans[1:]):
            assert b0 >= a1 - 1e-6


def test_validate_chrome_trace_flags_bad_docs():
    assert validate_chrome_trace([]) == ["document is not a JSON object"]
    assert validate_chrome_trace({}) == ["missing traceEvents array"]
    bad = {"traceEvents": [{"ph": "X", "name": "w", "pid": 1, "tid": 0,
                            "ts": -5, "dur": 1},
                           {"ph": "?", "name": "x"}]}
    problems = validate_chrome_trace(bad)
    assert len(problems) == 2


def test_planectl_trace_why_top(tmp_path):
    conf, correct = oracle_tables(600)
    out = tmp_path / "obs.jsonl"
    spec = ServeSpec(policy="edf", source="live",
                     batching={"stage_times": STAGE_TIMES,
                               "buckets": [1, 2, 4], "marginal": 0.15},
                     admission={"mode": "reject", "headroom": 2.0},
                     trace={"enabled": True, "export": str(out)})
    svc = Service.from_spec(spec, conf_table=conf, correct_table=correct)
    for i in range(12):
        svc.submit(Request(inputs=None, rel_deadline=0.05, sample=i,
                           client=0, arrival=0.0), at=i * 0.003,
                   request_id=f"req-{i}")
    svc.drain()
    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "planectl.py")
    env = dict(os.environ, PYTHONPATH=os.path.join(
        os.path.dirname(tool), "..", "src"))

    def run(*args):
        return subprocess.run([sys.executable, tool, *args], env=env,
                              capture_output=True, text=True)

    r = run("trace", str(out), "req-0")
    assert r.returncode == 0 and "req-0" in r.stdout \
        and "queued" in r.stdout
    r = run("why", str(out), "req-11")
    assert r.returncode == 0
    r = run("top", str(out), "-n", "3", "--by", "latency")
    assert r.returncode == 0 and "total 12 traced" in r.stdout
    r = run("trace", str(out), "no-such-request")
    assert r.returncode == 1


# ---------------------------------------------------------------------------
# metrics registry + streamer integration + reset regression
# ---------------------------------------------------------------------------

def test_registry_instruments():
    reg = MetricsRegistry()
    c = reg.counter("a")
    c.inc()
    c.inc(2)
    assert reg.counter("a").value == 3
    g = reg.gauge("g")
    g.set(7)
    assert reg.gauge("g").value == 7.0
    h = reg.histogram("h", buckets=[1, 2, 4])
    for v in (0.5, 1.5, 3.0, 100.0):
        h.observe(v)
    assert h.counts == [1, 1, 1, 1] and h.n == 4
    assert h.mean == pytest.approx(105.0 / 4)
    with pytest.raises(ValueError):
        reg.histogram("bad", buckets=[3, 1])
    d = reg.to_dict()
    assert d["a"]["value"] == 3 and d["h"]["buckets"] == [1.0, 2.0, 4.0]


def test_snapshots_read_registry_counters():
    """With tracing on, ServiceSnapshot's rejected/capped windows come
    from the obs registry — and match the untraced (legacy-derived)
    stream exactly."""
    def run(trace):
        spec = scenario_spec("2x-overload", stage_times=STAGE_TIMES,
                             n_requests=250,
                             admission={"mode": "reject", "headroom": 3.0},
                             metrics_interval=0.2, trace=trace)
        conf, correct = oracle_tables(600)
        svc = Service.from_spec(spec, conf_table=conf,
                                correct_table=correct)
        svc.run()
        return svc.snapshots

    legacy = [(s.t, s.rejected, s.capped) for s in run({})]
    traced = [(s.t, s.rejected, s.capped) for s in run({"enabled": True})]
    assert traced == legacy
    assert sum(r for _, r, _ in traced) > 0


def test_streamer_counters_reset_on_service_reuse():
    """Regression (telemetry reset satellite): intake/backpressure
    counters are fresh per run on a reused Service, so a second run's
    metrics and first snapshot window don't inherit the first run's
    rejects."""
    conf, correct = oracle_tables(600)
    spec = ServeSpec(policy="edf", source="live",
                     batching={"stage_times": STAGE_TIMES,
                               "buckets": [1, 2], "marginal": 0.15},
                     source_args={"bound": 1, "overflow": "reject"},
                     metrics_interval=0.1)
    svc = Service.from_spec(spec, conf_table=conf, correct_table=correct)

    def cycle(n):
        for i in range(n):
            svc.submit(Request(inputs=None, rel_deadline=0.5, sample=i,
                               client=0, arrival=0.0), at=0.0)
        return svc.drain()

    m1 = cycle(3)
    assert m1.rejected == 2
    assert sum(s.rejected for s in svc.snapshots) == 2
    m2 = cycle(1)
    assert m2.rejected == 0, "second run inherited first run's rejects"
    assert sum(s.rejected for s in svc.snapshots) == 0
    assert m2.cancelled == 0 and m2.capped == 0


def test_spec_trace_validation():
    with pytest.raises(ValueError, match="unknown trace keys"):
        ServeSpec(trace={"enable": True}).validate()
    with pytest.raises(ValueError, match="file path"):
        ServeSpec(trace={"enabled": True, "export": 7}).validate()
    # round-trips like every other spec field
    spec = ServeSpec(trace={"enabled": True, "spans": False})
    assert ServeSpec.from_json(spec.to_json()).trace == spec.trace


def test_trace_spans_off_keeps_time_splits():
    svc, res = _run(_spec("edf", {"enabled": True, "spans": False}))
    assert svc.obs.traces == {}          # span retention gated off
    assert all("queue_wait" in r for r in res.per_request)
    assert svc.obs.registry.counter("requests_admitted").value \
        == res.n_requests


# ---------------------------------------------------------------------------
# wall-clock smoke: obs under the device-batched executor
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.wallclock
def test_wall_clock_device_batched_obs_smoke():
    import jax
    from repro.configs import get_config
    from repro.models import init_params
    from repro.serving import closed_loop_stream
    from repro.serving.batch import BatchTimeModel
    from repro.training import DifficultyDataset

    cfg = get_config("anytime-classifier")
    params = init_params(cfg, jax.random.PRNGKey(0))
    ds = DifficultyDataset(num_classes=cfg.vocab_size, seed=0)
    test = ds.sample(30, seed=9)
    tm = BatchTimeModel.linear((0.002, 0.003, 0.004), (1, 2, 4),
                               marginal=0.25)
    spec = ServeSpec(policy="rtdeepiot",
                     policy_args={"predictor": "exp",
                                  "prior_curve": [.5, .7, .85]},
                     executor="device-batched", clock="wall",
                     source="stream", trace={"enabled": True})
    svc = Service.from_spec(spec, cfg=cfg, params=params, time_model=tm)
    stream = closed_loop_stream(test["inputs"], test["labels"], n_clients=4,
                                d_lo=0.2, d_hi=0.5, n_requests=10, seed=1)
    svc.run(stream)
    assert len(svc.responses) == 10
    assert len(svc.obs.traces) == 10
    for tr in svc.obs.traces.values():
        assert tr.span_names()[0] == "queued"
        # wall-clock device windows really cost time
        if not tr.missed:
            assert tr.device_time > 0
    assert validate_chrome_trace(svc.obs.chrome_trace()) == []
