"""Durable request plane (repro.serving.plane): journal codec + WAL
semantics, idempotent durable submission, crash recovery (bit-for-bit
redo, kill -9 subprocess), multi-tenant front door (quotas, DRR
fairness, weight composition), and the health surfaces."""
import json
import os
import signal
import subprocess
import sys
import textwrap

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.serving import (DurableQueue, FrontDoor, Journal, Record,
                           ServeSpec, Service, journal_stats, recover,
                           scan_journal, verify_recovery)
from repro.serving.engine import Request
from repro.serving.plane.frontdoor import FrontDoorSource, TokenBucket
from repro.serving.runtime import OracleExecutor
from repro.serving.traffic.trace import TRACE_VERSION, load_trace

from conftest import oracle_tables

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STAGE_TIMES = (0.004, 0.007, 0.010)


def live_spec(**overrides):
    kw = dict(policy="edf", executor="oracle", clock="virtual",
              source="live", default_slo="gold",
              slo_classes={"gold": {"rel_deadline": 0.2}},
              batching={"mode": "none", "stage_times": list(STAGE_TIMES)})
    kw.update(overrides)
    return ServeSpec(**kw)


def truncate_after_retires(journal_dir, keep):
    """Crash simulation: drop every terminal record after the keep-th
    (line-boundary truncation of a single-segment journal)."""
    seg = os.path.join(journal_dir, "wal-000000.jsonl")
    out, n_term = [], 0
    with open(seg) as f:
        for line in f:
            if '"kind": "RETIRE"' in line or '"kind": "REJECT"' in line:
                n_term += 1
                if n_term > keep:
                    continue
            out.append(line)
    with open(seg, "w") as f:
        f.writelines(out)


# ---------------------------------------------------------------------------
# record codec
# ---------------------------------------------------------------------------

def test_record_roundtrip_all_fields():
    r = Record(offset=1.25, sample=7, client=2, slo="gold",
               rel_deadline=0.1, outcome={"depth": 2, "missed": False},
               kind="RETIRE", tenant="acme", request_id="r-1", seq=42)
    back = Record.from_dict(json.loads(r.to_json()))
    assert back == r
    with pytest.raises(ValueError, match="unknown record kind"):
        Record.from_dict({"offset": 0.0, "kind": "NOPE"})


def test_record_event_serializes_as_version1():
    """A plain EVENT row must stay byte-identical to the version-1 trace
    schema: no kind/tenant/request_id/seq keys on disk."""
    r = Record(offset=0.5, sample=3, client=1, slo="gold", rel_deadline=0.2,
               outcome={"depth": 1})
    d = json.loads(r.to_json())
    assert set(d) == {"offset", "sample", "client", "slo", "rel_deadline",
                      "outcome"}
    assert Record.from_dict(d).kind == "EVENT"


def test_record_model_field_roundtrips_and_stays_v1_compatible():
    """The model-zoo id follows the emit-only-when-set rule: a record
    carrying one round-trips it exactly, a record without one serializes
    byte-identically to the pre-zoo schema and reads back as None."""
    r = Record(offset=0.75, sample=4, kind="SUBMIT", request_id="r-9",
               model="llm")
    d = json.loads(r.to_json())
    assert d["model"] == "llm"
    back = Record.from_dict(d)
    assert back == r and back.request().model == "llm"
    plain = Record(offset=0.75, sample=4, kind="SUBMIT", request_id="r-9")
    dp = json.loads(plain.to_json())
    assert "model" not in dp                    # v2-without-model byte compat
    assert Record.from_dict(dp).model is None
    v1 = Record(offset=0.5, sample=3, client=1, slo="gold", rel_deadline=0.2)
    assert v1.to_json() == json.dumps(dict(
        offset=0.5, sample=3, client=1, slo="gold", rel_deadline=0.2))


def test_record_request_carries_plane_fields():
    r = Record(offset=2.0, sample=5, slo="gold", rel_deadline=0.3,
               kind="SUBMIT", tenant="t0", request_id="rid-5")
    req = r.request()
    assert (req.arrival, req.sample, req.slo) == (2.0, 5, "gold")
    assert (req.tenant, req.request_id) == ("t0", "rid-5")


def test_record_dedup_key_shapes():
    assert Record(offset=0.0).dedup_key() is None
    a = Record(offset=0.0, kind="RETIRE", request_id="x")
    assert a.dedup_key() == ("RETIRE", "x")
    s1 = Record(offset=0.0, kind="STAGE", request_id="x",
                outcome={"depth": 1})
    s2 = Record(offset=0.0, kind="STAGE", request_id="x",
                outcome={"depth": 2})
    assert s1.dedup_key() != s2.dedup_key()


@settings(max_examples=50, deadline=None)
@given(offset=st.floats(min_value=0, max_value=1e6, allow_nan=False),
       sample=st.integers(min_value=0, max_value=10**6),
       client=st.integers(min_value=0, max_value=10**4),
       kind=st.sampled_from(("SUBMIT", "ADMIT", "STAGE", "RETIRE",
                             "REJECT", "EVENT")),
       tenant=st.one_of(st.none(), st.text(min_size=1, max_size=20)),
       rid=st.one_of(st.none(), st.text(min_size=1, max_size=40)),
       seq=st.one_of(st.none(), st.integers(min_value=0, max_value=2**40)),
       rel=st.one_of(st.none(), st.floats(min_value=1e-6, max_value=100,
                                          allow_nan=False)))
def test_record_codec_roundtrip_property(offset, sample, client, kind,
                                         tenant, rid, seq, rel):
    """Property: any record (unicode tenant ids included) survives the
    JSONL round trip exactly."""
    r = Record(offset=offset, sample=sample, client=client, slo=None,
               rel_deadline=rel, outcome=None, kind=kind, tenant=tenant,
               request_id=rid, seq=seq)
    assert Record.from_dict(json.loads(r.to_json())) == r


# ---------------------------------------------------------------------------
# journal WAL semantics
# ---------------------------------------------------------------------------

def test_journal_rotation_dedup_and_reopen(tmp_path):
    d = str(tmp_path / "j")
    spec = live_spec()
    with Journal(d, spec=spec, fsync_every=2, segment_records=4) as j:
        for i in range(10):
            j.append("SUBMIT", offset=i * 0.1, sample=i,
                     request_id=f"r{i}")
        # idempotent: same (kind, request_id) refuses
        assert j.append("SUBMIT", offset=9.9, request_id="r3") is None
        assert j.counts["SUBMIT"] == 10
        first_seq = j.next_seq
    segs = sorted(p for p in os.listdir(d) if p.startswith("wal-"))
    assert len(segs) == 3          # 4+4+2 records across rotated segments
    # every segment carries a header with the spec
    for seg in segs:
        with open(os.path.join(d, seg)) as f:
            h = json.loads(f.readline())
        assert h["type"] == "header" and "spec" in h
    # reopen: seq continues, dedup index rebuilt from disk
    with Journal(d) as j2:
        assert j2.next_seq == first_seq
        assert j2.spec is not None and j2.spec.source == spec.source
        assert j2.append("SUBMIT", offset=0.0, request_id="r5") is None
        assert j2.append("RETIRE", offset=1.0, request_id="r5",
                         outcome={"depth": 1}) is not None
    header, records = scan_journal(d)
    assert header["version"] == TRACE_VERSION
    assert [r.seq for r in records] == list(range(len(records)))


def test_journal_torn_tail_tolerated_corruption_not(tmp_path):
    d = str(tmp_path / "j")
    with Journal(d, spec=live_spec(), segment_records=4) as j:
        for i in range(6):         # two segments
            j.append("SUBMIT", offset=float(i), request_id=f"r{i}")
    segs = sorted(p for p in os.listdir(d) if p.startswith("wal-"))
    # a torn final line on the *last* segment is a crash artifact: ignored
    with open(os.path.join(d, segs[-1]), "a") as f:
        f.write('{"kind": "RETIRE", "request_id": "r5", "of')
    _, records = scan_journal(d)
    assert len(records) == 6
    # reopen after the torn tail keeps appending (the partial line is
    # not a record; its rid stays un-deduped)
    with Journal(d) as j2:
        assert j2.append("RETIRE", offset=9.0, request_id="r5",
                         outcome={"depth": 1}) is not None
    # the same damage mid-journal is corruption, not a crash artifact
    with open(os.path.join(d, segs[0]), "a") as f:
        f.write('{"broken')
    with pytest.raises(ValueError, match="corrupt journal line"):
        scan_journal(d)


def test_journal_lag_and_sync(tmp_path):
    d = str(tmp_path / "j")
    j = Journal(d, spec=live_spec(), fsync_every=100)
    for i in range(5):
        j.append("SUBMIT", offset=float(i), request_id=f"r{i}")
    assert j.lag() == 5
    j.append("RETIRE", offset=9.0, request_id="r0", outcome={}, sync=True)
    assert j.lag() == 0            # sync=True flushes the whole batch
    j.close()


def test_scan_journal_missing_dir():
    with pytest.raises(FileNotFoundError):
        scan_journal("/nonexistent/journal/dir")


# ---------------------------------------------------------------------------
# durable queue: idempotent submission
# ---------------------------------------------------------------------------

def test_durable_queue_idempotent_submission(tmp_path):
    conf, correct = oracle_tables(120)
    spec = live_spec()
    with Journal(str(tmp_path / "j"), spec=spec, fsync_every=1) as j:
        svc = Service.from_spec(spec, conf_table=conf, correct_table=correct)
        q = DurableQueue(svc, j)
        h1 = q.submit(Request(None, sample=1, request_id="a"), at=0.0)
        h2 = q.submit(Request(None, sample=1, request_id="a"), at=0.5)
        assert h2 is h1                       # same handle object
        assert j.counts["SUBMIT"] == 1        # single journal entry
        with pytest.raises(ValueError, match="request_id"):
            q.submit(Request(None, sample=2))
        met = svc.drain()
    assert met.n_requests == 1
    assert met.per_request[0]["request_id"] == "a"


def test_durable_queue_replayed_duplicate_noops(tmp_path):
    """A duplicate submitted against a *reopened* journal (fresh queue,
    no in-memory handle) must not create a second SUBMIT record."""
    conf, correct = oracle_tables(120)
    spec = live_spec()
    d = str(tmp_path / "j")
    with Journal(d, spec=spec, fsync_every=1) as j:
        svc = Service.from_spec(spec, conf_table=conf, correct_table=correct)
        DurableQueue(svc, j).submit(Request(None, sample=1, request_id="a"),
                                    at=0.0)
        svc.drain()
    with Journal(d) as j2:
        assert j2.append("SUBMIT", offset=0.0, request_id="a") is None
        assert j2.counts["SUBMIT"] == 1


# ---------------------------------------------------------------------------
# crash recovery: bit-for-bit redo under the virtual clock
# ---------------------------------------------------------------------------

def _durable_run(journal_dir, spec, conf, correct, n=12):
    with Journal(journal_dir, spec=spec, fsync_every=1) as j:
        svc = Service.from_spec(spec, conf_table=conf, correct_table=correct)
        q = DurableQueue(svc, j)
        for i in range(n):
            q.submit(Request(None, sample=i % conf.shape[0],
                             request_id=f"r{i:03d}"), at=i * 0.006)
        return svc.drain()


def test_recovery_reproduces_uncrashed_run_bitwise(tmp_path):
    conf, correct = oracle_tables(120)
    spec = live_spec()
    ref = _durable_run(str(tmp_path / "ref"), spec, conf, correct)
    crash = str(tmp_path / "crash")
    _durable_run(crash, spec, conf, correct)
    truncate_after_retires(crash, keep=4)     # die after the 4th terminal

    res = recover(crash, conf_table=conf, correct_table=correct)
    rep = verify_recovery(ref.per_request, res)
    assert rep["recovered"] and rep["bitwise"] and rep["overlap_consistent"]
    assert len(res.already_delivered) == 4
    assert len(res.responses) == 12 - 4
    assert res.delivered_once
    assert set(res.responses).isdisjoint(res.already_delivered)
    # the redo completed the journal: a second recovery redelivers nothing
    res2 = recover(crash, conf_table=conf, correct_table=correct)
    assert res2.report["n_redelivered"] == 0
    assert verify_recovery(ref.per_request, res2)["recovered"]


def test_recovery_spec_from_header_and_override(tmp_path):
    conf, correct = oracle_tables(120)
    spec = live_spec()
    d = str(tmp_path / "j")
    _durable_run(d, spec, conf, correct, n=4)
    truncate_after_retires(d, keep=0)
    res = recover(d, conf_table=conf, correct_table=correct)
    assert res.metrics.components["policy"] == spec.policy
    assert res.report["n_redelivered"] == 4
    # a spec-less journal demands an explicit spec
    d2 = str(tmp_path / "nospec")
    with Journal(d2, spec=None) as j:
        j.append("SUBMIT", offset=0.0, sample=0, request_id="x",
                 rel_deadline=0.2)
    with pytest.raises(ValueError, match="no spec"):
        recover(d2, conf_table=conf, correct_table=correct)
    res2 = recover(d2, spec=spec, conf_table=conf, correct_table=correct)
    assert res2.report["n_redelivered"] == 1


def test_recovery_through_frontdoor_keeps_discipline(tmp_path):
    """A frontdoor journal recovers through the same DRR arbitration the
    original run used, not a plain stream."""
    conf, correct = oracle_tables(120)
    spec = live_spec(
        source="frontdoor",
        source_args={"discipline": "drr", "run_queue": 2},
        tenants={"gold": {"weight": 5.0}, "free": {"weight": 1.0}})
    ref_dir, crash = str(tmp_path / "ref"), str(tmp_path / "crash")

    def run(d):
        with Journal(d, spec=spec, fsync_every=1) as j:
            svc = Service.from_spec(spec, conf_table=conf,
                                    correct_table=correct)
            door = FrontDoor(svc, journal=j)
            for i in range(14):
                door.submit(Request(None, sample=i),
                            tenant="gold" if i % 2 else "free",
                            request_id=f"r{i:03d}", at=i * 0.004)
            return svc.drain()

    ref = run(ref_dir)
    run(crash)
    truncate_after_retires(crash, keep=3)
    res = recover(crash, conf_table=conf, correct_table=correct)
    rep = verify_recovery(ref.per_request, res)
    assert rep["recovered"] and rep["overlap_consistent"], rep
    assert res.metrics.per_tenant.keys() == {"gold", "free"}


@pytest.mark.slow
@pytest.mark.wallclock
def test_crash_recovery_kill9_subprocess(tmp_path):
    """Real crash: a wall-clock live run is SIGKILLed mid-stream; the
    journal alone must recover the rest — every request delivered exactly
    once, no duplicate journal entries, redo bitwise-equal to an
    uncrashed virtual run over the same journaled arrivals."""
    d = str(tmp_path / "j")
    script = textwrap.dedent(f"""
        import os, signal, time
        import numpy as np
        from repro.serving import DurableQueue, Journal, ServeSpec, Service
        from repro.serving.engine import Request

        rng = np.random.default_rng(0)
        conf = np.sort(rng.uniform(0.3, 1.0, (120, 3)), axis=1)
        correct = rng.uniform(size=(120, 3)) < conf
        spec = ServeSpec(
            policy="edf", executor="oracle", clock="wall", source="live",
            default_slo="gold", slo_classes={{"gold": {{"rel_deadline": 2.0}}}},
            batching={{"mode": "none", "stage_times": [0.004, 0.007, 0.01]}})
        j = Journal({d!r}, spec=spec, fsync_every=1)
        svc = Service.from_spec(spec, conf_table=conf, correct_table=correct)
        q = DurableQueue(svc, j)
        for i in range(40):
            q.submit(Request(None, sample=i % 120, request_id=f"r{{i:03d}}"))
            time.sleep(0.004)
        deadline = time.monotonic() + 15.0
        while j.counts.get("RETIRE", 0) < 5 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert j.counts.get("RETIRE", 0) >= 5, j.counts
        os.kill(os.getpid(), signal.SIGKILL)   # no drain, no close, no flush
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=420,
                          env=env)
    assert proc.returncode == -signal.SIGKILL, proc.stderr

    header, records = scan_journal(d)
    submits = [r for r in records if r.kind == "SUBMIT"]
    assert len(submits) == 40
    pre = {r.request_id for r in records if r.kind in ("RETIRE", "REJECT")}
    assert 5 <= len(pre) < 40      # genuinely mid-stream

    conf, correct = oracle_tables(120)
    res = recover(d, conf_table=conf, correct_table=correct)
    # exactly-once across the crash: pre-crash terminals plus the redo's
    # deliveries partition the submitted set
    assert res.delivered_once
    assert set(res.responses) | set(res.already_delivered) \
        == {f"r{i:03d}" for i in range(40)}
    # no duplicate terminal entries in the (now-complete) journal
    _, after = scan_journal(d)
    term = [(r.kind, r.request_id) for r in after
            if r.kind in ("RETIRE", "REJECT")]
    assert len(term) == len(set(term)) == 40
    # an uncrashed virtual run over the same journaled arrivals is the
    # ground truth the redo must match bit-for-bit
    import dataclasses
    spec = ServeSpec.from_dict(header["spec"])
    spec = dataclasses.replace(spec, clock="virtual", clock_args={},
                               source="durable",
                               source_args={"path": d})
    ref = Service.from_spec(spec, conf_table=conf,
                            correct_table=correct).run()
    assert verify_recovery(ref.per_request, res)["recovered"]
    assert journal_stats(d)["queue_depth"] == 0


# ---------------------------------------------------------------------------
# front door: quotas, DRR fairness, weight composition
# ---------------------------------------------------------------------------

def test_token_bucket_deterministic_refill():
    b = TokenBucket(rate=10.0, burst=2)
    assert b.allow(0.0) and b.allow(0.0)
    assert not b.allow(0.0)        # burst exhausted at t=0
    assert b.allow(0.1)            # one token back after 0.1s
    assert not b.allow(0.1)
    with pytest.raises(ValueError):
        TokenBucket(rate=0.0, burst=1)
    with pytest.raises(ValueError):
        TokenBucket(rate=1.0, burst=0)


def test_frontdoor_drr_release_order_weighted():
    class _Clock:
        realtime = False

    src = FrontDoorSource(lambda req, now: req, _Clock(),
                          tenants={"big": {"weight": 3.0},
                                   "small": {"weight": 1.0}},
                          discipline="drr")
    for i in range(12):
        src.push(0.0, Request(None, sample=i, tenant="big"))
    for i in range(8):
        src.push(0.0, Request(None, sample=100 + i, tenant="small"))
    order = []
    while src.qsize():
        order.append(src.pop(0.0).tenant)
    # while both backlogged, releases approach the 3:1 weight ratio
    head = order[:12]
    assert head.count("big") == 9 and head.count("small") == 3
    assert sorted(src.tenant_depths().items()) == []


def test_frontdoor_quota_rejects_fail_fast(tmp_path):
    conf, correct = oracle_tables(120)
    spec = live_spec(source="frontdoor", source_args={},
                     tenants={"a": {"weight": 1.0, "rate": 10.0,
                                    "burst": 2}})
    with Journal(str(tmp_path / "j"), spec=spec, fsync_every=1) as j:
        svc = Service.from_spec(spec, conf_table=conf,
                                correct_table=correct)
        door = FrontDoor(svc, journal=j)
        hs = [door.submit(Request(None, sample=i), tenant="a", at=0.0,
                          request_id=f"r{i}") for i in range(5)]
        # burst=2 at t=0: three quota rejects, resolved without running
        rejected = [h for h in hs if h.done() and h.result().rejected]
        assert len(rejected) == 3
        assert j.counts.get("REJECT", 0) == 3
        assert j.counts["SUBMIT"] == 2        # rejects are never SUBMITs
        met = svc.drain()
    assert met.per_tenant["a"]["rejected"] == 3
    assert met.per_tenant["a"]["served"] == 2
    assert door.counts["a"] == {"submitted": 5, "quota_rejected": 3}
    assert journal_stats(str(tmp_path / "j"))["queue_depth"] == 0


def test_drr_protects_light_tenant_fifo_starves_it():
    """The fairness claim in miniature: under ~2x overload with the
    light (low-rate, high-weight) tenant at its fair share, DRR serves
    it nearly fully while global-FIFO release order starves it."""
    conf, correct = oracle_tables(400)

    def run(discipline):
        spec = live_spec(
            source="frontdoor",
            source_args={"discipline": discipline, "run_queue": 2},
            tenants={"light": {"weight": 10.0}, "heavy": {"weight": 1.0}},
            admission={"mode": "reject", "headroom": 5.0},
            slo_classes={"gold": {"rel_deadline": 0.08}})
        svc = Service.from_spec(spec, conf_table=conf,
                                correct_table=correct)
        for i in range(190):
            svc.submit(Request(None, sample=i % 400, tenant="heavy",
                               request_id=f"h{i}"), at=i * (2.0 / 190))
        for i in range(8):
            svc.submit(Request(None, sample=(200 + i) % 400, tenant="light",
                               request_id=f"l{i}"), at=i * 0.25)
        met = svc.drain()
        return met.per_tenant["light"]["served"] / 8, met.admitted_miss_rate

    drr_frac, drr_miss = run("drr")
    fifo_frac, fifo_miss = run("fifo")
    assert drr_frac >= 0.9, (drr_frac, fifo_frac)
    assert fifo_frac <= 0.6, (drr_frac, fifo_frac)
    assert drr_miss <= 0.01 and fifo_miss <= 0.01


def test_tenant_weight_composes_with_slo_weight():
    conf, correct = oracle_tables(120)
    spec = live_spec(
        source="frontdoor", source_args={},
        tenants={"vip": {"weight": 4.0}, "std": {"weight": 1.0}},
        slo_classes={"gold": {"rel_deadline": 0.2, "utility_weight": 3.0}})
    svc = Service.from_spec(spec, conf_table=conf, correct_table=correct)
    svc.submit(Request(None, sample=0, tenant="vip", request_id="a"),
               at=0.0)
    svc.submit(Request(None, sample=1, tenant="std", request_id="b"),
               at=0.0)
    met = svc.drain()
    w = {r["tenant"]: r["weight"] for r in met.per_request}
    assert w == {"vip": 12.0, "std": 3.0}     # slo 3.0 x tenant {4, 1}


def test_frontdoor_validation():
    with pytest.raises(ValueError, match="weight must be > 0"):
        live_spec(tenants={"a": {"weight": 0.0}}).validate()
    with pytest.raises(ValueError, match="discipline"):
        live_spec(source="frontdoor",
                  source_args={"discipline": "lifo"}).validate()
    with pytest.raises(ValueError, match="run_queue"):
        live_spec(source="frontdoor",
                  source_args={"run_queue": 0}).validate()
    with pytest.raises(ValueError, match="spec.source"):
        conf, correct = oracle_tables(120)
        FrontDoor(Service.from_spec(live_spec(), conf_table=conf,
                                    correct_table=correct))


# ---------------------------------------------------------------------------
# drain()/close() robustness
# ---------------------------------------------------------------------------

class _BoomExecutor:
    """Delegating wrapper whose submit always raises — the regression
    target: a raising executor must not wedge close()."""

    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def submit(self, stage, tasks, now):
        raise RuntimeError("boom")


@pytest.mark.wallclock
def test_close_survives_raising_executor_wall_clock():
    from repro.serving.batch import BatchTimeModel
    conf, correct = oracle_tables(120)
    tm = BatchTimeModel.linear(STAGE_TIMES, (1,))
    spec = live_spec(clock="wall",
                     slo_classes={"gold": {"rel_deadline": 0.5}})
    svc = Service.from_spec(
        spec, executor=_BoomExecutor(OracleExecutor(tm, conf)),
        time_model=tm, conf_table=conf, correct_table=correct)
    h = svc.submit(Request(None, sample=0))
    with pytest.raises(RuntimeError):
        h.result(timeout=10.0)     # handle resolved with the error
    svc.close()                    # swallows the engine error, returns
    assert svc._closed and svc._live is None
    svc.close()                    # idempotent
    with pytest.raises(RuntimeError, match="closed"):
        svc.submit(Request(None, sample=1))


def test_drain_raises_once_then_recovers_virtual():
    from repro.serving.batch import BatchTimeModel
    conf, correct = oracle_tables(120)
    tm = BatchTimeModel.linear(STAGE_TIMES, (1,))
    svc = Service.from_spec(
        live_spec(), executor=_BoomExecutor(OracleExecutor(tm, conf)),
        time_model=tm, conf_table=conf, correct_table=correct)
    h = svc.submit(Request(None, sample=0), at=0.0)
    with pytest.raises(RuntimeError, match="boom"):
        svc.drain()                # buffered virtual drain surfaces it
    with pytest.raises(RuntimeError):
        h.result(timeout=0.1)      # ... after failing the handle
    svc.drain()                    # idempotent: no buffered work left
    svc.close()


def test_drain_idempotent_after_success():
    conf, correct = oracle_tables(120)
    svc = Service.from_spec(live_spec(), conf_table=conf,
                            correct_table=correct)
    svc.submit(Request(None, sample=0), at=0.0)
    met = svc.drain()
    assert svc.drain() is met      # second drain: same metrics, no rerun
    svc.close()


# ---------------------------------------------------------------------------
# snapshots: uniform intake depth + per-tenant breakdown
# ---------------------------------------------------------------------------

def test_snapshot_intake_depth_and_per_tenant():
    conf, correct = oracle_tables(120)
    spec = live_spec(
        source="frontdoor",
        source_args={"discipline": "drr", "run_queue": 1},
        tenants={"a": {"weight": 2.0}, "b": {"weight": 1.0}},
        metrics_interval=0.02)
    svc = Service.from_spec(spec, conf_table=conf, correct_table=correct)
    for i in range(16):
        svc.submit(Request(None, sample=i, tenant="a" if i % 2 else "b",
                           request_id=f"r{i}"), at=i * 0.001)
    met = svc.drain()
    snaps = svc.snapshots
    assert snaps, "windowed metrics must have streamed"
    assert sum(s.n for s in snaps) == met.n_requests
    assert all(s.intake_depth >= s.queue_depth for s in snaps)
    # run_queue=1 with a burst of 16: early windows must see a backlog
    assert max(s.intake_depth for s in snaps) > 0
    seen = set()
    for s in snaps:
        seen.update(s.per_tenant)
        for t, row in s.per_tenant.items():
            assert set(row) == {"queued", "n"}
    assert seen == {"a", "b"}
    d = snaps[0].to_dict()
    assert "intake_depth" in d and "per_tenant" in d


# ---------------------------------------------------------------------------
# trace schema unification (v1 read path)
# ---------------------------------------------------------------------------

def test_load_trace_reads_version1_files(tmp_path):
    p = tmp_path / "v1.jsonl"
    lines = [json.dumps({"type": "header", "version": 1, "n_events": 2,
                         "source": "test"})]
    for i in range(2):
        lines.append(json.dumps({
            "offset": i * 0.1, "sample": i, "client": 0, "slo": "gold",
            "rel_deadline": 0.2,
            "outcome": {"depth": 1, "missed": False, "rejected": False}}))
    p.write_text("\n".join(lines) + "\n")
    header, events = load_trace(str(p))
    assert header["version"] == 1
    assert [e.kind for e in events] == ["EVENT", "EVENT"]
    assert events[1].request().sample == 1
    # a future version refuses loudly
    p2 = tmp_path / "v99.jsonl"
    p2.write_text(json.dumps({"type": "header", "version": 99,
                              "n_events": 0}) + "\n")
    with pytest.raises(ValueError, match="version 99"):
        load_trace(str(p2))


def test_checked_in_mini_trace_still_old_format():
    """The checked-in regression trace stays on the version-1 format and
    the old read path keeps replaying it (examples/traffic_replay.py
    --trace covers the bit-for-bit outcome check)."""
    path = os.path.join(REPO, "examples", "data", "mini_trace.jsonl")
    header, events = load_trace(path)
    assert header["version"] == 1
    assert len(events) == header["n_events"] > 0
    assert all(e.kind == "EVENT" for e in events)
    with open(path) as f:
        for line in f:
            d = json.loads(line)
            assert "kind" not in d and "tenant" not in d


# ---------------------------------------------------------------------------
# planectl CLI
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_planectl_cli(tmp_path):
    conf, correct = oracle_tables(120)
    d = str(tmp_path / "j")
    _durable_run(d, live_spec(), conf, correct, n=6)
    truncate_after_retires(d, keep=2)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    tool = os.path.join(REPO, "tools", "planectl.py")

    out = subprocess.run([sys.executable, tool, "stats", d, "--json"],
                         capture_output=True, text=True, timeout=420,
                         env=env)
    assert out.returncode == 0, out.stderr
    st = json.loads(out.stdout)
    assert st["queue_depth"] == 4 and st["counts"]["SUBMIT"] == 6

    out = subprocess.run([sys.executable, tool, "pending", d],
                         capture_output=True, text=True, timeout=420,
                         env=env)
    assert out.returncode == 1     # pending work -> exit 1
    assert len(out.stdout.split()) == 4

    out = subprocess.run([sys.executable, tool, "tail", d, "-n", "3"],
                         capture_output=True, text=True, timeout=420,
                         env=env)
    assert out.returncode == 0
    assert len(out.stdout.strip().splitlines()) == 3

    # recovery drains it: stats agree, pending exits 0
    recover(d, conf_table=conf, correct_table=correct)
    out = subprocess.run([sys.executable, tool, "pending", d],
                         capture_output=True, text=True, timeout=420,
                         env=env)
    assert out.returncode == 0 and not out.stdout.strip()
