"""Cross-subsystem integration matrix: traffic scenarios x {plain
engine, predictive admission, multi-model zoo, multi-tenant front door}
x {obs tracing on, off} — every cell must schedule **bit-for-bit
deterministically** on the virtual clock across two identical runs.

The matrix is the regression net under the adaptive-control work: the
subsystems compose through one Service facade, so a nondeterministic
iteration order, a wall-clock read, or a fitted-forecast float leak in
any layer shows up here as a signature mismatch.  Rows are compared by
content (offset/sample/slo/model/tenant/depth/outcome), never by ``tid``
— task ids come from a process-global counter and differ between runs by
design.
"""
import dataclasses

import numpy as np
import pytest

from repro.serving import Request, Service
from repro.serving.traffic import (admission_signature, arrival_signature,
                                   scenario_spec)

from conftest import oracle_tables

STAGE_TIMES = (0.004, 0.007, 0.010)

LLM_TIMES = (0.006, 0.010, 0.014)
VISION_TIMES = (0.003, 0.005, 0.007)
ZOO = {
    "llm": {"stage_times": list(LLM_TIMES), "weight": 2.0},
    "vision": {"stage_times": list(VISION_TIMES)},
}
MIX_STAGE_TIMES = tuple(0.4 * a + 0.6 * b
                        for a, b in zip(LLM_TIMES, VISION_TIMES))


def zoo_tables(models=("llm", "vision"), n=200, L=3, seed=0):
    out = {}
    for i, model in enumerate(sorted(models)):
        rng = np.random.default_rng(seed + i)
        conf = np.sort(rng.uniform(0.3, 1.0, (n, L)), axis=1)
        out[model] = {"conf": conf,
                      "correct": rng.uniform(size=(n, L)) < conf}
    return out


def row_key(r):
    """Replay-comparable content of one per_request row (no tid)."""
    return (round(float(r["offset"]), 9), r["sample"], r.get("slo"),
            r.get("model"), r.get("tenant"), r["depth"], bool(r["missed"]),
            bool(r["rejected"]), r.get("depth_cap"),
            round(float(r["conf"]), 9), round(float(r["latency"]), 9),
            round(float(r["deadline"]), 9))


def signatures(res):
    return (arrival_signature(res.per_request),
            admission_signature(res.per_request),
            sorted(row_key(r) for r in res.per_request))


# ---------------------------------------------------------------------------
# the matrix
# ---------------------------------------------------------------------------

NOMINAL = 1.0 / sum(STAGE_TIMES)
FORECAST = {"process": {"kind": "flash-crowd", "base_rate": 0.7 * NOMINAL,
                        "spike_rate": 3.5 * NOMINAL, "spike_at": 1.9,
                        "spike_len": 1.6},
            "horizon": 0.25}

#: (id, scenario, spec overrides) — each also runs with tracing on
MATRIX = [
    ("steady-plain", "steady",
     dict(policy="rtdeepiot", admission={"mode": "depth_cap"})),
    ("overload-reject", "2x-overload",
     dict(policy="rtdeepiot", admission={"mode": "reject"})),
    ("diurnal-weighted", "diurnal",
     dict(policy="rtdeepiot-weighted", admission={"mode": "depth_cap"})),
    ("flash-forecast", "flash-crowd",
     dict(policy="rtdeepiot-adaptive",
          admission={"mode": "depth_cap", "forecast": FORECAST})),
]


def run_scenario(scenario, overrides, trace, resources=None, stage_times=None):
    spec = scenario_spec(scenario, stage_times=stage_times or STAGE_TIMES,
                         n_requests=80, seed=7, **overrides)
    if trace:
        spec = dataclasses.replace(spec, trace={"enabled": True})
    if resources is None:
        conf, correct = oracle_tables(200)
        resources = dict(conf_table=conf, correct_table=correct)
    return Service.from_spec(spec, **resources).run()


@pytest.mark.parametrize("trace", [False, True], ids=["raw", "traced"])
@pytest.mark.parametrize("name,scenario,overrides",
                         MATRIX, ids=[m[0] for m in MATRIX])
def test_matrix_cell_is_bitwise_deterministic(name, scenario, overrides,
                                              trace):
    a = run_scenario(scenario, overrides, trace)
    b = run_scenario(scenario, overrides, trace)
    assert a.n_requests == b.n_requests == 80
    assert signatures(a) == signatures(b)


@pytest.mark.parametrize("name,scenario,overrides",
                         MATRIX, ids=[m[0] for m in MATRIX])
def test_tracing_never_perturbs_scheduling(name, scenario, overrides):
    """Observability is read-only: the traced run's schedule is the raw
    run's schedule, bit for bit."""
    raw = run_scenario(scenario, overrides, trace=False)
    traced = run_scenario(scenario, overrides, trace=True)
    assert signatures(raw) == signatures(traced)


@pytest.mark.parametrize("trace", [False, True], ids=["raw", "traced"])
def test_zoo_model_mix_cell_is_bitwise_deterministic(trace):
    tables = zoo_tables()
    runs = []
    for _ in range(2):
        spec = scenario_spec("model-mix", policy="rtdeepiot-zoo",
                             admission={"mode": "depth_cap",
                                        "forecast": FORECAST},
                             stage_times=MIX_STAGE_TIMES, n_requests=80,
                             seed=7, models=ZOO)
        spec = dataclasses.replace(spec, executor="zoo-oracle")
        if trace:
            spec = dataclasses.replace(spec, trace={"enabled": True})
        runs.append(Service.from_spec(
            spec, zoo_tables=tables,
            n_samples=tables["llm"]["conf"].shape[0]).run())
    a, b = runs
    assert {r["model"] for r in a.per_request} == {"llm", "vision"}
    assert signatures(a) == signatures(b)


@pytest.mark.parametrize("trace", [False, True], ids=["raw", "traced"])
def test_frontdoor_tenant_cell_is_bitwise_deterministic(trace):
    from repro.serving import ServeSpec
    conf, correct = oracle_tables(200)

    def run_once():
        spec = ServeSpec(
            policy="rtdeepiot", executor="oracle", clock="virtual",
            source="frontdoor",
            source_args={"discipline": "drr", "run_queue": 2},
            batching={"mode": "none", "stage_times": list(STAGE_TIMES)},
            slo_classes={"gold": {"rel_deadline": 0.2}},
            default_slo="gold",
            tenants={"gold": {"weight": 5.0}, "free": {"weight": 1.0}},
            trace={"enabled": True} if trace else {})
        svc = Service.from_spec(spec, conf_table=conf,
                                correct_table=correct)
        for i in range(30):
            svc.submit(Request(None, sample=i),
                       tenant="gold" if i % 2 else "free",
                       request_id=f"r{i:03d}", at=i * 0.003)
        return svc.drain()

    a, b = run_once(), run_once()
    assert a.per_tenant.keys() == b.per_tenant.keys() == {"gold", "free"}
    assert signatures(a) == signatures(b)
