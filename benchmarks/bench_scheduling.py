"""Scheduling benchmarks — one per paper table/figure (paper §IV).

Figures reproduced (CPU-scale analog of CIFAR-10/ImageNet ResNet-3-stage):
  fig3_5   utility-heuristic comparison (Exp/Max/Lin vs Oracle) across
           K, D_u, D_l sweeps                     [paper Fig. 3–5]
  fig6_7   scheduler comparison (RTDeepIoT vs EDF/LCF/RR): accuracy +
           deadline-miss rate vs K                [paper Fig. 6–7]
  fig8_11  accuracy + miss rate vs D_u and D_l    [paper Fig. 8–11]
  fig12    reward-quantization Δ sweep            [paper Fig. 12]
  fig13    scheduler overhead vs K                [paper Fig. 13]
  batch    continuous stage-level micro-batching: goodput (completed
           requests/s), miss rate and accuracy vs offered load, batched
           (repro.serving.batch) vs unbatched engine [extension]
  async    pipelined async dispatch (repro.serving.runtime,
           pipeline_depth=2) vs synchronous batched dispatch: charged
           host-overhead fraction, goodput, accuracy, miss rate
           [extension; deterministic modeled host costs]
  traffic  open-loop traffic scenarios (repro.serving.traffic): steady /
           2x sustained overload / flash crowd / diurnal ramp, policies
           with and without admission control + shedding; includes the
           record/replay bit-for-bit regression check  [extension]
  sharded  the device-sharded executor (repro.launch.sharded): modeled
           goodput vs data-parallel mesh width under 2x overload scaled
           to each width, plus the end-to-end device-sharded run on the
           real anytime classifier through a traffic scenario with
           bit-for-bit parity against device-batched on a 1x1 mesh
           [extension]
  kernel   the device-kernel fast path (repro.launch.kernel): depth-3
           dispatch pipelining vs the async figure's charged host-cost
           floor, ragged length-bucket batching under 2x overload, the
           end-to-end Pallas-backed run on the real anytime classifier
           (fused exit-confidence bit-for-bit vs the unfused reference,
           ragged decode batching vs singletons: preds exact, hidden
           states to float32 rounding)  [extension]
  plane    the durable request plane (repro.serving.plane): DRR vs FIFO
           tenant fairness under skewed overload, idempotent journaled
           submission, and bit-for-bit mid-stream crash recovery
           [extension]
  zoo      the multi-model zoo (repro.serving.zoo): cross-model
           preemption (rtdeepiot-zoo scope=global) vs per-model-siloed
           planning on the model-mix 2x-overload scenario, scored on
           weighted admitted accuracy, plus the single-member zoo spec's
           bit-for-bit parity against the plain device-batched path
           [extension]
  obs      the observability layer (repro.serving.obs): measured
           wall-clock overhead of full tracing on the batch figure's
           config (claim: < 5%), bitwise scheduling parity traced vs
           untraced, audit-log coverage of every shed/rejected request
           at 2x overload, and Chrome trace_event export validity
           [extension]

All rows print as CSV (name,metric,value triples per configuration) and are
also returned as dicts (``SimResult.to_dict`` rows) for EXPERIMENTS.md
generation.  Inputs: the trained anytime classifier's oracle tables
(artifacts/oracle_tables.npz, produced by examples/train_multiexit.py) +
profiled stage WCETs.

Every engine is built through the public serving API: a declarative
``ServeSpec`` (policy/executor/clock/source by registry key) run through
``repro.serving.Service``.

``--smoke`` runs every figure on tiny workloads (synthetic oracle tables
when the artifact is absent) without writing artifacts — the CI job that
keeps these code paths alive.
"""
from __future__ import annotations

import argparse
import dataclasses as _dc
import json
import os

import numpy as np

from repro.core import Workload
from repro.serving import ServeSpec, Service
from repro.serving.batch.batcher import DEFAULT_BUCKETS

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts")

# stage WCETs: paper-like magnitudes (~ms-scale stages vs 10-300 ms
# deadlines), proportional to our anytime stages' 1/2/3-layer depths.  (The
# wall-clock engine profiles real stage times itself; see
# examples/serve_anytime.py.)
DEFAULT_STAGE_TIMES = (0.004, 0.007, 0.010)

DEFAULTS = dict(n_clients=20, d_lo=0.01, d_hi=0.3, n_requests=600)

# modeled host costs for the async figure: one policy invocation
# (selection / replan / §II-E hook) and one device submit — deterministic,
# so pipelined-vs-synchronous comparisons are reproducible
ASYNC_POLICY_COST = 5e-4
ASYNC_DISPATCH_OVERHEAD = 1e-4


def load_tables(smoke: bool = False):
    path = os.path.join(ART, "oracle_tables.npz")
    if not os.path.exists(path):
        if smoke:
            return (*synthetic_tables(), None)
        raise FileNotFoundError(
            f"{path} missing — run examples/train_multiexit.py first")
    z = np.load(path)
    return z["confidence"], z["correct"], z


def synthetic_tables(n=600, L=3, seed=0):
    """Oracle-shaped tables for smoke runs: monotone per-sample confidence
    curves whose correctness is confidence-consistent."""
    rng = np.random.default_rng(seed)
    conf = np.sort(rng.uniform(0.3, 1.0, (n, L)), axis=1)
    correct = rng.uniform(size=(n, L)) < conf
    return conf, correct.astype(bool)


def _stage_times():
    # simulation figures always use the paper-analog times; the wall-clock
    # engine (examples/serve_anytime.py) profiles real ones separately
    return DEFAULT_STAGE_TIMES


def _policy_conf(name, delta=0.1):
    """Registry (policy, policy_args) for a figure's policy label."""
    if name in ("exp", "max", "lin", "oracle"):
        return "rtdeepiot", {"predictor": name, "delta": delta}
    return name, {}


def _spec(policy_name, *, delta=0.1, batched=False, admission=None,
          charge_overhead=False, dispatch_overhead=0.0, policy_cost=None,
          pipeline_depth=1) -> ServeSpec:
    """One place every figure's engine is declared: the ServeSpec."""
    pol, pargs = _policy_conf(policy_name, delta)
    batching = ({"buckets": list(DEFAULT_BUCKETS), "marginal": 0.15,
                 "stage_times": list(_stage_times())} if batched
                else {"mode": "none", "stage_times": list(_stage_times())})
    return ServeSpec(policy=pol, policy_args=pargs, executor="oracle",
                     clock="virtual", source="closed-loop",
                     batching=batching, admission=admission or {},
                     charge_overhead=charge_overhead,
                     dispatch_overhead=dispatch_overhead,
                     policy_cost=policy_cost, pipeline_depth=pipeline_depth)


def _serve(spec, conf, correct, **wl_kwargs):
    wl = Workload(**{**DEFAULTS, **wl_kwargs})
    return Service.from_spec(spec, workload=wl, conf_table=conf,
                             correct_table=correct).run()


def _run(policy_name, conf, correct, *, delta=0.1, charge_overhead=False,
         **wl_kwargs):
    return _serve(_spec(policy_name, delta=delta,
                        charge_overhead=charge_overhead),
                  conf, correct, **wl_kwargs)


def _emit(rows, fig, key, policy, res):
    row = {k: (round(v, 6) if isinstance(v, float) else v)
           for k, v in res.to_dict().items() if not isinstance(v, dict)}
    rows.append(dict(figure=fig, config=key, policy=policy, **row))
    print(f"{fig},{key},{policy},acc={res.accuracy:.4f},"
          f"miss={res.miss_rate:.4f},depth={res.mean_depth:.2f},"
          f"ovh={res.overhead_frac:.4f},thr={res.throughput:.1f}")


def fig3_5_utility_heuristics(conf, correct, ks=(10, 20, 40),
                              dus=(0.1, 0.3, 0.6), dls=(0.01, 0.05, 0.1)):
    """Exp vs Max vs Lin vs Oracle across K / D_u / D_l (paper Fig. 3–5)."""
    rows = []
    for k in ks:
        for p in ("exp", "max", "lin", "oracle"):
            _emit(rows, "fig3", f"K={k}", f"rtdeepiot-{p}",
                  _run(p, conf, correct, n_clients=k))
    for du in dus:
        for p in ("exp", "max", "lin", "oracle"):
            _emit(rows, "fig4", f"Du={du}", f"rtdeepiot-{p}",
                  _run(p, conf, correct, d_hi=du))
    for dl in dls:
        for p in ("exp", "max", "lin", "oracle"):
            _emit(rows, "fig5", f"Dl={dl}", f"rtdeepiot-{p}",
                  _run(p, conf, correct, d_lo=dl))
    return rows


def fig6_7_scheduler_comparison(conf, correct, ks=(5, 10, 20, 40, 60)):
    rows = []
    for k in ks:
        for p in ("exp", "edf", "lcf", "rr"):
            name = "rtdeepiot" if p == "exp" else p
            _emit(rows, "fig6_7", f"K={k}", name,
                  _run(p, conf, correct, n_clients=k))
    return rows


def fig8_11_deadline_sweeps(conf, correct, dus=(0.1, 0.2, 0.3, 0.5),
                            dls=(0.01, 0.03, 0.06, 0.1)):
    rows = []
    for du in dus:
        for p in ("exp", "edf", "lcf", "rr"):
            name = "rtdeepiot" if p == "exp" else p
            _emit(rows, "fig8_9", f"Du={du}", name,
                  _run(p, conf, correct, d_hi=du))
    for dl in dls:
        for p in ("exp", "edf", "lcf", "rr"):
            name = "rtdeepiot" if p == "exp" else p
            _emit(rows, "fig10_11", f"Dl={dl}", name,
                  _run(p, conf, correct, d_lo=dl))
    return rows


def fig12_delta_sweep(conf, correct,
                      deltas=(0.4, 0.2, 0.1, 0.05, 0.02, 0.005)):
    """Reward quantization step Δ: accuracy vs scheduling granularity,
    with scheduler wall time charged to the simulated clock so too-fine Δ
    hurts exactly as in the paper."""
    rows = []
    for delta in deltas:
        res = _run("exp", conf, correct, delta=delta, charge_overhead=True)
        _emit(rows, "fig12", f"delta={delta}", "rtdeepiot", res)
    return rows


def fig_batch_throughput(conf, correct, ks=(16, 32, 64), n_requests=800):
    """Batched vs unbatched serving across offered load (repro.serving.batch).

    Same closed-loop workload and policies on both paths; the batched path
    dispatches padded micro-batches priced by a linear BatchTimeModel
    (each extra item costs 15% of the single-item stage time — conservative
    vs. measured GPU batch scaling).  Goodput = completed requests/s."""
    rows = []
    speedups = {}
    for k in ks:
        wl_kwargs = dict(n_clients=k, n_requests=n_requests)
        for p in ("exp", "edf"):
            name = "rtdeepiot" if p == "exp" else p
            res_u = _run(p, conf, correct, **wl_kwargs)
            _emit(rows, "batch", f"K={k}", name, res_u)
            res_b = _serve(_spec(p, batched=True), conf, correct, **wl_kwargs)
            _emit(rows, "batch", f"K={k}", f"batched-{name}", res_b)
            speedups[(k, name)] = (res_b.throughput
                                   / max(res_u.throughput, 1e-9),
                                   res_b.accuracy - res_u.accuracy)
            # admission-controlled variant: fail fast under overload
            res_a = _serve(_spec(p, batched=True,
                                 admission={"mode": "depth_cap"}),
                           conf, correct, **wl_kwargs)
            _emit(rows, "batch", f"K={k}", f"batched-{name}-admit", res_a)
    for (k, name), (sp, dacc) in sorted(speedups.items()):
        print(f"batch,K={k},{name},speedup={sp:.2f}x,acc_delta={dacc:+.4f}")
    return rows, speedups


def fig_async_dispatch(conf, correct, ks=(16, 32, 64), n_requests=1200):
    """Pipelined async dispatch vs synchronous batched dispatch
    (repro.serving.runtime, pipeline_depth=2 vs 1).

    Both paths run the same batched EngineCore with deterministic modeled
    host costs (one policy invocation = {ASYNC_POLICY_COST}s, one submit =
    {ASYNC_DISPATCH_OVERHEAD}s) charged to the virtual clock.  Synchronous
    dispatch serializes every host second with the device; the pipelined
    host pre-selects batch N+1 inside batch N's window (re-validating
    deadline feasibility at true dispatch time), so most host work hides
    behind device execution — charged host-overhead fraction drops at
    equal-or-better goodput/accuracy/miss."""
    rows = []
    comp = {}
    for k in ks:
        # 1200+ requests: accuracy deltas between the two dispatch modes
        # are schedule-chaos noise at small n; this concentrates them
        wl_kwargs = dict(n_clients=k, n_requests=n_requests)
        for p in ("exp", "edf"):
            name = "rtdeepiot" if p == "exp" else p
            kw = dict(batched=True, charge_overhead=True,
                      dispatch_overhead=ASYNC_DISPATCH_OVERHEAD,
                      policy_cost=ASYNC_POLICY_COST)
            res_s = _serve(_spec(p, pipeline_depth=1, **kw), conf, correct,
                           **wl_kwargs)
            _emit(rows, "async", f"K={k}", f"sync-{name}", res_s)
            res_a = _serve(_spec(p, pipeline_depth=2, **kw), conf, correct,
                           **wl_kwargs)
            _emit(rows, "async", f"K={k}", f"pipelined-{name}", res_a)
            comp[(k, name)] = dict(
                host_frac_sync=res_s.host_overhead_frac,
                host_frac_async=res_a.host_overhead_frac,
                acc_sync=res_s.accuracy, miss_sync=res_s.miss_rate,
                acc_delta=res_a.accuracy - res_s.accuracy,
                miss_delta=res_a.miss_rate - res_s.miss_rate,
                goodput_ratio=res_a.throughput / max(res_s.throughput, 1e-9),
                presel_hit_rate=res_a.presel_hits
                / max(res_a.presel_hits + res_a.presel_misses, 1))
    for (k, name), c in sorted(comp.items()):
        print(f"async,K={k},{name},host_frac {c['host_frac_sync']:.4f}->"
              f"{c['host_frac_async']:.4f},goodput x{c['goodput_ratio']:.2f},"
              f"acc{c['acc_delta']:+.4f},miss{c['miss_delta']:+.4f}")
    return rows, comp


def fig13_overhead(conf, correct, ks=(5, 10, 20, 40)):
    rows = []
    for k in ks:
        res = _run("exp", conf, correct, n_clients=k)
        _emit(rows, "fig13", f"K={k}", "rtdeepiot", res)
    return rows


# policy x overload-control variants run in every traffic scenario:
# (label, registry policy key, admission config)
TRAFFIC_VARIANTS = (
    ("edf", "edf", None),                               # uncontrolled
    ("rtdeepiot", "rtdeepiot", None),                   # planner only
    ("rtdeepiot-admit", "rtdeepiot", {"mode": "reject"}),
    ("rtdeepiot-shed", "rtdeepiot", {"mode": "depth_cap"}),
)


def fig_traffic(conf, correct, n_requests=1500, seed=0):
    """Open-loop traffic scenarios (repro.serving.traffic).

    Every scenario drives the same service through the registry's
    ``traffic`` source: seeded arrival process x gold/silver/bronze SLO
    mix, rates scaled to the nominal full-depth service rate.  The
    headline regime is ``2x-overload`` — load the closed-loop §IV
    workload cannot express: uncontrolled EDF collapses (deadline misses
    pile up), while RTDeepIoT behind admission control (reject) or
    shedding (depth_cap) keeps *admitted* misses near zero at bounded
    accuracy loss.

    Also performs the record/replay regression check: the
    ``rtdeepiot-admit`` 2x-overload run is captured as a trace and
    re-injected through ``register_source("replay")`` — arrival order and
    admission decisions must reproduce bit-for-bit under the virtual
    clock.
    """
    from repro.serving.traffic import (SCENARIOS, TraceRecorder,
                                       scenario_spec, verify_replay)
    rows = []
    comp = {}
    st = _stage_times()
    for scen in sorted(SCENARIOS):
        for label, pol, adm in TRAFFIC_VARIANTS:
            spec = scenario_spec(scen, policy=pol, admission=adm,
                                 stage_times=st, n_requests=n_requests,
                                 seed=seed)
            res = Service.from_spec(spec, conf_table=conf,
                                    correct_table=correct).run()
            _emit(rows, "traffic", scen, label, res)
            comp[(scen, label)] = res
    # record/replay round trip on the headline configuration
    spec = scenario_spec("2x-overload", policy="rtdeepiot",
                         admission={"mode": "reject"}, stage_times=st,
                         n_requests=n_requests, seed=seed)
    orig = comp[("2x-overload", "rtdeepiot-admit")]
    rec = TraceRecorder(source="traffic", spec=spec)
    rec.capture(orig)
    rspec = _dc.replace(spec, source="replay", source_args={})
    rep = Service.from_spec(rspec, conf_table=conf, correct_table=correct,
                            trace=rec.events).run()
    replay = verify_replay(orig.per_request, rep.per_request)
    print(f"traffic,replay,rtdeepiot-admit,arrival_order="
          f"{replay['arrival_order']},admission={replay['admission_decisions']}")
    return rows, comp, replay


def traffic_claims(comp, replay):
    """Headline check for the traffic subsystem: at 2x sustained overload
    RTDeepIoT + admission/shedding holds admitted deadline misses < 1%
    with bounded accuracy loss while uncontrolled EDF exceeds 20% —
    and a recorded trace replays bit-for-bit."""
    o = {label: comp[("2x-overload", label)]
         for label, _, _ in TRAFFIC_VARIANTS}
    steady_acc = comp[("steady", "rtdeepiot")].accuracy
    controlled = {"rtdeepiot-admit": o["rtdeepiot-admit"],
                  "rtdeepiot-shed": o["rtdeepiot-shed"]}
    ctl_miss = max(m.admitted_miss_rate for m in controlled.values())
    ctl_acc = min((m.admitted_accuracy if m.admitted_accuracy is not None
                   else m.accuracy) for m in controlled.values())
    claims = {
        "traffic_overload_edf_miss": round(o["edf"].miss_rate, 4),
        "traffic_overload_admitted_miss": {
            k: round(m.admitted_miss_rate, 4) for k, m in controlled.items()},
        "traffic_overload_served_frac": {
            k: round(1.0 - (m.rejected / max(m.n_requests, 1)), 4)
            for k, m in controlled.items()},
        "traffic_overload_admitted_accuracy": round(ctl_acc, 4),
        "traffic_steady_rtdeepiot_accuracy": round(steady_acc, 4),
        # "bounded accuracy loss": admitted work degrades depth, it does
        # not fall off a cliff — stays within 25% of the steady-state
        # accuracy while EDF's overall accuracy collapses below it
        "traffic_overload_acc_bounded":
            bool(ctl_acc >= 0.75 * steady_acc
                 and ctl_acc > o["edf"].accuracy),
        "traffic_replay_arrival_order": bool(replay["arrival_order"]),
        "traffic_replay_admission_decisions":
            bool(replay["admission_decisions"]),
        "traffic_claim_met": bool(
            o["edf"].miss_rate > 0.20 and ctl_miss < 0.01
            and ctl_acc >= 0.75 * steady_acc and replay["bitwise"]),
    }
    print("TRAFFIC CLAIMS:", claims)
    return claims


# per-dispatch cross-replica sync cost (seconds) charged by the modeled
# sharded sweep whenever dp > 1 — deliberately pessimistic vs ICI numbers
SHARDED_COLLECTIVE = 2e-4


def fig_sharded(conf, correct, dps=(1, 2, 4), n_requests=900,
                e2e_requests=40, seed=0):
    """The ``device-sharded`` executor (repro.launch.sharded), two parts.

    **Modeled dp sweep** — virtual clock, oracle executor priced by
    ``sharded_time_model(dp)``: each data-parallel width is offered the
    ``2x-overload`` traffic scenario scaled to *its own* capacity (2x of
    dp devices), with admission control on.  Goodput (completed
    requests/s) must scale near-linearly in dp while admitted misses stay
    near zero — the "server side actually scales with offered load" claim.

    **End-to-end 1x1-mesh run** — ``ServeSpec(executor="device-sharded")``
    on the real anytime classifier, driven by the ``steady`` traffic
    scenario through the registry (``repro.launch.serve`` registers the
    executor from outside the serving package).  On the 1x1 mesh the
    results must match ``device-batched`` **bit-for-bit**; the per-request hidden-state
    cache must be fully evicted at drain.  This is the CI leg: the full
    sharded code path (mesh build, sharding constraints, dp-divisible
    buckets, state cache) runs everywhere.
    """
    from repro.launch.sharded import sharded_time_model
    from repro.serving.batch.batcher import BatchTimeModel
    from repro.serving.traffic import scenario_spec
    rows = []
    st = _stage_times()
    base_tm = BatchTimeModel.linear(st, DEFAULT_BUCKETS, marginal=0.15)
    goodput, admitted_miss = {}, {}
    for dp in dps:
        tm_dp = sharded_time_model(base_tm, dp,
                                   collective=SHARDED_COLLECTIVE)
        spec = scenario_spec("2x-overload", policy="rtdeepiot",
                             admission={"mode": "reject"}, stage_times=st,
                             n_requests=n_requests, seed=seed)
        # offered load scales with the provisioned width: every dp level
        # faces 2x of *its own* capacity, so goodput measures scaling,
        # not saturation against a fixed arrival rate
        spec.source_args["arrival"]["rate"] *= dp
        spec.batching = {}               # the time_model resource prices it
        res = Service.from_spec(spec, conf_table=conf, correct_table=correct,
                                time_model=tm_dp).run()
        _emit(rows, "sharded", f"dp={dp}", "rtdeepiot-admit", res)
        goodput[dp] = res.throughput
        admitted_miss[dp] = res.admitted_miss_rate
    e2e = _sharded_e2e(rows, n_requests=e2e_requests, seed=seed)
    return rows, dict(goodput=goodput, admitted_miss=admitted_miss,
                      dps=tuple(dps)), e2e


def _sharded_e2e(rows, n_requests=40, seed=0):
    """Real-model leg of the sharded figure: device-sharded vs
    device-batched on the same traffic scenario stream, virtual clock."""
    import dataclasses

    import jax

    import repro.launch.serve  # noqa: F401 — registers device-sharded
    from repro.configs import get_config
    from repro.models import init_params
    from repro.serving.traffic import scenario_spec

    cfg = get_config("anytime-classifier")
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    pool = rng.normal(size=(48, 1, 16, 32)).astype(np.float32)
    labels = rng.integers(0, cfg.vocab_size, size=48)
    st = (0.002, 0.003, 0.004)
    base = scenario_spec(
        "steady", policy="rtdeepiot",
        policy_args={"predictor": "exp", "prior_curve": [0.5, 0.7, 0.85]},
        stage_times=st, n_requests=n_requests, seed=seed)
    base.batching = {"buckets": [1, 2, 4], "stage_times": list(st),
                     "marginal": 0.25}
    runs = {}
    for ex, ea in (("device-batched", {}),
                   ("device-sharded", {"dp": 1, "tp": 1})):
        spec = dataclasses.replace(base, executor=ex, executor_args=ea)
        svc = Service.from_spec(
            spec, cfg=cfg, params=params, n_samples=len(pool), labels=labels,
            traffic_inputs=lambda s: {"features": pool[s]})
        res = svc.run()
        _emit(rows, "sharded", "e2e", ex, res)
        runs[ex] = (svc, res)

    def key(recs):
        return [(r["sample"], r["prediction"], r["conf"], r["depth"],
                 r["missed"]) for r in recs]
    sx = runs["device-sharded"][0].executor
    parity = key(runs["device-batched"][1].per_request) \
        == key(runs["device-sharded"][1].per_request)
    print(f"sharded,e2e,parity,mesh={sx.dp}x{sx.tp},bitwise={parity}")
    return dict(mesh=[sx.dp, sx.tp], parity=parity,
                cache=sx.cache_stats(), n_requests=n_requests,
                served=runs["device-sharded"][1].n_requests)


def sharded_claims(modeled, e2e):
    """Headline check for the sharded executor: goodput scales >= 0.6x
    linearly in dp at < 1% admitted misses under per-width 2x overload,
    and the end-to-end 1x1-mesh run matches device-batched bit-for-bit
    with a fully-evicted hidden-state cache."""
    dps = sorted(modeled["goodput"])
    g = modeled["goodput"]
    monotone = all(g[a] <= g[b] * 1.02 for a, b in zip(dps, dps[1:]))
    scaling = g[dps[-1]] / max(g[dps[0]], 1e-9)
    miss_max = max(modeled["admitted_miss"].values())
    cache_clean = e2e["cache"]["live"] == 0 \
        and e2e["cache"]["evictions"] >= e2e["n_requests"]
    # parity is bitwise only where both runs use one device — a real
    # multi-device mesh reorders float reductions
    parity_req = e2e["mesh"] != [1, 1]
    claims = {
        "sharded_collective_s": SHARDED_COLLECTIVE,
        "sharded_goodput_by_dp": {str(d): round(g[d], 1) for d in dps},
        "sharded_scaling": round(scaling, 2),
        "sharded_admitted_miss_max": round(miss_max, 4),
        "sharded_e2e_mesh": e2e["mesh"],
        "sharded_e2e_parity_bitwise": bool(e2e["parity"]),
        "sharded_e2e_cache": e2e["cache"],
        "sharded_claim_met": bool(
            monotone and scaling >= 0.6 * dps[-1] and miss_max < 0.01
            and (e2e["parity"] or parity_req) and cache_clean
            and e2e["served"] == e2e["n_requests"]),
    }
    print("SHARDED CLAIMS:", claims)
    return claims


# ragged traffic for the kernel figure: per-SLO-tier seq_len ranges
# spanning the length buckets (gold = full-length, bronze = short)
KERNEL_LEN_BUCKETS = (16, 64, 256)
KERNEL_SEQ_RANGES = {"gold": (96, 256), "silver": (24, 64), "bronze": (2, 16)}


def fig_kernel(conf, correct, async_comp, *, n_requests=1200,
               ragged_requests=900, e2e_requests=40, seed=0):
    """The ``device-kernel`` fast path (repro.launch.kernel), three parts.

    **Deep-pipeline modeled leg** — the async figure's charged-host-cost
    comparison extended to ``pipeline_depth=3``: the executor enqueues a
    second device window behind the running one, so the next window's
    policy selection *and* submit overhead happen inside an open window
    instead of serializing when the device idles.  Charged host-overhead
    fraction must drop to or below the async figure's floor at
    accuracy/miss equal-or-better than synchronous dispatch.

    **Ragged length-bucket leg** — 2x-overload traffic whose requests
    carry ragged ``seq_len`` (per-tier ``seq_range`` in the mix), priced
    by a ``LengthBucketTimeModel``: admission and batching charge
    ``(stage, batch-bucket, len-bucket)`` WCETs and same-stage co-runners
    batch only within a length bucket.  Admitted misses must stay < 1%.

    **End-to-end kernel leg** — ``ServeSpec(executor="device-kernel")``
    on the real anytime classifier through the ``steady`` traffic
    scenario: predictions/depths must match ``device-batched`` exactly
    (confidences to 1e-6 — the fused epilogue computes the same
    max-softmax probability by a different formula), the fused
    exit-confidence epilogue must be *bit-for-bit* the unfused reference
    in interpret mode, a ``pipeline_depth=3`` run must stack device
    windows and drain its hidden-state cache, and co-batched ragged
    decode must match singleton decode (preds exact, hidden states to
    float32 rounding).
    """
    from repro.serving.batch.time_model import LengthBucketTimeModel
    from repro.serving.traffic import scenario_spec
    rows = []
    # -- deep-pipeline modeled leg: depth 3 over the async figure's grid
    kw = dict(batched=True, charge_overhead=True,
              dispatch_overhead=ASYNC_DISPATCH_OVERHEAD,
              policy_cost=ASYNC_POLICY_COST)
    deep = {}
    for (k, name) in sorted(async_comp):
        p = "exp" if name == "rtdeepiot" else name
        res = _serve(_spec(p, pipeline_depth=3, **kw), conf, correct,
                     n_clients=k, n_requests=n_requests)
        _emit(rows, "kernel", f"K={k}", f"deep-{name}", res)
        deep[(k, name)] = dict(host_frac_deep=res.host_overhead_frac,
                               acc_deep=res.accuracy,
                               miss_deep=res.miss_rate)
    # -- ragged length-bucket leg --------------------------------------
    st = _stage_times()
    lb_tm = LengthBucketTimeModel.linear(st, DEFAULT_BUCKETS, marginal=0.15,
                                         len_buckets=KERNEL_LEN_BUCKETS)
    # the scenario's 2x is relative to the *unbatched full-length*
    # capacity; the ragged mix costs roughly half of full-length and
    # bucket-16 batching amortizes another ~4x, so 8x the nominal rate is
    # what actually sustains ~2x of this engine's mixed-length capacity.
    # headroom=4 makes admission price the full multi-stage cost (not the
    # amortized batch estimate) — rejections absorb the overload instead
    # of deadline misses
    spec = scenario_spec("2x-overload", policy="rtdeepiot",
                         admission={"mode": "reject", "headroom": 4.0},
                         stage_times=st, n_requests=ragged_requests,
                         seed=seed)
    spec.source_args["arrival"]["rate"] *= 4
    spec.batching = {}       # the LengthBucketTimeModel resource prices it
    spec.source_args["mix"] = [
        dict(c, seq_range=list(KERNEL_SEQ_RANGES[c["slo"]]))
        for c in spec.source_args["mix"]]
    res = Service.from_spec(spec, conf_table=conf, correct_table=correct,
                            time_model=lb_tm).run()
    _emit(rows, "kernel", "ragged-2x", "rtdeepiot-admit", res)
    ragged = dict(admitted_miss=res.admitted_miss_rate,
                  served_frac=1.0 - res.rejected / max(res.n_requests, 1),
                  rejected=res.rejected, mean_depth=res.mean_depth)
    e2e = _kernel_e2e(rows, n_requests=e2e_requests, seed=seed)
    e2e["decode"] = _kernel_decode_check()
    return rows, deep, ragged, e2e


def _kernel_e2e(rows, n_requests=40, seed=0):
    """Real-model leg of the kernel figure: device-kernel vs
    device-batched on the same traffic scenario stream, plus a depth-3
    run for window stacking, telemetry and cache drain."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    import repro.launch.serve  # noqa: F401 — registers device-kernel
    from repro.configs import get_config
    from repro.models import (exit_rows, exit_stats_fused,
                              exit_stats_unfused, init_params, stage_trunk)
    from repro.serving.traffic import scenario_spec

    cfg = get_config("anytime-classifier")
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    pool = rng.normal(size=(48, 1, 16, 32)).astype(np.float32)
    labels = rng.integers(0, cfg.vocab_size, size=48)
    st = (0.002, 0.003, 0.004)
    base = scenario_spec(
        "steady", policy="rtdeepiot",
        policy_args={"predictor": "exp", "prior_curve": [0.5, 0.7, 0.85]},
        stage_times=st, n_requests=n_requests, seed=seed)
    base.batching = {"buckets": [1, 2, 4], "stage_times": list(st),
                     "marginal": 0.25}
    runs = {}
    for label, ex, depth in (("device-batched", "device-batched", 1),
                             ("device-kernel", "device-kernel", 1),
                             ("device-kernel-deep", "device-kernel", 3)):
        spec = dataclasses.replace(base, executor=ex, pipeline_depth=depth)
        svc = Service.from_spec(
            spec, cfg=cfg, params=params, n_samples=len(pool), labels=labels,
            traffic_inputs=lambda s: {"features": pool[s]})
        res = svc.run()
        _emit(rows, "kernel", "e2e", label, res)
        runs[label] = (svc, res)

    def key(res):
        return [(r["sample"], r["prediction"], r["depth"], r["missed"])
                for r in res.per_request]
    parity = key(runs["device-batched"][1]) == key(runs["device-kernel"][1])
    conf_close = bool(np.allclose(
        [r["conf"] for r in runs["device-kernel"][1].per_request],
        [r["conf"] for r in runs["device-batched"][1].per_request],
        rtol=1e-6))
    # fused epilogue vs unfused reference on the same trunk output — the
    # bit-for-bit claim (the kernel's online pass folds exactly once on a
    # single vocab block, so interpret mode reproduces the reference)
    h = stage_trunk(cfg, params, 0, {"features": jnp.asarray(pool[:8, 0])},
                    mode="train")
    rws = exit_rows(cfg, h)
    fused = exit_stats_fused(rws, params["exits"][0]["ln"],
                             params["exit_shared"]["w_out"],
                             eps=cfg.norm_eps)
    unfused = exit_stats_unfused(rws, params["exits"][0]["ln"],
                                 params["exit_shared"]["w_out"],
                                 eps=cfg.norm_eps)
    fused_bitwise = all(np.array_equal(np.asarray(a), np.asarray(b))
                        for a, b in zip(fused, unfused))
    dsvc, dres = runs["device-kernel-deep"]
    times = dres.executor_times
    print(f"kernel,e2e,parity,pred_depth={parity},conf_close={conf_close},"
          f"fused_bitwise={fused_bitwise},windows={dsvc.executor.max_inflight}")
    return dict(parity=bool(parity), conf_close=conf_close,
                fused_bitwise=bool(fused_bitwise),
                max_inflight=dsvc.executor.max_inflight,
                host_time=round(float(times.get("host_time", 0.0)), 4),
                device_time=round(float(times.get("device_time", 0.0)), 4),
                cache=dres.executor_cache, n_requests=n_requests,
                served=dres.n_requests)


def _kernel_decode_check():
    """Ragged decode batching exactness: co-batched decode at ragged
    cache positions through the Pallas route must be bitwise equal to a
    same-shape batch of each request alone, and give each request the
    prediction it gets at batch 1 with hidden states, confidences and
    cache rows equal to float32 rounding (1e-5 of the hidden scale — XLA
    does not promise batch-shape-invariant bits), thanks to the per-row
    slot-position map; the legacy jnp route shares row 0's and is only
    approximately equal."""
    import jax

    from repro.configs.base import ModelConfig
    from repro.launch.kernel import KernelDecodeStageFns, ragged_decode_check
    from repro.launch.mesh import make_serving_mesh
    from repro.models import ParallelCtx, init_params
    cfg = ModelConfig(name="bench-decode", arch_type="dense", source="bench",
                      num_layers=2, d_model=32, num_heads=2, num_kv_heads=2,
                      head_dim=16, d_ff=64, vocab_size=16, period=("attn",),
                      ffn_type="swiglu", modality="text", causal=True,
                      num_stages=2, mandatory_stages=1, stage_ends=(1, 2),
                      dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    ctx = ParallelCtx(mesh=make_serving_mesh(1, 1), decode_attn="kernel")
    fns = KernelDecodeStageFns(cfg, (1, 2, 4), ctx)
    positions = [2, 5, 7]
    r = ragged_decode_check(fns, params, positions, 8, seed=0)
    tol = 1e-5 * max(1.0, r["h_scale"])
    exact = bool(r["same_shape_equal"] and r["pred_equal"]
                 and r["h_err"] <= tol
                 and r["cache_err"] <= tol and r["conf_err"] <= 1e-6)
    print(f"kernel,decode,ragged,positions={positions},exact={exact},"
          f"h_err={r['h_err']:.3g},conf_err={r['conf_err']:.3g}")
    return dict(exact=exact, positions=positions, h_err=r["h_err"],
                conf_err=r["conf_err"])


def kernel_claims(deep, ragged, e2e, async_comp):
    """Headline check for the kernel fast path: depth-3 dispatch holds
    charged host-overhead at or below the async figure's floor at
    accuracy/miss equal-or-better than synchronous dispatch; the fused
    exit epilogue is bit-for-bit the unfused reference; ragged traffic
    batched via length buckets keeps admitted misses < 1%; co-batched
    ragged decode matches singleton decode (preds exact, hidden states
    to float32 rounding)."""
    floor = min(c["host_frac_async"] for c in async_comp.values())
    qualifying = {}
    for (k, name), d in deep.items():
        c = async_comp[(k, name)]
        if (d["host_frac_deep"] <= floor
                and d["acc_deep"] >= c["acc_sync"]
                and d["miss_deep"] <= c["miss_sync"]):
            qualifying[f"K={k}/{name}"] = round(d["host_frac_deep"], 4)
    by_k = {}
    for (k, name) in deep:
        by_k.setdefault(k, []).append(f"K={k}/{name}" in qualifying)
    full_ks = sorted(k for k, oks in by_k.items() if all(oks))
    dec = e2e["decode"]
    claims = {
        "kernel_async_floor_host_frac": round(floor, 4),
        "kernel_deep_host_frac": {
            f"K={k}/{n}": round(d["host_frac_deep"], 4)
            for (k, n), d in sorted(deep.items())},
        "kernel_deep_qualifying_configs": qualifying,
        "kernel_deep_fully_qualifying_K": full_ks,
        "kernel_len_buckets": list(KERNEL_LEN_BUCKETS),
        "kernel_ragged_admitted_miss": round(ragged["admitted_miss"], 4),
        "kernel_ragged_served_frac": round(ragged["served_frac"], 4),
        "kernel_e2e_parity_pred_depth": bool(e2e["parity"]),
        "kernel_e2e_conf_allclose": bool(e2e["conf_close"]),
        "kernel_fused_exit_bitwise": bool(e2e["fused_bitwise"]),
        "kernel_e2e_windows": e2e["max_inflight"],
        "kernel_e2e_times": {"host_time": e2e["host_time"],
                             "device_time": e2e["device_time"]},
        "kernel_e2e_cache": e2e["cache"],
        "kernel_decode_ragged_exact": bool(dec["exact"]),
        "kernel_claim_met": bool(
            full_ks and ragged["admitted_miss"] < 0.01
            and ragged["rejected"] > 0 and e2e["parity"]
            and e2e["conf_close"] and e2e["fused_bitwise"]
            and dec["exact"] and e2e["cache"]["live"] == 0
            and e2e["served"] == e2e["n_requests"]),
    }
    print("KERNEL CLAIMS:", claims)
    return claims


# durable plane fairness scenario (repro.serving.plane): ~2x sustained
# overload from a heavy background tenant against a light premium tenant
# submitting at its fair share, 10:1 tenant weight skew in the light
# tenant's favor.  EDF executes optional stages of admitted work, so the
# admission headroom prices the full 3-stage cost (~5x the amortized
# mandatory-only estimate) — that is what keeps admitted misses ~0.
PLANE_HEAVY_N = 190
PLANE_HEAVY_SPAN = 2.0
PLANE_LIGHT_N = 8
PLANE_LIGHT_PERIOD = 0.25
PLANE_REL_DEADLINE = 0.08


def _plane_spec(discipline):
    return ServeSpec(
        policy="edf", executor="oracle", clock="virtual",
        source="frontdoor",
        source_args={"discipline": discipline, "run_queue": 2},
        tenants={"light": {"weight": 10.0}, "heavy": {"weight": 1.0}},
        admission={"mode": "reject", "headroom": 5.0},
        default_slo="gold",
        slo_classes={"gold": {"rel_deadline": PLANE_REL_DEADLINE}},
        batching={"mode": "none", "stage_times": list(_stage_times())})


def fig_plane(conf, correct):
    """Durable request plane (repro.serving.plane): DRR fairness vs a
    global-FIFO front door under tenant-skewed overload, idempotent
    journaled submission, and mid-stream crash recovery."""
    import shutil
    import tempfile
    import time as _time

    from repro.serving import (DurableQueue, FrontDoor, Journal, recover,
                               verify_recovery)
    from repro.serving.engine import Request

    rows, data = [], {}
    # -- fairness: DRR vs FIFO release order under tenant skew ----------
    for disc in ("drr", "fifo"):
        svc = Service.from_spec(_plane_spec(disc), conf_table=conf,
                                correct_table=correct)
        for i in range(PLANE_HEAVY_N):
            svc.submit(Request(None, sample=i % conf.shape[0],
                               tenant="heavy", request_id=f"h{i}"),
                       at=i * (PLANE_HEAVY_SPAN / PLANE_HEAVY_N))
        for i in range(PLANE_LIGHT_N):
            svc.submit(Request(None, sample=(7 * i) % conf.shape[0],
                               tenant="light", request_id=f"l{i}"),
                       at=i * PLANE_LIGHT_PERIOD)
        res = svc.drain()
        _emit(rows, "plane", "tenant-skew", disc, res)
        data[disc] = dict(
            light_served_frac=res.per_tenant["light"]["served"]
            / PLANE_LIGHT_N,
            heavy_served_frac=res.per_tenant["heavy"]["served"]
            / PLANE_HEAVY_N,
            admitted_miss=res.admitted_miss_rate)
        print(f"plane,tenant-skew,{disc},"
              f"light={data[disc]['light_served_frac']:.2f},"
              f"heavy={data[disc]['heavy_served_frac']:.2f},"
              f"amiss={data[disc]['admitted_miss']:.4f}")

    # -- idempotency + crash recovery through the journal ---------------
    spec = _plane_spec("drr")
    workdir = tempfile.mkdtemp(prefix="plane-bench-")
    try:
        ref_dir = os.path.join(workdir, "ref")
        crash_dir = os.path.join(workdir, "crash")
        n = 60
        dedup_ok = True

        def durable_run(d):
            nonlocal dedup_ok
            with Journal(d, spec=spec, fsync_every=1) as j:
                svc = Service.from_spec(spec, conf_table=conf,
                                        correct_table=correct)
                door = FrontDoor(svc, journal=j)
                hs = {}
                for i in range(n):
                    rid = f"r{i:03d}"
                    hs[rid] = door.submit(
                        Request(None, sample=i % conf.shape[0]),
                        tenant="light" if i % 5 == 0 else "heavy",
                        request_id=rid, at=i * 0.01)
                dup = door.submit(Request(None, sample=0), tenant="heavy",
                                  request_id="r001", at=0.5)
                dedup_ok &= (dup is hs["r001"]
                             and j.counts["SUBMIT"] == n)
                return svc.drain()

        ref = durable_run(ref_dir)
        durable_run(crash_dir)
        # crash: drop every journaled terminal after the 10th
        seg = os.path.join(crash_dir, "wal-000000.jsonl")
        kept, n_term = [], 0
        with open(seg) as f:
            for line in f:
                if '"kind": "RETIRE"' in line or '"kind": "REJECT"' in line:
                    n_term += 1
                    if n_term > 10:
                        continue
                kept.append(line)
        with open(seg, "w") as f:
            f.writelines(kept)
        t0 = _time.perf_counter()
        res = recover(crash_dir, conf_table=conf, correct_table=correct)
        dt = _time.perf_counter() - t0
        rep = verify_recovery(ref.per_request, res)
        _emit(rows, "plane", "recovery", "drr", res.metrics)
        data["recovery"] = dict(
            bitwise=bool(rep["bitwise"]),
            delivered_once=bool(rep["delivered_once"]),
            overlap_consistent=bool(rep["overlap_consistent"]),
            recovered=bool(rep["recovered"]),
            n_pre=res.report["n_pre_delivered"],
            n_redelivered=res.report["n_redelivered"],
            recover_seconds=round(dt, 3))
        data["idempotent_dedup"] = bool(dedup_ok)
        print(f"plane,recovery,drr,bitwise={rep['bitwise']},"
              f"once={rep['delivered_once']},"
              f"pre={res.report['n_pre_delivered']},"
              f"redone={res.report['n_redelivered']},t={dt:.3f}s")
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    return rows, data


def plane_claims(data):
    """Headline check for the durable plane: at ~2x overload with a 10:1
    tenant weight skew, DRR keeps the light tenant at >= 90% of its fair
    share while the FIFO front door starves it to <= 60%, both below 1%
    admitted misses; duplicate request_ids are provably idempotent and a
    mid-stream crash recovers bit-for-bit with exactly-once delivery."""
    drr, fifo, rec = data["drr"], data["fifo"], data["recovery"]
    claims = {
        "plane_drr_light_served_frac": round(drr["light_served_frac"], 4),
        "plane_fifo_light_served_frac": round(fifo["light_served_frac"], 4),
        "plane_admitted_miss": {
            "drr": round(drr["admitted_miss"], 4),
            "fifo": round(fifo["admitted_miss"], 4)},
        "plane_idempotent_dedup": bool(data["idempotent_dedup"]),
        "plane_recovery": rec,
        "plane_claim_met": bool(
            drr["light_served_frac"] >= 0.9
            and fifo["light_served_frac"] <= 0.6
            and drr["admitted_miss"] <= 0.01
            and fifo["admitted_miss"] <= 0.01
            and data["idempotent_dedup"] and rec["recovered"]),
    }
    print("PLANE CLAIMS:", claims)
    return claims


# the two-model zoo the zoo figure serves: an expensive high-weight "llm"
# head next to a cheap "vision" model on one device (3 anytime stages
# each — the oracle tables' depth axis)
ZOO_MODELS = {
    "llm": {"stage_times": [0.006, 0.010, 0.014], "marginal": 0.15,
            "weight": 2.0},
    "vision": {"stage_times": [0.003, 0.005, 0.007], "marginal": 0.15},
}


def _zoo_mix_stage_times():
    """Capacity anchor for the ``model-mix`` scenario: the mix-weighted
    mean per-stage times, so the scenario's 2.0x factor is 2x of the
    *blended* full-depth capacity (anchoring on either model alone would
    under- or over-state the overload)."""
    from repro.serving.traffic.scenarios import MODEL_MIX
    L = len(ZOO_MODELS["llm"]["stage_times"])
    tot = sum(c["share"] for c in MODEL_MIX)
    return tuple(
        sum(c["share"] * ZOO_MODELS[c["model"]]["stage_times"][s]
            for c in MODEL_MIX) / tot
        for s in range(L))


def _zoo_tables(conf, correct):
    """Per-model oracle tables: llm reads the trained tables as-is,
    vision a sample-rolled view — per-sample curves differ across models
    while confidence/correctness stay consistent within each."""
    roll = conf.shape[0] // 3
    return {"llm": {"conf": conf, "correct": correct},
            "vision": {"conf": np.roll(conf, roll, axis=0),
                       "correct": np.roll(correct, roll, axis=0)}}


def _zoo_weighted(res, ztabs):
    """Weighted admitted accuracy with the paper's utility-accrual
    semantics (a missed deadline earns zero, whatever the late answer
    was); weights are the end-to-end ``Task.weight`` = SLO utility
    weight x model weight."""
    num = den = 0.0
    adm = miss = 0
    for r in res.per_request:
        if r["rejected"]:
            continue
        adm += 1
        miss += int(r["missed"])
        w = float(r.get("weight") or 1.0)
        den += w
        ok = (not r["missed"]) and r["depth"] >= 1 and bool(
            ztabs[r["model"]]["correct"][r["sample"], r["depth"] - 1])
        num += w * float(ok)
    return dict(weighted_acc=num / den if den else 0.0,
                admitted_miss=miss / adm if adm else 0.0, admitted=adm)


def fig_zoo(conf, correct, n_requests=600, e2e_requests=24, seed=0):
    """The multi-model zoo (repro.serving.zoo), two parts.

    **Cross-model preemption** — the ``model-mix`` scenario (2x of the
    blended two-model capacity) through ``policy="rtdeepiot-zoo"`` with
    admission on, ``scope="global"`` (one FPTAS over both models: sheds
    the globally least-valuable optional stages, whichever model owns
    them) vs ``scope="siloed"`` (each model planned independently against
    the full device — every silo believes it owns the machine, so the
    union plan overcommits).  Scored on weighted admitted accuracy.

    **Single-model parity** — a one-model zoo spec
    (``executor="zoo-device"`` + ``rtdeepiot-zoo``) on the real anytime
    classifier must reproduce the plain ``device-batched`` +
    ``rtdeepiot`` run **bit-for-bit**: the blended time model of a
    single-member zoo *is* that member's table, so the zoo machinery adds
    nothing but the model id.
    """
    from repro.serving.traffic import scenario_spec
    rows = []
    st = _zoo_mix_stage_times()
    ztabs = _zoo_tables(conf, correct)
    data = {"models": {m: dict(cfg) for m, cfg in ZOO_MODELS.items()}}
    for label, scope in (("zoo-global", "global"), ("zoo-siloed", "siloed")):
        spec = _dc.replace(
            scenario_spec(
                "model-mix", policy="rtdeepiot-zoo",
                policy_args={"predictor": "exp", "scope": scope},
                admission={"mode": "reject"}, stage_times=st,
                n_requests=n_requests, seed=seed, models=ZOO_MODELS),
            executor="zoo-oracle")
        res = Service.from_spec(spec, zoo_tables=ztabs,
                                n_samples=conf.shape[0]).run()
        _emit(rows, "zoo", "model-mix", label, res)
        data[scope] = _zoo_weighted(res, ztabs)
        data[scope]["per_model"] = res.per_model
        for m, pm in sorted(res.per_model.items()):
            print(f"zoo,model-mix/{m},{label},served={pm['served']},"
                  f"rejected={pm['rejected']},miss={pm['miss_rate']:.4f},"
                  f"depth={pm['mean_depth']:.2f},"
                  f"wacc={pm['weighted_accuracy']}")
        print(f"zoo,model-mix,{label},"
              f"wacc={data[scope]['weighted_acc']:.4f},"
              f"amiss={data[scope]['admitted_miss']:.4f},"
              f"admitted={data[scope]['admitted']}")
    e2e = _zoo_e2e(rows, n_requests=e2e_requests, seed=seed)
    return rows, data, e2e


def _zoo_e2e(rows, n_requests=24, seed=0):
    """Real-model leg of the zoo figure: a single-member zoo
    (zoo-device + rtdeepiot-zoo) vs the plain device-batched path on the
    same traffic stream, virtual clock, bit-for-bit."""
    import dataclasses

    import jax

    import repro.launch.serve  # noqa: F401 — registers zoo-device
    from repro.configs import get_config
    from repro.models import init_params
    from repro.serving.traffic import scenario_spec

    cfg = get_config("anytime-classifier")
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    pool = rng.normal(size=(48, 1, 16, 32)).astype(np.float32)
    labels = rng.integers(0, cfg.vocab_size, size=48)
    st = (0.002, 0.003, 0.004)
    base = scenario_spec(
        "steady", policy="rtdeepiot",
        policy_args={"predictor": "exp", "prior_curve": [0.5, 0.7, 0.85]},
        stage_times=st, n_requests=n_requests, seed=seed)
    base.batching = {"buckets": [1, 2, 4], "stage_times": list(st),
                     "marginal": 0.25}
    # the zoo leg: same stream, every request tagged with the one model
    zspec = dataclasses.replace(
        base, executor="zoo-device", policy="rtdeepiot-zoo",
        models={"m": {"stage_times": list(st), "buckets": [1, 2, 4],
                      "marginal": 0.25}},
        source_args={**base.source_args,
                     "mix": [dict(c, model="m")
                             for c in base.source_args["mix"]]})
    common = dict(cfg=cfg, params=params, n_samples=len(pool),
                  labels=labels,
                  traffic_inputs=lambda s: {"features": pool[s]})
    runs = {}
    for name, spec, extra in (
            ("device-batched",
             dataclasses.replace(base, executor="device-batched"), {}),
            ("zoo-device", zspec,
             {"zoo_models": {"m": {"cfg": cfg, "params": params}}})):
        svc = Service.from_spec(spec, **common, **extra)
        res = svc.run()
        _emit(rows, "zoo", "e2e", name, res)
        runs[name] = (svc, res)

    def key(recs):
        return [(r["sample"], r["prediction"], r["conf"], r["depth"],
                 r["missed"]) for r in recs]
    zx = runs["zoo-device"][0].executor
    parity = key(runs["device-batched"][1].per_request) \
        == key(runs["zoo-device"][1].per_request)
    print(f"zoo,e2e,parity,bitwise={parity}")
    return dict(parity=parity, cache=zx.cache_stats(),
                n_requests=n_requests,
                served=runs["zoo-device"][1].n_requests)


def zoo_claims(data, e2e):
    """Headline check for the model zoo: under 2x mixed-model overload,
    global cross-model shedding scores >= per-model-siloed planning on
    weighted admitted accuracy at < 1% admitted misses, and a
    single-member zoo spec reproduces the device-batched path
    bit-for-bit with a fully-evicted state cache."""
    g, s = data["global"], data["siloed"]
    cache_clean = e2e["cache"]["live"] == 0 \
        and e2e["cache"]["evictions"] >= e2e["n_requests"]
    claims = {
        "zoo_models": sorted(data["models"]),
        "zoo_overload_weighted_admitted_acc": {
            "global": round(g["weighted_acc"], 4),
            "siloed": round(s["weighted_acc"], 4)},
        "zoo_overload_admitted_miss": {
            "global": round(g["admitted_miss"], 4),
            "siloed": round(s["admitted_miss"], 4)},
        "zoo_overload_admitted": {"global": g["admitted"],
                                  "siloed": s["admitted"]},
        "zoo_e2e_parity_bitwise": bool(e2e["parity"]),
        "zoo_e2e_cache": e2e["cache"],
        "zoo_claim_met": bool(
            g["weighted_acc"] >= s["weighted_acc"] - 1e-9
            and g["admitted_miss"] < 0.01
            and e2e["parity"] and cache_clean
            and e2e["served"] == e2e["n_requests"]),
    }
    print("ZOO CLAIMS:", claims)
    return claims


def fig_obs(conf, correct, *, k=32, n_requests=600, reps=3,
            overload_requests=300, write_trace=False):
    """Observability layer (repro.serving.obs): the acceptance bar is
    that full tracing is cheap enough to leave on — measured wall-clock
    overhead on the batch figure's config, plus the three correctness
    claims (bitwise parity, audit coverage at 2x overload, valid Chrome
    trace_event export)."""
    import time

    from repro.serving import validate_chrome_trace
    from repro.serving.traffic import scenario_spec

    rows = []
    wl_kwargs = dict(n_clients=k, n_requests=n_requests)
    base = _spec("exp", batched=True, admission={"mode": "depth_cap"})

    def run_once(trace):
        spec = _dc.replace(base, trace=dict(trace))
        t0 = time.perf_counter()
        res = _serve(spec, conf, correct, **wl_kwargs)
        return time.perf_counter() - t0, res

    # interleaved best-of-reps: tracing-on and -off alternate so drift
    # (thermal, allocator state) hits both arms equally
    best = {"off": float("inf"), "on": float("inf")}
    res_off = res_on = None
    for _ in range(reps):
        for label, trace in (("off", {}), ("on", {"enabled": True})):
            dt, res = run_once(trace)
            if dt < best[label]:
                best[label] = dt
            if label == "off":
                res_off = res
            else:
                res_on = res
    overhead = best["on"] / best["off"] - 1.0
    _emit(rows, "obs", f"K={k}", "batched-rtdeepiot", res_off)
    _emit(rows, "obs", f"K={k}", "batched-rtdeepiot-traced", res_on)
    print(f"obs,K={k},trace_overhead={overhead:+.4f} "
          f"(off={best['off']:.3f}s on={best['on']:.3f}s)")

    def _sig(res):
        obs_keys = ("queue_wait", "host_time", "device_time", "decision",
                    "tid")
        per = [tuple(sorted((kk, vv) for kk, vv in r.items()
                            if kk not in obs_keys))
               for r in res.per_request]
        return (res.accuracy, res.miss_rate, res.mean_depth, res.mean_conf,
                res.makespan, res.throughput, res.n_dispatches, per)

    bitwise = _sig(res_on) == _sig(res_off)

    # audit coverage: every rejected/capped request at 2x overload has an
    # audit entry naming the rule that fired
    spec = scenario_spec("2x-overload", stage_times=_stage_times(),
                         n_requests=overload_requests,
                         admission={"mode": "reject", "headroom": 3.0},
                         trace={"enabled": True})
    svc = Service.from_spec(spec, conf_table=conf, correct_table=correct)
    svc.run()
    audited = {row["tid"] for row in svc.obs.audit_log}
    degraded = [tr for tr in svc.obs.traces.values()
                if tr.rejected or tr.depth_cap is not None]
    coverage = (sum(1 for tr in degraded if tr.tid in audited)
                / len(degraded)) if degraded else 0.0
    print(f"obs,2x-overload,degraded={len(degraded)},"
          f"audit_rows={len(svc.obs.audit_log)},coverage={coverage:.3f}")

    doc = svc.obs.chrome_trace()
    problems = validate_chrome_trace(doc)
    if write_trace:
        os.makedirs(ART, exist_ok=True)
        path = os.path.join(ART, "obs_trace.json")
        with open(path, "w") as f:
            json.dump(doc, f)
        print(f"obs,chrome_trace,{path},{len(doc['traceEvents'])} events")
    data = dict(overhead=overhead, bitwise=bitwise, coverage=coverage,
                chrome_problems=problems, n_degraded=len(degraded))
    return rows, data


def fig_adaptive(conf, correct, n_requests=600, seed=11):
    """Adaptive control (repro.serving.adaptive), three parts.

    **Workload identification** — record "yesterday's" flash-crowd run,
    fit every arrival kind from the per_request offsets, and check
    :func:`fit_report` names ``flash-crowd`` as the best explanation.

    **Predictive vs reactive admission** — replay "today" (same process,
    different seed) twice: a reactive static ``depth_cap`` controller vs
    the same controller armed with yesterday's fitted process as a
    forecast.  The forecast sheds optional stages *before* the spike
    lands, so the predictive arm takes strictly fewer admitted deadline
    misses at equal-or-better admitted accuracy.

    **Learned curves vs the oracle table** — ``rtdeepiot-adaptive``
    (FPTAS against an :class:`OnlineCurveEstimator` fed by observed
    stage exits) warms its tables on one steady run, then a measured
    run on fresh traffic must land within 2% of the oracle-predictor
    policy's accuracy.

    Runs at full size even under ``--smoke``: all seven runs are
    virtual-clock and the claims' margins don't survive shrinking (a
    spike-truncated record reads as MMPP, not flash-crowd).
    """
    from repro.serving.adaptive import OnlineCurveEstimator, fit_report
    from repro.serving.traffic import scenario_spec
    rows = []
    st = _stage_times()
    data = {}

    def scen_run(name, *, policy="rtdeepiot", pargs=None, admission=None,
                 run_seed=0, **res):
        spec = scenario_spec(name, policy=policy,
                             policy_args=pargs
                             if pargs is not None else {"predictor": "exp"},
                             admission=admission or {}, stage_times=st,
                             n_requests=n_requests, seed=run_seed)
        return Service.from_spec(spec, conf_table=conf,
                                 correct_table=correct, **res).run()

    # -- yesterday: record, fit, identify -------------------------------
    rec = scen_run("flash-crowd", admission={"mode": "depth_cap"},
                   run_seed=seed)
    fit = fit_report([r["offset"] for r in rec.per_request])
    data["fit"] = {"best": fit["best"], "scores": fit["scores"],
                   "n_arrivals": fit["n_arrivals"],
                   "params": fit["fits"][fit["best"]]}
    print(f"adaptive,fit,best={fit['best']},"
          + ",".join(f"{k}={v}" for k, v in sorted(fit["scores"].items())))
    # horizon 0.1: long lookahead over-caps the pre-spike lull and costs
    # admitted accuracy on the trained tables; 0.1 still clears the spike
    forecast = {"process": fit["fits"][fit["best"]], "horizon": 0.1}

    # -- today: reactive vs forecast-armed admission --------------------
    arms = {}
    for label, adm in (("reactive", {"mode": "depth_cap"}),
                       ("predictive", {"mode": "depth_cap",
                                       "forecast": forecast})):
        res = scen_run("flash-crowd", admission=adm, run_seed=seed + 1)
        _emit(rows, "adaptive", "flash-crowd", label, res)
        n_admitted = res.n_requests - res.rejected
        arms[label] = {
            "admitted_misses": int(round(res.admitted_miss_rate
                                         * n_admitted)),
            "admitted_accuracy": res.admitted_accuracy,
            "capped": res.capped}
        print(f"adaptive,flash-crowd,{label},"
              f"admitted_misses={arms[label]['admitted_misses']},"
              f"admitted_acc={arms[label]['admitted_accuracy']:.4f},"
              f"capped={arms[label]['capped']}")
    data["admission"] = arms

    # -- learned curves vs the oracle table -----------------------------
    oracle = scen_run("steady", pargs={"predictor": "oracle"},
                      run_seed=seed + 11)
    _emit(rows, "adaptive", "steady", "rtdeepiot-oracle", oracle)
    est = OnlineCurveEstimator(num_stages=conf.shape[1],
                               prior=[0.5, 0.7, 0.85])
    warmup = scen_run("steady", policy="rtdeepiot-adaptive", pargs={},
                      run_seed=seed + 10, curve_estimator=est)
    _emit(rows, "adaptive", "steady-warmup", "rtdeepiot-adaptive", warmup)
    warm = scen_run("steady", policy="rtdeepiot-adaptive", pargs={},
                    run_seed=seed + 11, curve_estimator=est)
    _emit(rows, "adaptive", "steady", "rtdeepiot-adaptive", warm)
    data["curves"] = {"oracle_acc": oracle.accuracy,
                      "adaptive_acc": warm.accuracy,
                      "n_observed": est.n_observed,
                      "learned_curve": [round(float(x), 4)
                                        for x in est.curve()]}
    print(f"adaptive,steady,curves,oracle={oracle.accuracy:.4f},"
          f"adaptive={warm.accuracy:.4f},n_observed={est.n_observed}")
    return rows, data


def adaptive_claims(data):
    """Headline check for adaptive control: the fitted report identifies
    the flash-crowd workload, forecast-armed admission takes strictly
    fewer admitted deadline misses than the reactive controller at
    equal-or-better admitted accuracy, and the learned-curve policy
    lands within 2% of the oracle-table policy after one warm-up run."""
    adm, cur = data["admission"], data["curves"]
    claims = {
        "adaptive_fit_best": data["fit"]["best"],
        "adaptive_admitted_misses": {
            "reactive": adm["reactive"]["admitted_misses"],
            "predictive": adm["predictive"]["admitted_misses"]},
        "adaptive_admitted_accuracy": {
            "reactive": round(adm["reactive"]["admitted_accuracy"], 4),
            "predictive": round(adm["predictive"]["admitted_accuracy"], 4)},
        "adaptive_oracle_gap": round(cur["adaptive_acc"]
                                     - cur["oracle_acc"], 4),
        "adaptive_learned_curve": cur["learned_curve"],
        "adaptive_claim_met": bool(
            data["fit"]["best"] == "flash-crowd"
            and adm["predictive"]["admitted_misses"]
            < adm["reactive"]["admitted_misses"]
            and adm["predictive"]["admitted_accuracy"]
            >= adm["reactive"]["admitted_accuracy"] - 1e-9
            and cur["adaptive_acc"] >= cur["oracle_acc"] - 0.02),
    }
    print("ADAPTIVE CLAIMS:", claims)
    return claims


def obs_claims(data, gate_overhead=True):
    """Headline check for the observability layer: full tracing costs
    < 5% wall clock on the batch figure, schedules bit-for-bit
    identically, audits every degraded request, and exports a valid
    Chrome trace_event document.  ``gate_overhead=False`` drops the
    overhead bound from the verdict — the smoke leg's runs are too
    short (~0.1s) for the wall-clock fraction to be signal; the
    ``--only obs`` leg measures it at full size and asserts it."""
    claims = {
        "obs_trace_overhead_frac": round(data["overhead"], 4),
        "obs_bitwise_identical": bool(data["bitwise"]),
        "obs_audit_coverage": round(data["coverage"], 4),
        "obs_chrome_trace_valid": not data["chrome_problems"],
        "obs_claim_met": bool(
            (not gate_overhead or data["overhead"] < 0.05)
            and data["bitwise"] and data["coverage"] == 1.0
            and not data["chrome_problems"]),
    }
    print("OBS CLAIMS:", claims)
    return claims


def summarize_claims(all_rows):
    """Validate the paper's headline claims on our reproduction."""
    byfig = {}
    for r in all_rows:
        byfig.setdefault((r["figure"], r["config"]), {})[r["policy"]] = r
    gains, exp_vs_opt = [], []
    per_baseline = {b: [] for b in ("edf", "lcf", "rr")}
    miss_rt, miss_edf = [], []
    for (fig, cfgk), pol in byfig.items():
        if fig in ("fig6_7", "fig8_9", "fig10_11") and "rtdeepiot" in pol:
            base = max(pol[p]["accuracy"] for p in ("edf", "lcf", "rr")
                       if p in pol)
            gains.append(pol["rtdeepiot"]["accuracy"] - base)
            for b in per_baseline:
                if b in pol:
                    per_baseline[b].append(pol["rtdeepiot"]["accuracy"]
                                           - pol[b]["accuracy"])
            miss_rt.append(pol["rtdeepiot"]["miss_rate"])
            if "edf" in pol:
                miss_edf.append(pol["edf"]["miss_rate"])
        if fig.startswith("fig3") and "rtdeepiot-exp" in pol \
                and "rtdeepiot-oracle" in pol:
            exp_vs_opt.append(pol["rtdeepiot-oracle"]["accuracy"]
                              - pol["rtdeepiot-exp"]["accuracy"])
    claims = {
        "max_gain_over_best_baseline": max(gains) if gains else None,
        "mean_gain_over_best_baseline": float(np.mean(gains)) if gains else None,
        "mean_gain_over_edf": float(np.mean(per_baseline["edf"])),
        "max_gain_over_edf": float(np.max(per_baseline["edf"])),
        "mean_gain_over_lcf": float(np.mean(per_baseline["lcf"])),
        "mean_gain_over_rr": float(np.mean(per_baseline["rr"])),
        "rtdeepiot_mean_miss": float(np.mean(miss_rt)),
        "edf_mean_miss": float(np.mean(miss_edf)),
        "exp_within_of_oracle_mean": float(np.mean(exp_vs_opt))
        if exp_vs_opt else None,
    }
    print("CLAIMS:", claims)
    return claims


def batch_claims(speedups):
    """Headline check for the batched subsystem: at some offered load the
    batched engine sustains >= 3x unbatched goodput without giving up
    accuracy (>= unbatched - 1 point)."""
    qualifying = {f"K={k}/{name}": round(sp, 2)
                  for (k, name), (sp, dacc) in speedups.items()
                  if sp >= 3.0 and dacc >= -0.01}
    best = max(sp for sp, _ in speedups.values())
    claims = {"batch_best_speedup": round(best, 2),
              "batch_speedup_ge_3x_configs": qualifying,
              "batch_claim_met": bool(qualifying)}
    print("BATCH CLAIMS:", claims)
    return claims


def async_claims(comp):
    """Headline check for pipelined dispatch: strictly lower charged
    host-overhead fraction than synchronous batched dispatch at
    equal-or-better accuracy and miss rate, K >= 16."""
    qualifying = {}
    for (k, name), c in comp.items():
        if (c["host_frac_async"] < c["host_frac_sync"]
                and c["acc_delta"] >= 0.0 and c["miss_delta"] <= 0.0):
            qualifying[f"K={k}/{name}"] = dict(
                host_frac=f"{c['host_frac_sync']:.4f}->"
                          f"{c['host_frac_async']:.4f}",
                goodput_ratio=round(c["goodput_ratio"], 3))
    reduction = [c["host_frac_sync"] - c["host_frac_async"]
                 for c in comp.values()]
    # claim met only where a whole load level qualifies: some K >= 16 at
    # which EVERY measured policy shows the improvement
    by_k = {}
    for (k, name) in comp:
        by_k.setdefault(k, []).append(f"K={k}/{name}" in qualifying)
    full_ks = sorted(k for k, oks in by_k.items() if k >= 16 and all(oks))
    claims = {
        "async_policy_cost": ASYNC_POLICY_COST,
        "async_dispatch_overhead": ASYNC_DISPATCH_OVERHEAD,
        "async_mean_host_frac_reduction": float(np.mean(reduction)),
        "async_qualifying_configs": qualifying,
        "async_fully_qualifying_K": full_ks,
        "async_claim_met": bool(full_ks),
    }
    print("ASYNC CLAIMS:", claims)
    return claims


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny workloads, synthetic tables if artifact "
                         "missing, no artifact writes (CI job)")
    ap.add_argument("--only", choices=("plane", "zoo", "obs", "adaptive"),
                    default=None,
                    help="run a single figure and merge its rows/claims "
                         "into artifacts/scheduling_results.json")
    args = ap.parse_args(argv)

    if args.only is not None:
        # partial regen: these figures need no trained artifact —
        # synthetic tables are deterministic and the claims are about
        # scheduling, not accuracy
        path = os.path.join(ART, "oracle_tables.npz")
        if os.path.exists(path):
            z = np.load(path)
            conf, correct = z["confidence"], z["correct"]
        else:
            conf, correct = synthetic_tables()
        if args.only == "plane":
            rows, pdata = fig_plane(conf, correct)
            claims = plane_claims(pdata)
        elif args.only == "obs":
            # the overhead claim is about the batch figure's regime, so
            # measure at full size; best-of-5 keeps the minimum stable
            # against scheduler noise on shared CI runners
            rows, odata = fig_obs(conf, correct, reps=5)
            claims = obs_claims(odata)
        elif args.only == "adaptive":
            rows, adata = fig_adaptive(conf, correct)
            claims = adaptive_claims(adata)
        else:
            rows, zdata, ze2e = fig_zoo(conf, correct)
            claims = zoo_claims(zdata, ze2e)
        os.makedirs(ART, exist_ok=True)
        out = os.path.join(ART, "scheduling_results.json")
        blob = {"rows": [], "claims": {}}
        if os.path.exists(out):
            with open(out) as f:
                blob = json.load(f)
        blob["rows"] = [r for r in blob.get("rows", [])
                        if r.get("figure") != args.only] + rows
        blob.setdefault("claims", {}).update(claims)
        with open(out, "w") as f:
            json.dump(blob, f, indent=1)
        return rows, claims

    conf, correct, _ = load_tables(smoke=args.smoke)
    if args.smoke:
        DEFAULTS["n_requests"] = 80
        DEFAULTS["n_clients"] = 8
        rows = []
        rows += fig3_5_utility_heuristics(conf, correct, ks=(8,), dus=(0.3,),
                                          dls=(0.01,))
        rows += fig6_7_scheduler_comparison(conf, correct, ks=(8, 24))
        rows += fig8_11_deadline_sweeps(conf, correct, dus=(0.2,),
                                        dls=(0.03,))
        rows += fig12_delta_sweep(conf, correct, deltas=(0.2, 0.05))
        rows += fig13_overhead(conf, correct, ks=(8,))
        brows, speedups = fig_batch_throughput(conf, correct, ks=(24,),
                                               n_requests=200)
        rows += brows
        arows, comp = fig_async_dispatch(conf, correct, ks=(16,),
                                         n_requests=200)
        rows += arows
        trows, tcomp, replay = fig_traffic(conf, correct, n_requests=150)
        rows += trows
        srows, smodeled, se2e = fig_sharded(conf, correct, n_requests=150,
                                            e2e_requests=12)
        rows += srows
        krows, kdeep, kragged, ke2e = fig_kernel(
            conf, correct, comp, n_requests=200, ragged_requests=150,
            e2e_requests=12)
        rows += krows
        prows, pdata = fig_plane(conf, correct)
        rows += prows
        zrows, zdata, ze2e = fig_zoo(conf, correct, n_requests=150,
                                     e2e_requests=12)
        rows += zrows
        orows, odata = fig_obs(conf, correct, k=16, n_requests=150,
                               reps=2, overload_requests=150)
        rows += orows
        adrows, adata = fig_adaptive(conf, correct)
        rows += adrows
        claims = summarize_claims(rows)
        claims.update(batch_claims(speedups))
        claims.update(async_claims(comp))
        claims.update(traffic_claims(tcomp, replay))
        claims.update(sharded_claims(smodeled, se2e))
        claims.update(kernel_claims(kdeep, kragged, ke2e, comp))
        claims.update(plane_claims(pdata))
        claims.update(zoo_claims(zdata, ze2e))
        # smoke runs are ~0.1s — too short for the overhead fraction to
        # be signal; the --only obs leg asserts it at full size
        claims.update(obs_claims(odata, gate_overhead=False))
        claims.update(adaptive_claims(adata))
        print(f"SMOKE OK: {len(rows)} rows")
        return rows, claims

    rows = []
    rows += fig3_5_utility_heuristics(conf, correct)
    rows += fig6_7_scheduler_comparison(conf, correct)
    rows += fig8_11_deadline_sweeps(conf, correct)
    rows += fig12_delta_sweep(conf, correct)
    rows += fig13_overhead(conf, correct)
    brows, speedups = fig_batch_throughput(conf, correct)
    rows += brows
    arows, comp = fig_async_dispatch(conf, correct)
    rows += arows
    trows, tcomp, replay = fig_traffic(conf, correct)
    rows += trows
    srows, smodeled, se2e = fig_sharded(conf, correct)
    rows += srows
    krows, kdeep, kragged, ke2e = fig_kernel(conf, correct, comp)
    rows += krows
    prows, pdata = fig_plane(conf, correct)
    rows += prows
    zrows, zdata, ze2e = fig_zoo(conf, correct)
    rows += zrows
    orows, odata = fig_obs(conf, correct, write_trace=True)
    rows += orows
    adrows, adata = fig_adaptive(conf, correct)
    rows += adrows
    claims = summarize_claims(rows)
    claims.update(batch_claims(speedups))
    claims.update(async_claims(comp))
    claims.update(traffic_claims(tcomp, replay))
    claims.update(sharded_claims(smodeled, se2e))
    claims.update(kernel_claims(kdeep, kragged, ke2e, comp))
    claims.update(plane_claims(pdata))
    claims.update(zoo_claims(zdata, ze2e))
    claims.update(obs_claims(odata))
    claims.update(adaptive_claims(adata))
    os.makedirs(ART, exist_ok=True)
    with open(os.path.join(ART, "scheduling_results.json"), "w") as f:
        json.dump({"rows": rows, "claims": claims}, f, indent=1)
    return rows, claims


if __name__ == "__main__":
    main()
