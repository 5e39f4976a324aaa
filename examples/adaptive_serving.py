"""Adaptive control end to end: learn the workload, then serve ahead of it.

1. **Record & identify** — run "yesterday's" flash-crowd scenario behind
   reactive admission control, then fit every arrival model
   (``repro.serving.adaptive.fit_report``) to the recorded offsets and
   let the BIC-penalized score name the workload.
2. **Predict** — re-serve "today" (same process, fresh seed) twice:
   reactive depth-cap admission vs the same controller armed with
   yesterday's fitted process (``admission={"forecast": ...}``).  The
   forecast sheds optional stages *before* the spike lands — strictly
   fewer admitted deadline misses at equal-or-better admitted accuracy.
3. **Learn the curves** — ``rtdeepiot-adaptive`` plans against an
   ``OnlineCurveEstimator`` fed by observed stage exits; after one
   warm-up run it lands within 2% of the oracle-table policy.
4. **Drive it live** — a wall-clock ``TrafficDriver`` paces requests
   sampled from the *fitted* process into ``Service.submit()``.

Usage:
  PYTHONPATH=src python examples/adaptive_serving.py           # full demo
  PYTHONPATH=src python examples/adaptive_serving.py --smoke   # CI-sized
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.serving import ServeSpec, Service, scenario_spec
from repro.serving.adaptive import (OnlineCurveEstimator, TrafficDriver,
                                    fit_report)

STAGE_TIMES = (0.004, 0.007, 0.010)
N_REQUESTS = 600        # the fit needs the whole spike: a truncated
                        # flash-crowd trace reads as MMPP instead


def synthetic_tables(n=600, L=3, seed=0):
    """Oracle-shaped tables: monotone per-sample confidence curves with
    confidence-consistent correctness (same recipe as bench_scheduling)."""
    rng = np.random.default_rng(seed)
    conf = np.sort(rng.uniform(0.3, 1.0, (n, L)), axis=1)
    correct = rng.uniform(size=(n, L)) < conf
    return conf, correct.astype(bool)


def flash_crowd(conf, correct, *, admission, seed, trace=None):
    spec = scenario_spec("flash-crowd", policy="rtdeepiot",
                         admission=admission, stage_times=STAGE_TIMES,
                         n_requests=N_REQUESTS, seed=seed,
                         trace=trace or {})
    svc = Service.from_spec(spec, conf_table=conf, correct_table=correct)
    return svc, svc.run()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="shrink the live-driver leg (CI job); the "
                         "virtual-clock legs already run full size")
    args = ap.parse_args(argv)
    conf, correct = synthetic_tables()

    # -- 1. record yesterday, fit the arrival process -------------------
    _, rec = flash_crowd(conf, correct, admission={"mode": "depth_cap"},
                         seed=11)
    report = fit_report([r["offset"] for r in rec.per_request])
    print(f"yesterday: {rec.n_requests} arrivals recorded, fits scored:")
    for kind in sorted(report["scores"], key=report["scores"].get,
                       reverse=True):
        tag = " <- best" if kind == report["best"] else ""
        print(f"  {kind:12s} {report['scores'][kind]:10.1f}{tag}")
    assert report["best"] == "flash-crowd"
    process = report["fits"][report["best"]]
    print(f"  fitted: base={process['base_rate']:.0f}/s "
          f"spike={process['spike_rate']:.0f}/s "
          f"at t={process['spike_at']:.2f}s "
          f"for {process['spike_len']:.2f}s")

    # -- 2. today: reactive vs forecast-armed admission -----------------
    arms = {}
    for label, adm in (
            ("reactive", {"mode": "depth_cap"}),
            ("predictive", {"mode": "depth_cap",
                            "forecast": {"process": process,
                                         "horizon": 0.1}})):
        svc, res = flash_crowd(conf, correct, admission=adm, seed=12,
                               trace={"enabled": True})
        n_admitted = res.n_requests - res.rejected
        misses = round(res.admitted_miss_rate * n_admitted)
        arms[label] = (misses, res.admitted_accuracy)
        why = sum(1 for r in svc.obs.audit_log
                  if r["rule"] == "forecast-capped")
        print(f"today/{label:10s} admitted_misses={misses:3d} "
              f"admitted_acc={res.admitted_accuracy:.3f} "
              f"capped={res.capped}"
              + (f" (forecast fired {why}x)" if why else ""))
    assert arms["predictive"][0] < arms["reactive"][0]
    assert arms["predictive"][1] >= arms["reactive"][1] - 1e-9

    # -- 3. learned curves vs the oracle table --------------------------
    def steady(policy, seed, **res):
        pargs = {"predictor": "oracle"} if policy == "rtdeepiot" else {}
        spec = scenario_spec("steady", policy=policy, policy_args=pargs,
                             stage_times=STAGE_TIMES,
                             n_requests=N_REQUESTS, seed=seed)
        return Service.from_spec(spec, conf_table=conf,
                                 correct_table=correct, **res).run()

    oracle = steady("rtdeepiot", 22)
    est = OnlineCurveEstimator(num_stages=conf.shape[1],
                               prior=[0.5, 0.7, 0.85])
    steady("rtdeepiot-adaptive", 21, curve_estimator=est)        # warm-up
    warm = steady("rtdeepiot-adaptive", 22, curve_estimator=est)
    curve = ", ".join(f"{c:.3f}" for c in est.curve())
    print(f"curves: oracle_acc={oracle.accuracy:.3f} "
          f"adaptive_acc={warm.accuracy:.3f} "
          f"({est.n_observed} exits observed, learned curve [{curve}])")
    assert warm.accuracy >= oracle.accuracy - 0.02

    # -- 4. live wall-clock driver off the fitted process ---------------
    n_live = 24 if args.smoke else 120
    live = ServeSpec(policy="edf", executor="oracle", clock="wall",
                     source="live",
                     batching={"mode": "none",
                               "stage_times": [0.001, 0.001, 0.001]},
                     slo_classes={"gold": {"rel_deadline": 2.0}},
                     default_slo="gold")
    with Service.from_spec(live, conf_table=conf,
                           correct_table=correct) as svc:
        drv = TrafficDriver(svc, arrival=dict(process),
                            n_samples=conf.shape[0], n_requests=n_live,
                            seed=7, speed=8.0).start()
        assert drv.join(timeout=60.0)
        met = svc.drain()
    print(f"live: drove {drv.submitted} requests sampled from the fitted "
          f"process at 8x (acc={met.accuracy:.3f}, "
          f"miss={met.miss_rate:.3f})")
    assert met.n_requests == n_live
    print("OK")


if __name__ == "__main__":
    main()
