"""Model zoo: one device, two anytime models, cross-model preemption.

1. Declare a two-model zoo in one ``ServeSpec``: an expensive
   high-weight "llm" head and a cheap "vision" model, each with its own
   WCET table and oracle confidence tables (``repro.serving.zoo``).
2. Drive the registered ``model-mix`` traffic scenario (2x the blended
   capacity) through ``policy="rtdeepiot-zoo"`` twice: ``scope="global"``
   (one FPTAS across both models — sheds the globally least-valuable
   optional stages, whichever model owns them) vs ``scope="siloed"``
   (each model planned as if it owned the device — the union plan
   overcommits and admitted work misses).
3. Read the per-model breakdown from ``ServiceMetrics.per_model`` and
   score both runs on weighted admitted accuracy (a missed deadline
   earns zero, the paper's utility-accrual semantics).
4. Inspect the blended worst-case time model vs the per-model tables,
   and show the spec-time validation a malformed zoo fails with.

Numpy-only (``executor="zoo-oracle"``) — no jax, no trained artifact.

Usage:
  PYTHONPATH=src python examples/model_zoo.py            # full demo
  PYTHONPATH=src python examples/model_zoo.py --smoke    # CI-sized
"""
from __future__ import annotations

import argparse
import dataclasses

import numpy as np

from repro.serving import ModelZoo, Service
from repro.serving.traffic import scenario_spec
from repro.serving.zoo import validate_models

#: the zoo: per-model stage WCETs (seconds) + scheduling contract.  llm
#: is ~2x the stage cost and 2x the utility weight of vision — the
#: trade the cross-model planner arbitrates under overload.
ZOO = {
    "llm": {"stage_times": [0.006, 0.010, 0.014], "weight": 2.0},
    "vision": {"stage_times": [0.003, 0.005, 0.007]},
}

#: capacity anchor for the scenario's 2.0x load factor: the model-mix
#: weighted mean per-stage times (0.4 llm / 0.6 vision — see
#: repro.serving.traffic.scenarios.MODEL_MIX)
MIX_STAGE_TIMES = tuple(
    0.4 * a + 0.6 * b for a, b in zip(ZOO["llm"]["stage_times"],
                                      ZOO["vision"]["stage_times"]))


def zoo_tables(n=240, L=3, seed=0):
    """Per-model oracle tables: monotone per-sample confidence curves
    with confidence-consistent correctness, one independent pair per
    model (same recipe as bench_scheduling's synthetic tables)."""
    out = {}
    for i, model in enumerate(sorted(ZOO)):
        rng = np.random.default_rng(seed + i)
        conf = np.sort(rng.uniform(0.3, 1.0, (n, L)), axis=1)
        out[model] = {"conf": conf,
                      "correct": rng.uniform(size=(n, L)) < conf}
    return out


def weighted_admitted_accuracy(res, tables):
    """Weighted admitted accuracy, utility-accrual semantics: weight =
    SLO utility weight x model weight, a missed deadline earns zero."""
    num = den = 0.0
    for r in res.per_request:
        if r["rejected"]:
            continue
        w = float(r["weight"])
        den += w
        ok = (not r["missed"]) and r["depth"] >= 1 and bool(
            tables[r["model"]]["correct"][r["sample"], r["depth"] - 1])
        num += w * float(ok)
    return num / den if den else 0.0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=600)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny run (CI examples-smoke job)")
    args = ap.parse_args(argv)
    n = 120 if args.smoke else args.requests

    tables = zoo_tables()

    # -- 1. + 2. the same zoo spec, global vs siloed planning -----------
    results = {}
    for scope in ("global", "siloed"):
        spec = dataclasses.replace(
            scenario_spec("model-mix", policy="rtdeepiot-zoo",
                          policy_args={"predictor": "exp", "scope": scope},
                          admission={"mode": "reject"},
                          stage_times=MIX_STAGE_TIMES, n_requests=n,
                          seed=0, models=ZOO),
            executor="zoo-oracle")
        results[scope] = Service.from_spec(
            spec, zoo_tables=tables,
            n_samples=tables["llm"]["conf"].shape[0]).run()

    # -- 3. per-model breakdown + the cross-model shedding payoff -------
    for scope, res in results.items():
        wacc = weighted_admitted_accuracy(res, tables)
        print(f"scope={scope}: admitted_miss={res.admitted_miss_rate:.4f} "
              f"weighted_admitted_acc={wacc:.4f}")
        for model, row in sorted(res.per_model.items()):
            print(f"  {model}: n={row['n']} served={row['served']} "
                  f"rejected={row['rejected']} miss={row['miss_rate']:.4f} "
                  f"mean_depth={row['mean_depth']:.2f}")
    g = weighted_admitted_accuracy(results["global"], tables)
    s = weighted_admitted_accuracy(results["siloed"], tables)
    assert set(results["global"].per_model) == set(ZOO)
    assert g >= s - 1e-9, (g, s)
    print(f"cross-model shedding holds its ground: global {g:.4f} >= "
          f"siloed {s:.4f} (siloed admitted-miss "
          f"{results['siloed'].admitted_miss_rate:.4f} vs global "
          f"{results['global'].admitted_miss_rate:.4f})")

    # -- 4a. blended worst case vs per-model pricing --------------------
    zoo = ModelZoo.from_spec(ZOO)
    tm = zoo.time_model
    print("stage-0 singleton WCET: "
          + "  ".join(f"{m}={tm.for_model(m).wcet(0, 1):.3f}s"
                      for m in zoo.names())
          + f"  blended(worst)={tm.wcet(0, 1):.3f}s")
    assert tm.wcet(0, 1) == max(tm.for_model(m).wcet(0, 1)
                                for m in zoo.names())

    # -- 4b. malformed zoos fail at spec time, not first dispatch -------
    try:
        validate_models({"a": {"stage_times": [0.01], "buckets": [1, 2]},
                         "b": {"stage_times": [0.01], "buckets": [1, 4]}})
    except ValueError as e:
        print(f"spec-time validation: {e}")
    else:
        raise AssertionError("mismatched buckets must be rejected")
    print("OK")


if __name__ == "__main__":
    main()
