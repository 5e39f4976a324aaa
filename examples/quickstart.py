"""Quickstart: the imprecise-computation scheduling stack in 60 seconds.

1. Build a tiny anytime model (3 stages + exit heads + confidences).
2. Cast inference requests as imprecise-computation Tasks.
3. Plan depths with the FPTAS DP (Algorithm 1), then compare schedulers
   through the one serving front door: a declarative ``ServeSpec`` naming
   every component by registry key (policy / executor / clock / source),
   run by ``repro.serving.Service``.  Swapping the ``executor`` key —
   ``oracle`` here, ``device-batched`` / ``device-sharded`` in
   examples/serve_anytime.py — is the only change between simulation and
   real (sharded) serving; see docs/architecture.md and
   docs/serving-api.md.

Usage: PYTHONPATH=src python examples/quickstart.py
"""
from __future__ import annotations

import jax
import numpy as np

from repro.configs import get_config
from repro.core import DepthPlanner, Task, Workload, make_predictor
from repro.models import forward, init_params
from repro.serving import ServeSpec, Service

# --- 1. an anytime model: every stage yields (prediction, confidence) ------
cfg = get_config("anytime-classifier")
params = init_params(cfg, jax.random.PRNGKey(0))
x = {"features": jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))}
out = forward(cfg, params, x, mode="train")
print("per-stage confidences for 4 inputs:")
for s, c in enumerate(out.confidences):
    print(f"  stage {s}: {np.round(np.asarray(c), 3)}")

# --- 2. requests as imprecise computations ---------------------------------
planner = DepthPlanner(delta=0.1)
pred = make_predictor("exp", prior_curve=[0.5, 0.75, 0.875])
tasks = [
    Task(arrival=0.0, deadline=0.08, stage_times=(0.02,) * 3, sample=0),
    Task(arrival=0.0, deadline=0.10, stage_times=(0.02,) * 3, sample=1),
    Task(arrival=0.0, deadline=0.16, stage_times=(0.02,) * 3, sample=2),
]
plan = planner.plan(tasks, now=0.0, predictor=pred)
print("\nFPTAS depth assignment (Algorithm 1):",
      {t.tid: plan[t.tid] for t in tasks})

# --- 3. schedulers head-to-head under overload -----------------------------
# one front door for every engine: name the components in a ServeSpec
# (registry keys), hand the runtime objects to Service as resources
rng = np.random.default_rng(0)
conf = np.clip(rng.uniform(0.35, 0.75, (300, 1))
               + rng.uniform(0.05, 0.25, (300, 3)).cumsum(1), 0, 1)
correct = rng.uniform(size=(300, 3)) < conf
wl = Workload(n_clients=16, d_lo=0.02, d_hi=0.18, n_requests=400)
print("\npolicy       accuracy  miss_rate  mean_depth")
for policy in ("rtdeepiot", "edf", "lcf", "rr"):
    spec = ServeSpec(policy=policy, executor="oracle", clock="virtual",
                     source="closed-loop",
                     batching={"mode": "none", "stage_times": [0.02] * 3})
    r = Service.from_spec(spec, workload=wl, conf_table=conf,
                          correct_table=correct).run()
    print(f"{policy:12s} {r.accuracy:8.3f} {r.miss_rate:9.3f} "
          f"{r.mean_depth:10.2f}")
