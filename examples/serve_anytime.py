"""End-to-end anytime serving driver (paper Fig. 2) — REAL model, wall clock.

Loads the trained anytime classifier, profiles per-stage WCETs (99th
percentile, paper §IV protocol) plus the host dispatch overhead, then
serves requests from K concurrent clients under uniform-random relative
deadlines with the RTDeepIoT scheduler vs. EDF, reporting accuracy / miss
rate / latency from actual jitted stage executions on this host.

Every engine is built through the public serving API: a declarative
``ServeSpec`` names the policy / executor / clock / source by registry key
(``device-batched`` under ``batching={"mode": "none"}`` = unbatched
singleton dispatch, ``device-batched`` with buckets = continuous
micro-batching, ``pipeline_depth=2`` = pipelined async
dispatch, ``device-sharded`` = the batched engine across a ``(dp, 1)``
mesh — ``--dp`` must not exceed the host's devices, ``device-kernel`` with
``--kernels`` = Pallas stage bodies with the fused exit-confidence
epilogue at ``pipeline_depth=3``, compiled on a TPU and interpreted on the
CPU), and
``repro.serving.Service`` owns the engine lifecycle; the model params /
stage fns / profiled time model ride along as resources.

Also writes artifacts/stage_times.npz so the simulation benchmarks use the
profiled WCETs.

Usage: PYTHONPATH=src python examples/serve_anytime.py [--requests 120]
       PYTHONPATH=src python examples/serve_anytime.py --smoke   # CI job
"""
from __future__ import annotations

import argparse
import os

import jax
import numpy as np

import repro.launch.serve  # noqa: F401 — registers device-sharded

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.serving import (BatchedStageFns, ServeSpec, Service,
                           closed_loop_stream, profile_batched_stages,
                           profile_host_overhead)
from repro.training import DifficultyDataset, checkpoint

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=120)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--d-lo", type=float, default=None,
                    help="min relative deadline (default: 1.2x one stage)")
    ap.add_argument("--d-hi", type=float, default=None,
                    help="max relative deadline (default: 6x one stage)")
    ap.add_argument("--buckets", type=int, nargs="+", default=[1, 2, 4, 8],
                    help="pre-compiled batch-size buckets for the batched "
                         "engine")
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel ways for the device-sharded engine "
                         "(the host must have that many devices)")
    ap.add_argument("--kernels", action="store_true",
                    help="also run the kernel-backed fast path (executor "
                         "'device-kernel': Pallas stage bodies, fused "
                         "exit-confidence, pipeline_depth=3)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny workload, few profiling runs, no artifact "
                         "writes (CI job)")
    args = ap.parse_args(argv)
    if args.smoke:
        args.requests, args.clients, args.buckets = 8, 2, [1, 2]
    n_runs = 5 if args.smoke else 60
    enable_compile_cache()

    cfg = get_config("anytime-classifier")
    ckpt_path = os.path.join(ART, "anytime_classifier.ckpt")
    if os.path.exists(ckpt_path):
        params, meta = checkpoint.load(ckpt_path,
                                       init_params(cfg, jax.random.PRNGKey(0)))
        print(f"loaded checkpoint ({meta.get('steps')} steps)")
    else:
        print("no checkpoint found — using random params "
              "(run examples/train_multiexit.py first for meaningful accuracy)")
        params = init_params(cfg, jax.random.PRNGKey(0))

    ds = DifficultyDataset(num_classes=cfg.vocab_size, seed=0)
    test = ds.sample(80 if args.smoke else 600, seed=999)

    # --- profile stages (paper §IV: WCET = upper CI over profiling runs) ---
    stage_fns = BatchedStageFns(cfg, (1,))
    sample = jax.tree.map(lambda x: x[:1], test["inputs"])
    _, smat = profile_batched_stages(cfg, params, stage_fns, sample,
                                     n_runs=n_runs)
    wcet = smat[:, 0]
    host_overhead = profile_host_overhead(n_runs=n_runs)
    print("stage WCETs (s):", np.round(wcet, 5),
          f" host_overhead={host_overhead*1e6:.1f}us")
    if not args.smoke:
        np.savez(os.path.join(ART, "stage_times.npz"), wcet=wcet,
                 host_overhead=host_overhead)

    # --- profile *batched* stage WCETs for the micro-batching engine ------
    buckets = tuple(sorted(args.buckets))
    bfns = BatchedStageFns(cfg, buckets)
    time_model, bmat = profile_batched_stages(cfg, params, bfns, sample,
                                              n_runs=max(5, n_runs // 2))
    print("batched stage WCETs (s) [stage x bucket]:\n", np.round(bmat, 5))

    d_lo = args.d_lo or float(4.0 * wcet.max())
    d_hi = args.d_hi or float(14.0 * wcet.max())
    print(f"deadlines ~ U[{d_lo:.4f}, {d_hi:.4f}] s, {args.clients} clients")

    def report(name, svc):
        responses = svc.responses
        labels = np.asarray(test["labels"])
        correct = [r.prediction == labels[r.sample]
                   for r in responses if not r.missed]
        acc = float(np.sum(correct)) / max(1, len(responses))
        miss = float(np.mean([r.missed for r in responses]))
        depth = float(np.mean([r.depth for r in responses if not r.missed]
                              or [0]))
        lat = float(np.mean([r.latency for r in responses]))
        print(f"{name:18s} n={len(responses)} acc={acc:.3f} miss={miss:.3f} "
              f"mean_depth={depth:.2f} mean_latency={lat*1e3:.1f}ms "
              f"sched_overhead={svc.policy.sched_time:.3f}s")
        return dict(acc=acc, miss=miss, depth=depth)

    def stream():
        return closed_loop_stream(test["inputs"], test["labels"],
                                  n_clients=args.clients, d_lo=d_lo,
                                  d_hi=d_hi, n_requests=args.requests,
                                  seed=1)

    POLICIES = [("rtdeepiot", {"predictor": "exp",
                               "prior_curve": [.5, .7, .85]}),
                ("edf", {})]

    def spec_for(policy, policy_args, *, batched, pipelined=False,
                 sharded=False, kernel=False):
        if batched:
            batching = {}            # priced by the profiled time_model
        else:
            batching = {"mode": "none",
                        "stage_times": [float(x) for x in wcet]}
        executor = "device-kernel" if kernel else \
            ("device-sharded" if sharded else "device-batched")
        return ServeSpec(
            policy=policy, policy_args=policy_args,
            executor=executor,
            executor_args={"dp": args.dp, "tp": 1} if sharded else {},
            clock="wall", source="stream", batching=batching,
            host_overhead=host_overhead,
            pipeline_depth=3 if kernel else (2 if pipelined else 1))

    results = {}
    for name, pargs in POLICIES:
        svc = Service.from_spec(spec_for(name, pargs, batched=False),
                                cfg=cfg, params=params, stage_fns=stage_fns)
        svc.run(stream())
        results[name] = report(name, svc)
    for name, pargs in POLICIES:
        svc = Service.from_spec(spec_for(name, pargs, batched=True),
                                cfg=cfg, params=params, stage_fns=bfns,
                                time_model=time_model)
        svc.run(stream())
        results[f"batched-{name}"] = report(f"batched-{name}", svc)
    # pipelined async dispatch (pipeline_depth=2): the host pre-selects the
    # next batch while the device executes the current one
    for name, pargs in POLICIES:
        svc = Service.from_spec(spec_for(name, pargs, batched=True,
                                         pipelined=True),
                                cfg=cfg, params=params, stage_fns=bfns,
                                time_model=time_model)
        svc.run(stream())
        results[f"pipelined-{name}"] = report(f"pipelined-{name}", svc)
    # sharded across a (dp, tp) mesh (executor "device-sharded", registered
    # by repro.launch.serve from outside the serving package); at the
    # default --dp 1 this leg exercises the full sharded path — mesh
    # build, sharding constraints, dp-divisible buckets, device-resident
    # state cache — on any single-device host
    name, pargs = POLICIES[0]
    svc = Service.from_spec(spec_for(name, pargs, batched=True, sharded=True),
                            cfg=cfg, params=params, time_model=time_model)
    svc.run(stream())
    ex = svc.executor
    results[f"sharded-{name}"] = report(
        f"sharded{ex.dp}x{ex.tp}-{name}", svc)
    assert ex.cache_stats()["live"] == 0      # state evicted on retire
    # kernel-backed fast path (executor "device-kernel", also registered
    # by repro.launch.serve): jitted Pallas stage bodies with the fused
    # exit-confidence epilogue, dispatching pipeline_depth-1 = 2 stacked
    # device windows
    if args.kernels:
        name, pargs = POLICIES[0]
        svc = Service.from_spec(spec_for(name, pargs, batched=True,
                                         kernel=True),
                                cfg=cfg, params=params,
                                time_model=time_model)
        svc.run(stream())
        results[f"kernel-{name}"] = report(f"kernel-{name}", svc)
        kx = svc.executor
        kt = kx.device_time_stats()
        print(f"kernel telemetry: host={kt['host_time']:.3f}s "
              f"device={kt['device_time']:.3f}s "
              f"windows={kx.max_inflight} "
              f"cache={kx.cache_stats()}")
        assert kx.max_inflight == 2
        assert kx.cache_stats()["live"] == 0
    if args.smoke:
        assert all(len(r) == 3 for r in results.values())
        print(f"SMOKE OK: {len(results)} engine configs served "
              f"{args.requests} requests each")
    return results


if __name__ == "__main__":
    main()
