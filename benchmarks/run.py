"""Benchmark orchestrator: one entry per paper table/figure + the system
benches.  ``PYTHONPATH=src python -m benchmarks.run [--quick]``

Every scheduling engine is declared as a ``ServeSpec`` and run through
``repro.serving.Service`` (see docs/serving-api.md) — the scheduling
block covers the paper figures plus the ``batch`` / ``async`` /
``traffic`` / ``sharded`` serving-extension figures and records their
claims in ``artifacts/scheduling_results.json``.

Prints ``name,us_per_call,derived`` style CSV blocks per bench.  A phase
that fails (a missing artifact included) ends the run with its exception
and a non-zero exit code.
"""
from __future__ import annotations

import argparse
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="ServeSpec-driven scheduling benches with fewer "
                         "requests per figure")
    ap.add_argument("--only", default=None,
                    choices=[None, "scheduling", "kernels", "roofline",
                             "ablations"])
    args = ap.parse_args(argv)

    t0 = time.time()
    if args.only in (None, "scheduling"):
        print("== scheduling benchmarks (paper Figs. 3-13) ==")
        from benchmarks import bench_scheduling
        if args.quick:
            bench_scheduling.DEFAULTS["n_requests"] = 200
        bench_scheduling.main()
    if args.only in (None, "kernels"):
        print("== kernel microbenchmarks ==")
        from benchmarks import bench_kernels
        bench_kernels.main()
    if args.only in (None, "ablations"):
        print("== scheduler ablations (beyond paper) ==")
        from benchmarks import bench_ablations
        bench_ablations.main()
    if args.only in (None, "roofline"):
        print("== roofline table (from dry-run artifacts) ==")
        from benchmarks import bench_roofline
        bench_roofline.main()
    print(f"total {time.time()-t0:.0f}s")


if __name__ == "__main__":
    main()
