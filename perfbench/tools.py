#!/usr/bin/env python3
"""Measurements that fix a cell's numbers; the benchmark's runs never call them.

    python3 perfbench/tools.py sweep --workload W --seconds S --rates R [R ...]
        [--deadline-x LO HI | --deadline-ms LO HI]
    python3 perfbench/tools.py calibrate --workload W --seconds S
        --seeds N [N ...] --control-seeds N [N ...]
    python3 perfbench/tools.py faults --workload W --seconds S
        --seeds N [N ...] [--faults NAME ...]

``sweep`` sets a prefill cell up once and serves a window at each Poisson
rate, printing attainment, depth and tails per rate: the knee is the
highest rate at which 99% of requests are answered by their deadline with
no growing backlog.  ``--deadline-x`` sets the deadlines from the WCET
profile (LO times one mandatory stage at the largest batch and length
bucket, HI times the full ladder there) and prints them in ms.

``calibrate`` serves one window per seed and reads the correctness numbers
of the program and, on the control seeds, of the control: the plain
reference in the precision below the configuration's (float8 operands for
bfloat16, bfloat16 for float32) put in the program's place, each judged
against the traffic file's limits as a run judges.  The limits in the
traffic files are set from these readings.

``faults`` makes whole runs of the cell, as ``run.py`` makes them, with one
fault of ``bench.faults`` planted in the timed path, and prints whether
``correct`` came out false.

Each prints one JSON object per line.  Run from the repository root, on a
TPU, as the only process on the chip.
"""
from __future__ import annotations

import argparse
import copy
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

CONTROL = {"bfloat16": "fp8", "float32": "bf16"}


def _setup(workload: str):
    import jax
    import run as runner
    from bench import cell, program, weights
    bench = cell.benchmark()
    w = cell.find_cell(bench, workload)
    devs = runner.check_devices(jax, int(w["chips"]))
    runner.enable_compile_cache(jax)
    m, tr = cell.config(w["config"]), cell.traffic(w["traffic"])
    cfg = program.program_config(m)
    from repro.models import init_params
    weights.check_layout(m, init_params, cfg)
    return devs, m, tr, cfg


def sweep(args) -> None:
    import numpy as np
    from bench import prefill, weights
    devs, m, tr, cfg = _setup(args.workload)
    c = prefill.PrefillCell(m, tr, cfg)
    c.setup(weights.make_weights(m, args.seed, devs[0]))
    wc = c.wcet                                   # (len, stage, bucket)
    print(json.dumps({"wcet_ms": (wc * 1e3).round(3).tolist(),
                      "host_overhead_ms": c.host_overhead * 1e3}), flush=True)
    tr = copy.deepcopy(tr)
    if args.deadline_x:
        stage = float(wc[-1, 0, -1])
        ladder = float(wc[-1, :, -1].sum())
        tr["deadline"] = {"lo_ms": round(args.deadline_x[0] * stage * 1e3, 1),
                          "hi_ms": round(args.deadline_x[1] * ladder * 1e3, 1)}
    elif args.deadline_ms:
        tr["deadline"] = {"lo_ms": args.deadline_ms[0],
                          "hi_ms": args.deadline_ms[1]}
    c.tr = tr
    for rate in args.rates:
        t0 = time.perf_counter()
        tr["arrivals"] = {"kind": "poisson", "rate": rate}
        reqs = c.requests(args.seconds, args.seed)
        t1 = time.perf_counter()
        out = c.serve(reqs, trace=False)
        t2 = time.perf_counter()
        e = prefill.end_to_end(out, reqs, args.seconds)
        recs = sorted(out["records"], key=lambda r: r["offset"])
        q = max(1, len(recs) // 4)
        first = [r["latency"] for r in recs[:q]]
        last = [r["latency"] for r in recs[-q:]]
        print(json.dumps({
            "rate": rate, "deadline": tr["deadline"], "n": len(reqs),
            "answered_share": 1 - e["failed"] / max(1, len(reqs)),
            "rejected": sum(r["rejected"] for r in recs),
            **e["values"],
            "latency_p50_ms": 1e3 * float(np.median([r["latency"]
                                                      for r in e["served"]])),
            "first_quarter_latency_ms": 1e3 * float(np.median(first)),
            "last_quarter_latency_ms": 1e3 * float(np.median(last)),
            "n_dispatches": out["n_dispatches"],
            "drain_s": out["window_end"] - out["window_start"] - args.seconds,
            "make_s": t1 - t0, "serve_s": t2 - t1,
            "before_window_s": out["window_start"] - t1,
        }), flush=True)


def calibrate(args) -> None:
    from bench import cell, decode, prefill, weights
    import run as runner
    devs, m, tr, cfg = _setup(args.workload)
    limits = tr["correct"]["limits"]
    ref_mod = cell.reference_module(m)
    rounding = CONTROL[m["torch_dtype"]]
    drv = tr["driver"]
    c = (prefill.PrefillCell(m, tr, cfg) if drv == "service_prefill"
         else decode.DecodeCell(m, tr, cfg))
    params = weights.make_weights(m, args.seeds[0], devs[0])
    c.setup(params)
    for i, seed in enumerate(args.seeds):
        if i:
            c.params = None
            del params
            gc.collect()
            params = weights.make_weights(m, seed, devs[0])
            c.params = params
        row = {"seed": seed}
        if drv == "service_prefill":
            reqs = c.requests(args.seconds, seed)
            out = c.serve(reqs, trace=False)
            e = prefill.end_to_end(out, reqs, args.seconds)
            row.update(e["values"])
            n = int(tr["correct"]["sample"])
            row["program"] = prefill.check(ref_mod, m, params, e["served"],
                                           reqs, n, seed)
            if seed in args.control_seeds:
                row["control"] = prefill.check(ref_mod, m, params,
                                               e["served"], reqs, n, seed,
                                               rounding=rounding)
        else:
            out = c.serve(args.seconds, seed, trace=False)
            row.update(decode.end_to_end(out)["values"])
            row["n_tokens"] = out["n_tokens"]
            row["depths"] = sorted(set(int(d) for d in out["depths"]))
            row["program"] = decode.check(ref_mod, m, params, out, c.slots)
            if seed in args.control_seeds:
                row["control"] = decode.check(ref_mod, m, params, out,
                                              c.slots, rounding=rounding)
        row["control_rounding"] = rounding
        row["program_correct"] = runner.judge(row["program"], limits)[0]
        if "control" in row:
            row["control_correct"] = runner.judge(row["control"], limits)[0]
        print(json.dumps(row), flush=True)


def faults(args) -> None:
    import run as runner
    from bench import cell
    from bench import faults as faults_mod
    bench = cell.benchmark()
    w = cell.find_cell(bench, args.workload)
    devs, m, tr, _cfg = _setup(args.workload)
    planted = faults_mod.BY_DRIVER[tr["driver"]]
    for name in args.faults or sorted(planted):
        for seed in args.seeds:
            with faults_mod.Patcher() as patch:
                planted[name](patch, m)
                r = runner.run_cell(w, m, copy.deepcopy(tr), seed=seed,
                                    seconds=args.seconds, trace=False,
                                    bench=bench, devs=devs)
            print(json.dumps({"fault": name, "seed": seed,
                              "correct": r["correct"],
                              "attempted": r["attempted"],
                              "failed": r["failed"],
                              "compared": r["compared"]}), flush=True)
            gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    s = sub.add_parser("sweep")
    s.add_argument("--workload", required=True)
    s.add_argument("--seconds", type=float, required=True)
    s.add_argument("--seed", type=int, default=1)
    s.add_argument("--rates", type=float, nargs="+", required=True)
    s.add_argument("--deadline-x", type=float, nargs=2)
    s.add_argument("--deadline-ms", type=float, nargs=2)
    c = sub.add_parser("calibrate")
    c.add_argument("--workload", required=True)
    c.add_argument("--seconds", type=float, required=True)
    c.add_argument("--seeds", type=int, nargs="+", required=True)
    c.add_argument("--control-seeds", type=int, nargs="*", default=[])
    f = sub.add_parser("faults")
    f.add_argument("--workload", required=True)
    f.add_argument("--seconds", type=float, required=True)
    f.add_argument("--seeds", type=int, nargs="+", required=True)
    f.add_argument("--faults", nargs="*", default=None)
    args = ap.parse_args(argv)
    {"sweep": sweep, "calibrate": calibrate, "faults": faults}[args.cmd](args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
