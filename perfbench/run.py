#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the repository root, as the only process on the chip.  The cell
(``BENCHMARK.json``'s ``workloads``) names a configuration file and a
traffic file under ``perfbench/``; the traffic file names the path driver.
The run makes its weights and requests from ``--seed``, compiles and warms
every shape the traffic uses (set-up), serves the traffic for ``--seconds``
(the window), then compares a sample of what the window served with the
plain float32 reference.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (end-to-end
with ``--trace 0``, per-layer with ``--trace 1``), ``device``, with
``--trace 1`` a ``breakdown``, and last ``compared``: each number of the
correctness comparison beside its limit.  Those also end standard error.

It exits non-zero and prints no result unless JAX's devices are TPUs, as
many as the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if HERE not in sys.path:
    sys.path.insert(0, HERE)

OUT_DIR = os.path.join(ROOT, ".perfbench_out")


class NoChip(RuntimeError):
    pass


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def enable_compile_cache(jax) -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where set,
    else a fixed directory inside the checkout; every program is kept."""
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return d


def check_devices(jax, chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform!r} "
                     f"({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX sees {len(devs)}")
    return devs[:chips]


def peak_bytes(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def judge(numbers: dict, limits: dict) -> tuple:
    """``(correct, compared)``: every limited number at or under its limit."""
    compared = {}
    ok = True
    for name, limit in limits.items():
        v = numbers.get(name)
        compared[name] = {"value": v, "limit": limit}
        if v is None or not v <= limit:
            ok = False
    return ok, compared


def run_cell(w: dict, m: dict, tr: dict, *, seed: int, seconds: float,
             trace: bool, bench: dict, devs, program_overrides=None,
             interpret=None) -> dict:
    """Set up, serve one window, read the metrics, check the answers."""
    import jax
    import numpy as np
    from bench import cell, decode, peaks, prefill, program, weights
    from bench import trace as trace_mod

    cfg = program.program_config(m, program_overrides)
    from repro.models import init_params
    weights.check_layout(m, init_params, cfg)
    params = weights.make_weights(m, seed, devs[0])
    ref_mod = cell.reference_module(m)
    driver = tr["driver"]
    trace_dir = os.path.join(OUT_DIR, "trace")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
    counter = None
    if driver == "service_prefill":
        c = prefill.PrefillCell(m, tr, cfg, interpret=interpret)
        c.setup(params)
        reqs = c.requests(seconds, seed)
        if trace:
            jax.profiler.start_trace(trace_dir)
        counter = _counter()
        counter.active = True
        try:
            out = c.serve(reqs, trace=trace)
        finally:
            counter.active = False
            if trace:
                jax.profiler.stop_trace()
        e2e = prefill.end_to_end(out, reqs, seconds)
    elif driver == "token_decode":
        c = decode.DecodeCell(m, tr, cfg)
        c.setup(params)
        if trace:
            jax.profiler.start_trace(trace_dir)
        counter = _counter()
        counter.active = True
        try:
            out = c.serve(seconds, seed, trace=trace)
        finally:
            counter.active = False
            if trace:
                jax.profiler.stop_trace()
        e2e = decode.end_to_end(out)
    else:
        raise ValueError(f"unknown driver {driver!r}")
    setup_s = out["window_start"] - T_START
    mem = peak_bytes(devs)
    # the program's state goes before the reference runs
    c.fns = None
    c.steps = None
    gc.collect()

    if driver == "service_prefill":
        numbers = prefill.check(ref_mod, m, params, e2e["served"], reqs,
                                int(tr["correct"]["sample"]), seed) \
            if e2e["served"] else {}
    else:
        numbers = decode.check(ref_mod, m, params, out, c.slots) \
            if out["n_tokens"] else {}
    correct, compared = judge(numbers, tr["correct"]["limits"])

    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    result = {"correct": bool(correct), "attempted": int(e2e["attempted"]),
              "failed": int(e2e["failed"]), "metrics": {}, "device": device}
    if not trace:
        vals = dict(e2e["values"], setup_s=setup_s)
        for e in cell.metrics_for(bench, w["name"], "end_to_end"):
            v = vals.get(e["name"])
            if v is not None:
                result["metrics"][e["name"]] = {"value": float(v),
                                                "unit": e["unit"]}
    else:
        summary = trace_mod.summarize(trace_mod.load(trace_dir))
        ctx = _Ctx(m=m, tr=tr, out=out, e2e=e2e, summary=summary,
                   peak=peaks.peaks(dev.device_kind), np=np)
        for pl in cell.metrics_for(bench, w["name"], "per_layer"):
            v = cell.metric_reader(pl["name"])(ctx)
            if v is not None:
                result["metrics"][pl["name"]] = {"value": float(v),
                                                 "unit": pl["unit"]}
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        result["breakdown"] = summary["breakdown"]
    if out.get("split_s"):
        result["split_s"] = out["split_s"]
    result["compiles_in_window"] = counter.count
    result["compared"] = compared
    return result


class _Ctx:
    """What a per-layer metric reader may read (see ``metrics/``)."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


_COUNTER = None


def _counter():
    global _COUNTER
    if _COUNTER is None:
        from bench.instrument import CompileCounter
        _COUNTER = CompileCounter()
    _COUNTER.count = 0
    return _COUNTER


def report(result: dict) -> None:
    for name, c in result["compared"].items():
        print(f"compared {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct {result['correct']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    sys.stdout.flush()


def main(argv=None) -> int:
    args = parse(argv)
    from bench import cell
    bench = cell.benchmark()
    w = cell.find_cell(bench, args.workload)
    import jax
    try:
        devs = check_devices(jax, int(w["chips"]))
    except NoChip as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 3
    enable_compile_cache(jax)
    from bench import program
    program.import_program()
    m = cell.config(w["config"])
    result = run_cell(w, m, cell.traffic(w["traffic"]),
                      seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), bench=bench, devs=devs)
    report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
