"""Traffic generation, overload control, and trace replay in one sitting.

1. Drive the serving stack with an open-loop **flash-crowd** scenario
   (repro.serving.traffic): Poisson base load with a 5x arrival spike the
   scheduler cannot have planned for, streamed windowed metrics showing
   the transient (queue depth, windowed miss rate, utilization).
2. Compare uncontrolled EDF against RTDeepIoT behind admission control —
   the imprecise-computation answer to overload (shed optional stages,
   reject what cannot meet its mandatory deadline).
3. **Record** the run into a JSONL trace and **replay** it through
   ``register_source("replay")``, verifying the replay reproduces the
   original arrival order and admission decisions bit-for-bit under the
   virtual clock — the regression-grade load test the ROADMAP asked for.

Usage:
  PYTHONPATH=src python examples/traffic_replay.py            # full demo
  PYTHONPATH=src python examples/traffic_replay.py --smoke    # CI-sized
  PYTHONPATH=src python examples/traffic_replay.py \
      --trace examples/data/mini_trace.jsonl                  # replay a
      # checked-in trace against its recorded ServeSpec (regression mode)

Traces pair with the synthetic oracle tables built here (seed 0), so a
checked-in trace replays identically on any host.
"""
from __future__ import annotations

import argparse
import dataclasses
import os

import numpy as np

from repro.serving import (ServeSpec, Service, load_trace, record_trace,
                           scenario_spec, verify_replay)

STAGE_TIMES = (0.004, 0.007, 0.010)
ART = os.path.join(os.path.dirname(__file__), "..", "artifacts")


def synthetic_tables(n=120, L=3, seed=0):
    """Oracle-shaped tables: monotone per-sample confidence curves with
    confidence-consistent correctness (same recipe as bench_scheduling)."""
    rng = np.random.default_rng(seed)
    conf = np.sort(rng.uniform(0.3, 1.0, (n, L)), axis=1)
    correct = rng.uniform(size=(n, L)) < conf
    return conf, correct.astype(bool)


def replay_checked_in(path: str) -> None:
    """Regression mode: replay a recorded trace against its stored spec
    and check the recorded outcomes reproduce."""
    header, events = load_trace(path)
    spec = ServeSpec.from_dict(header["spec"])
    spec = dataclasses.replace(spec, source="replay", source_args={})
    conf, correct = synthetic_tables()
    res = Service.from_spec(spec, conf_table=conf, correct_table=correct,
                            trace=events).run()
    recorded = [(ev.outcome["rejected"], ev.outcome["depth"],
                 ev.outcome["missed"]) for ev in events]
    replayed = [(r["rejected"], r["depth"], r["missed"])
                for r in sorted(res.per_request, key=lambda r: r["tid"])]
    assert recorded == replayed, (
        "replay diverged from the recorded outcomes — scheduling behavior "
        "changed since this trace was recorded")
    print(f"replayed {len(events)} recorded requests from {path}: "
          f"outcomes reproduce bit-for-bit "
          f"(miss={res.miss_rate:.3f}, rejected={res.rejected})")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=600)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny scenario, no artifact writes (CI job)")
    ap.add_argument("--trace", default=None,
                    help="replay this JSONL trace (regression mode)")
    args = ap.parse_args(argv)
    if args.trace:
        replay_checked_in(args.trace)
        if not args.smoke:
            return
    n_requests = 80 if args.smoke else args.requests
    conf, correct = synthetic_tables()

    # -- 1. flash crowd with streamed windowed metrics ------------------
    print("flash-crowd scenario, RTDeepIoT + admission control "
          "(windowed metrics):")
    print(f"{'t':>6} {'n':>4} {'miss%':>6} {'queue':>6} {'util%':>6} "
          f"{'shed':>5} {'rej':>4}")

    def show(s):
        print(f"{s.t:6.2f} {s.n:4d} {100 * s.miss_rate:6.1f} "
              f"{s.queue_depth:6d} {100 * s.utilization:6.1f} "
              f"{s.capped:5d} {s.rejected:4d}")

    spec = scenario_spec("flash-crowd", policy="rtdeepiot",
                         admission={"mode": "depth_cap"},
                         stage_times=STAGE_TIMES, n_requests=n_requests,
                         metrics_interval=0.5)
    svc = Service.from_spec(spec, conf_table=conf, correct_table=correct,
                            on_metrics=show)
    controlled = svc.run()

    # -- 2. the same crowd, uncontrolled EDF ----------------------------
    edf = Service.from_spec(
        scenario_spec("flash-crowd", policy="edf", stage_times=STAGE_TIMES,
                      n_requests=n_requests),
        conf_table=conf, correct_table=correct).run()
    print(f"\nuncontrolled edf:        miss={edf.miss_rate:.3f} "
          f"acc={edf.accuracy:.3f}")
    print(f"rtdeepiot + shedding:    miss={controlled.miss_rate:.3f} "
          f"acc={controlled.accuracy:.3f} capped={controlled.capped}")

    # -- 3. record -> replay, bit-for-bit -------------------------------
    if args.smoke:
        import tempfile
        trace_path = os.path.join(tempfile.mkdtemp(), "flash_crowd.jsonl")
    else:
        os.makedirs(os.path.join(ART, "traces"), exist_ok=True)
        trace_path = os.path.join(ART, "traces", "flash_crowd.jsonl")
    record_trace(controlled, trace_path, source="traffic", spec=spec)
    _, events = load_trace(trace_path)
    replayed = Service.from_spec(
        dataclasses.replace(spec, source="replay", source_args={},
                            metrics_interval=0.0),
        conf_table=conf, correct_table=correct, trace=events).run()
    v = verify_replay(controlled.per_request, replayed.per_request)
    print(f"\nrecorded {len(events)} requests -> {trace_path}")
    print(f"replay: arrival_order={v['arrival_order']} "
          f"admission_decisions={v['admission_decisions']} "
          f"bitwise={v['bitwise']}")
    assert v["bitwise"], "replay must reproduce the run bit-for-bit"
    print("OK")


if __name__ == "__main__":
    main()
