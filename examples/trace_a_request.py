"""Trace a request through the Fig. 2 serving loop (repro.serving.obs).

Runs the 2x-overload scenario with full tracing on, then answers the
two operator questions the observability layer exists for:

* *What happened to this request?* — its typed spans (``queued`` ->
  ``admitted`` -> ``batched``/``dispatch`` -> ``device-window`` ->
  ``stage-exit`` -> ``retire``/``expire``) with queue-wait / host /
  device time splits.
* *Why was this request degraded?* — the scheduler audit log names the
  admission rule that fired (``overload``, ``mandatory-infeasible``,
  ...) and the numbers behind it (slack, backlog, amortized WCET).

The run also writes the JSONL export and the Chrome ``trace_event``
JSON (open it at https://ui.perfetto.dev), and replays the same
questions through the offline CLI:

    PYTHONPATH=src python tools/planectl.py trace <export> <tid>
    PYTHONPATH=src python tools/planectl.py why   <export> <tid>
    PYTHONPATH=src python tools/planectl.py top   <export> --by queue_wait

Usage: PYTHONPATH=src python examples/trace_a_request.py [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile

import numpy as np

from repro.serving import Service, validate_chrome_trace
from repro.serving.traffic import scenario_spec

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STAGE_TIMES = [0.004, 0.007, 0.010]


def planectl(*argv):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "planectl.py"),
         *argv], capture_output=True, text=True, env=env)
    return proc


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small run + assertions (CI job)")
    args = ap.parse_args(argv)
    n_requests = 150 if args.smoke else 300

    rng = np.random.default_rng(0)
    conf = np.sort(rng.uniform(0.3, 1.0, (600, 3)), axis=1)
    correct = rng.uniform(size=(600, 3)) < conf

    outdir = tempfile.mkdtemp(prefix="obs_demo_")
    export = os.path.join(outdir, "obs.jsonl")
    chrome = os.path.join(outdir, "trace.json")

    # 2x overload forces the admission controller to reject work, so the
    # audit log has decisions to explain; export paths are written when
    # the run finishes
    spec = scenario_spec("2x-overload", stage_times=STAGE_TIMES,
                         n_requests=n_requests,
                         admission={"mode": "reject", "headroom": 3.0},
                         trace={"enabled": True, "export": export,
                                "chrome": chrome})
    svc = Service.from_spec(spec, conf_table=conf, correct_table=correct)
    res = svc.run()
    obs = svc.obs
    print(f"2x-overload: {res.n_requests} requests, "
          f"miss_rate={res.miss_rate:.3f}, "
          f"{len(obs.audit_log)} audit rows, "
          f"{len(obs.windows)} device windows\n")

    served = next(tr for tr in obs.traces.values()
                  if not tr.rejected and not tr.missed)
    rejected = next(tr for tr in obs.traces.values() if tr.rejected)

    # -- what happened to a served request? -------------------------------
    print(f"trace of served request tid={served.tid} "
          f"(depth={served.depth}, latency={served.latency:.4f}s, "
          f"queue_wait={served.queue_wait:.4f}s, "
          f"device_time={served.device_time:.4f}s):")
    for s in served.spans:
        extra = f"  {json.dumps(s.attrs)}" if s.attrs else ""
        print(f"  {s.t0:8.4f} .. {s.t1:8.4f}  {s.name:<14}{extra}")

    # -- why was this one rejected? ---------------------------------------
    print(f"\nwhy was tid={rejected.tid} rejected? "
          f"decision={rejected.decision}")
    for row in obs.audit_for(rejected.tid):
        print(f"  t={row['t']:.4f}  rule={row['rule']}  "
              f"{json.dumps(row['detail'], sort_keys=True)}")

    # -- exports ----------------------------------------------------------
    doc = json.load(open(chrome))
    problems = validate_chrome_trace(doc)
    print(f"\nwrote {export}")
    print(f"wrote {chrome} ({len(doc['traceEvents'])} trace events, "
          f"{'valid' if not problems else problems}) — open in "
          f"https://ui.perfetto.dev")

    # -- same questions, offline, via planectl ----------------------------
    print("\n$ planectl why", export, rejected.tid)
    why = planectl("why", export, str(rejected.tid))
    print(why.stdout, end="")
    print("$ planectl top", export, "-n", "3")
    top = planectl("top", export, "-n", "3")
    print(top.stdout, end="")

    if args.smoke:
        assert len(obs.traces) == res.n_requests
        assert served.span_names()[0] == "queued"
        assert served.span_names()[-1] == "retire"
        audited = {row.get("tid") for row in obs.audit_log}
        assert rejected.tid in audited
        assert not problems
        tr_cli = planectl("trace", export, str(served.tid))
        assert tr_cli.returncode == 0 and "retire" in tr_cli.stdout
        assert why.returncode == 0 and "rule=" in why.stdout
        assert top.returncode == 0 and "total" in top.stdout
        print("\nSMOKE OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
