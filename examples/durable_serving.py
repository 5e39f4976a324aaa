"""Durable request plane: submit -> crash -> recover -> drain.

1. Stand up a multi-tenant service behind the durable plane
   (``repro.serving.plane``): a write-ahead :class:`Journal` plus a
   :class:`FrontDoor` (token-bucket quotas + deficit-round-robin fair
   queueing), so every accepted request is fsynced to disk *before* its
   handle exists and duplicate submits of one ``request_id`` are no-ops.
2. **Crash** before anything was served: drop the service on the floor
   without draining.  The journal is the only survivor.
3. **Recover**: :func:`repro.serving.plane.recover` rebuilds the exact
   engine from the journal header's ServeSpec and redoes every journaled
   SUBMIT under the virtual clock — delivering each request exactly once
   (pre-crash terminals are never re-delivered) and reproducing the
   admission decisions an uncrashed run would have made bit-for-bit.
4. Read the plane's health from the journal alone (``journal_stats`` —
   the same numbers ``tools/planectl.py`` prints).

Usage:
  PYTHONPATH=src python examples/durable_serving.py            # full demo
  PYTHONPATH=src python examples/durable_serving.py --smoke    # CI-sized
"""
from __future__ import annotations

import argparse
import shutil
import tempfile

import numpy as np

from repro.serving import (FrontDoor, Journal, ServeSpec, Service,
                           journal_stats, recover, verify_recovery)
from repro.serving.engine import Request

STAGE_TIMES = (0.004, 0.007, 0.010)


def synthetic_tables(n=120, L=3, seed=0):
    """Oracle-shaped tables: monotone per-sample confidence curves with
    confidence-consistent correctness (same recipe as bench_scheduling)."""
    rng = np.random.default_rng(seed)
    conf = np.sort(rng.uniform(0.3, 1.0, (n, L)), axis=1)
    correct = rng.uniform(size=(n, L)) < conf
    return conf, correct.astype(bool)


def plane_spec() -> ServeSpec:
    return ServeSpec(
        policy="edf", source="frontdoor",
        source_args={"discipline": "drr", "run_queue": 4},
        tenants={"gold": {"weight": 4.0, "rate": 500.0, "burst": 50},
                 "free": {"weight": 1.0, "rate": 200.0, "burst": 20}},
        admission={"mode": "reject", "headroom": 2.0},
        default_slo="std",
        slo_classes={"std": {"rel_deadline": 0.25}},
        batching={"mode": "none", "stage_times": list(STAGE_TIMES)})


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny run (CI examples-smoke job)")
    args = ap.parse_args(argv)
    n = 40 if args.smoke else args.requests

    conf, correct = synthetic_tables()
    spec = plane_spec()
    journal_dir = tempfile.mkdtemp(prefix="plane-journal-")
    print(f"journal: {journal_dir}")

    # -- 1. durable, idempotent, multi-tenant submission ----------------
    journal = Journal(journal_dir, spec=spec, fsync_every=1)
    service = Service.from_spec(spec, conf_table=conf, correct_table=correct)
    door = FrontDoor(service, journal=journal)
    handles = {}
    for i in range(n):
        rid = f"req-{i:04d}"
        handles[rid] = door.submit(
            Request(None, sample=i % conf.shape[0]),
            tenant="gold" if i % 3 == 0 else "free",
            request_id=rid, at=i * 0.01)
    # duplicate submit: same request_id -> the original handle back,
    # no second SUBMIT record
    dup = door.submit(Request(None, sample=0), tenant="gold",
                      request_id="req-0000", at=0.0)
    assert dup is handles["req-0000"], "duplicate must return same handle"
    assert journal.counts["SUBMIT"] == n, "duplicate must not re-journal"
    print(f"submitted {n} requests across 2 tenants "
          f"(+1 duplicate, deduplicated); journal has "
          f"{journal.counts['SUBMIT']} SUBMIT records")

    # -- 2. crash ------------------------------------------------------
    # the virtual-clock service had not run yet: no request was served,
    # no handle resolved.  Simulate the process dying here by abandoning
    # the service and journal objects without draining.
    del service, door, handles, dup
    journal.close()
    print("crashed before serving anything "
          "(journal is the only survivor)")

    # -- 3. recover ----------------------------------------------------
    # rebuild the spec'd engine from the journal header and redo every
    # journaled SUBMIT through the same DRR front door, virtual-clocked
    result = recover(journal_dir, conf_table=conf, correct_table=correct)
    print(f"recovered: {result.replayed} submits redone, "
          f"{result.report['n_redelivered']} newly delivered, "
          f"{result.report['n_pre_delivered']} already delivered pre-crash")
    assert result.delivered_once
    assert result.report["n_redelivered"] == n

    # the redo *is* the uncrashed run: a second recovery redelivers
    # nothing (every request is terminal in the journal now) and its
    # engine decisions reproduce bit-for-bit
    again = recover(journal_dir, conf_table=conf, correct_table=correct)
    rep = verify_recovery(result.metrics.per_request, again)
    assert rep["recovered"] and again.report["n_redelivered"] == 0, rep
    print(f"re-recovery: bitwise={rep['bitwise']} "
          f"delivered_once={rep['delivered_once']} redelivered=0")

    # -- 4. health from the journal alone ------------------------------
    stats = journal_stats(journal_dir)
    print(f"journal_stats: queue_depth={stats['queue_depth']} "
          f"records={stats['records']} segments={stats['segments']}")
    for tenant, c in sorted(stats["per_tenant"].items()):
        print(f"  {tenant}: submitted={c['submitted']} "
              f"retired={c['retired']} rejected={c['rejected']} "
              f"pending={c['pending']}")
    assert stats["queue_depth"] == 0, "recovery must drain the queue"

    met = result.metrics
    print(f"\nper-tenant outcome (recovered run): ")
    for tenant, row in sorted(met.per_tenant.items()):
        print(f"  {tenant}: n={row['n']} served={row['served']} "
              f"miss_rate={row['miss_rate']:.3f} "
              f"mean_depth={row['mean_depth']:.2f}")
    shutil.rmtree(journal_dir, ignore_errors=True)
    print("OK")


if __name__ == "__main__":
    main()
