"""Unified async event-driven serving runtime (one loop).

``EngineCore`` is the single implementation of the paper's user-space
scheduling loop — admit → expire → dispatch → observe → retire, §II-B
deadline semantics, admission control, closed-loop reissue, and result
aggregation — parameterized along three axes:

=====================  ========================  =========================
axis                   discrete-event            wall clock
=====================  ========================  =========================
Clock                  ``VirtualClock``          ``WallClock``
Executor               ``OracleExecutor``        ``DeviceExecutor``
                       (conf/correct tables +    (jitted stage fns,
                       ``BatchTimeModel``)       async XLA dispatch)
RequestSource          ``ClosedLoopSource``      ``StreamSource``
                       (K clients, §IV)          ((offset, Request) list)
=====================  ========================  =========================

Callers do not wire these axes by hand: the front door is
``repro.serving.service`` — a declarative ``ServeSpec`` resolved through
``repro.serving.registry`` builds the ``EngineCore``.  Unbatched serving
is ``ServeSpec(batching={"mode": "none", ...})``: single-bucket pricing,
every dispatch a singleton.

Capabilities of the core:

* ``pipeline_depth=2`` — pipelined async dispatch: the host pre-selects
  batch *N+1* while batch *N* runs on the device, re-validating deadline
  feasibility at true dispatch time (see ``EngineCore._revalidate``).
* ``policy_cost`` — deterministic per-invocation host-cost model, so
  charged-overhead comparisons are reproducible.
* unified host-cost accounting (``sched_charged`` / ``host_serial`` /
  ``host_overhead_frac`` / ``n_dispatches`` on ``SimResult``) on every
  path.

``DeviceExecutor`` lives in ``repro.serving.runtime.device`` (imports jax);
everything imported here is numpy-only so the simulators stay light.
"""
from repro.serving.runtime.clock import Clock, VirtualClock, WallClock
from repro.serving.runtime.core import (EngineCore, ResponseRecorder,
                                        TableRecorder)
from repro.serving.runtime.executor import Executor, OracleExecutor
from repro.serving.runtime.sources import (ClosedLoopSource, RequestSource,
                                           StreamSource)

__all__ = [
    "Clock", "ClosedLoopSource", "EngineCore", "Executor", "OracleExecutor",
    "RequestSource", "ResponseRecorder", "StreamSource", "TableRecorder",
    "VirtualClock", "WallClock",
]
