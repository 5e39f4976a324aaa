"""Serving package — one public front door, one runtime core.

New code talks to :class:`~repro.serving.service.Service` built from a
declarative :class:`~repro.serving.service.ServeSpec` (components named by
registry key — see :mod:`repro.serving.registry`), which builds the one
event loop, :class:`~repro.serving.runtime.EngineCore`.
"""
from repro.serving.engine import (Request, Response, closed_loop_stream,
                                  profile_host_overhead)
from repro.serving.batch import (AdmissionController, BatchedPolicy,
                                 BatchedStageFns, BatchPolicy, BatchTimeModel,
                                 LengthBucketTimeModel, StageBatcher,
                                 as_batch_policy, pad_batch,
                                 profile_batched_stages)
from repro.serving.registry import (available, register_clock,
                                    register_executor, register_policy,
                                    register_source)
from repro.serving.runtime import (ClosedLoopSource, EngineCore,
                                   OracleExecutor, StreamSource, TableRecorder,
                                   VirtualClock, WallClock)
from repro.serving.service import (ResponseHandle, ServeSpec, Service,
                                   ServiceMetrics, ServiceResponse, SLOClass,
                                   StageExit)
# importing the traffic subsystem registers its source keys
# ("traffic", "replay") — see repro.serving.traffic for the full surface
from repro.serving.traffic import (MetricsStreamer, RequestMix, Scenario,
                                   ServiceSnapshot, TraceRecorder,
                                   TrafficSource, load_trace,
                                   make_arrival_process, record_trace,
                                   scenario_spec, verify_replay)
# the durable request plane registers "durable" and "frontdoor" —
# see repro.serving.plane for the full surface
from repro.serving.plane import (DurableQueue, FrontDoor, Journal, Record,
                                 journal_stats, recover, scan_journal,
                                 verify_recovery)
# the multi-model zoo registers "rtdeepiot-zoo" and "zoo-oracle"
# ("zoo-device" is jax-heavy and registers from repro.launch.serve)
from repro.serving.zoo import (ModelZoo, ZooAdmissionController, ZooModel,
                               ZooOracleExecutor, ZooRTDeepIoT,
                               ZooTimeModel)
# observability: per-request tracing, decision audit log, metrics registry
# (enable with ServeSpec(trace={"enabled": True}); see docs/observability.md)
from repro.serving.obs import (MetricsRegistry, RequestTrace, Span, Tracer,
                               chrome_trace, load_obs,
                               validate_chrome_trace, write_jsonl)
# adaptive control registers "rtdeepiot-adaptive" — learned workload /
# confidence curves, predictive admission, wall-clock traffic driver
# (see repro.serving.adaptive and docs/adaptive.md)
from repro.serving.adaptive import (OnlineCurveEstimator,
                                    PredictiveAdmissionController,
                                    TrafficDriver, fit_arrival_process,
                                    fit_report)

__all__ = ["Request", "Response", "closed_loop_stream",
           "profile_host_overhead", "AdmissionController", "BatchedPolicy",
           "BatchedStageFns", "BatchPolicy", "BatchTimeModel",
           "LengthBucketTimeModel", "StageBatcher", "as_batch_policy",
           "pad_batch", "profile_batched_stages",
           "ClosedLoopSource", "EngineCore", "OracleExecutor", "StreamSource",
           "TableRecorder", "VirtualClock", "WallClock",
           "ResponseHandle", "ServeSpec", "Service", "ServiceMetrics",
           "ServiceResponse", "SLOClass", "StageExit",
           "available", "register_clock", "register_executor",
           "register_policy", "register_source",
           "MetricsStreamer", "RequestMix", "Scenario", "ServiceSnapshot",
           "TraceRecorder", "TrafficSource", "load_trace",
           "make_arrival_process", "record_trace", "scenario_spec",
           "verify_replay",
           "DurableQueue", "FrontDoor", "Journal", "Record",
           "journal_stats", "recover", "scan_journal", "verify_recovery",
           "ModelZoo", "ZooAdmissionController", "ZooModel",
           "ZooOracleExecutor", "ZooRTDeepIoT", "ZooTimeModel",
           "MetricsRegistry", "RequestTrace", "Span", "Tracer",
           "chrome_trace", "load_obs", "validate_chrome_trace",
           "write_jsonl",
           "OnlineCurveEstimator", "PredictiveAdmissionController",
           "TrafficDriver", "fit_arrival_process", "fit_report"]
