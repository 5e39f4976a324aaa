"""Request types and host-side helpers of the serving loop.

* ``Request`` / ``Response`` — what a stream source admits (input pytree,
  relative deadline, sample / client / SLO / tenant labels) and what the
  device executors answer (deepest in-time exit, latency, miss).
* ``closed_loop_stream`` — the paper's K-client workload laid out as an
  ``(offset_seconds, Request)`` stream for the ``stream`` source.
* ``profile_host_overhead`` — the per-dispatch host cost behind the §II-B
  deadline adjustment (``ServeSpec.host_overhead``).

The loop itself is ``repro.serving.runtime.EngineCore``, built through
``repro.serving.Service``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Optional

import jax
import numpy as np


@dataclasses.dataclass
class Request:
    inputs: Any                    # single-sample input pytree (no batch dim)
    rel_deadline: Optional[float] = None   # None: the SLO class supplies it
    sample: int = 0
    client: int = 0
    arrival: float = 0.0           # wall time, filled by the engine
    slo: Optional[str] = None      # SLO class name (repro.serving.service)
    tenant: Optional[str] = None   # tenant label (repro.serving.plane)
    request_id: Optional[str] = None  # idempotence key (durable plane)
    seq_len: Optional[int] = None  # ragged input length (length-bucket WCETs)
    model: Optional[str] = None    # model-zoo id (repro.serving.zoo)


@dataclasses.dataclass
class Response:
    sample: int
    prediction: Optional[int]
    confidence: float
    depth: int
    missed: bool
    latency: float
    deadline: float


def profile_host_overhead(*, n_runs: int = 100,
                          percentile: float = 99.0) -> float:
    """Host dispatch overhead: round-trip of a no-op jitted call (§II-B).

    This is the per-dispatch CPU cost the engine pays before the accelerator
    starts a stage, so the caller-visible deadline is shrunk by it."""
    noop = jax.jit(lambda x: x)
    z = np.zeros((), np.float32)
    jax.block_until_ready(noop(z))                 # compile
    samples = np.zeros(n_runs)
    for i in range(n_runs):
        t0 = time.perf_counter()
        jax.block_until_ready(noop(z))
        samples[i] = time.perf_counter() - t0
    return float(np.percentile(samples, percentile))


def closed_loop_stream(dataset_inputs, labels, *, n_clients, d_lo, d_hi,
                       n_requests, seed=0, spacing=None):
    """Open-loop approximation of the paper's K-client workload for the
    wall-clock engine: K interleaved request lanes with deadline-spaced
    issue times."""
    rng = np.random.default_rng(seed)
    n = len(labels)
    order = rng.permutation(n)
    reqs = []
    t_client = np.zeros(n_clients)
    for j in range(n_requests):
        c = int(np.argmin(t_client))
        rel = float(rng.uniform(d_lo, d_hi))
        sample = int(order[j % n])
        inputs = jax.tree.map(lambda x: x[sample:sample + 1], dataset_inputs)
        reqs.append((float(t_client[c]), Request(inputs, rel, sample, c)))
        t_client[c] += rel if spacing is None else spacing
    reqs.sort(key=lambda p: p[0])
    return reqs
