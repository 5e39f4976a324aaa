"""DeviceExecutor — real jitted stage functions behind the runtime core.

``submit`` dispatches the batched stage *without* blocking (XLA dispatch is
asynchronous), so with ``pipeline_depth=2`` the core pre-selects the next
batch on the host while the device computes; ``complete`` blocks on the
results and reads the wall clock for the completion time, right after
``block_until_ready``.

Multiple in-flight windows: the executor accepts up to ``max_inflight``
submitted-but-uncompleted batches (a FIFO — XLA executes dispatches in
submission order on one device stream).  The core enqueues further windows
while ``accepting`` is true (``pipeline_depth >= 3``), so the device never
drains between windows waiting for host work.  ``complete`` retires the
oldest window; ``running_tasks`` covers every queued window so the core
never double-dispatches an in-flight task.

Per-request state (input/hidden pytree, deepest in-time exit) lives here:
the executor is the layer that owns device data.  Its ``states`` dict is
the serving stack's hidden-state cache: a request's state is registered
at admission, **persisted across stage dispatches** (each ``commit``
slices the request's row out of the batched stage output — a
device-resident array, never copied to host between stages) and
**evicted on retire** (the recorder pops it via ``pop_state``).
``cache_stats()`` reports live/peak/evicted counts so tests and metrics
can hold the cache to that lifecycle.  ``ShardedDeviceExecutor``
(:mod:`repro.launch.sharded`) runs the same contract with stage fns
sharded over a device mesh; ``KernelDeviceExecutor``
(:mod:`repro.launch.kernel`) swaps the stage bodies for
Pallas-kernel-backed fns.

Telemetry: per-stage host seconds (synchronous dispatch + commit work,
measured on ``perf_counter`` so it is meaningful under any engine clock)
vs device seconds (time the host spent *blocked* in ``block_until_ready``)
— the measured decomposition behind the kernel-serving figure's
"device-time-dominated" claim, surfaced via :meth:`device_time_stats`.
"""
from __future__ import annotations

import collections
import math
import time

import jax
import numpy as np

from repro.serving.obs.hostspans import span


class DeviceExecutor:
    def __init__(self, stage_fns, params, time_model, *,
                 max_inflight: int = 1):
        self.stage_fns = stage_fns      # object with .run(stage, params, [h])
        self.params = params
        self.time_model = time_model
        self.max_inflight = max(1, int(max_inflight))
        self.total_busy = 0.0           # host-observed device-busy seconds
        self.states: dict = {}          # tid -> [request, hidden/inputs, exit]
        self.evictions = 0              # states popped on retire
        self.peak_cached = 0            # high-water mark of live states
        self._inflight = collections.deque()   # submitted, oldest first
        self._done = None
        # per-stage host/device seconds (see module docstring)
        self.stage_host_time: dict = collections.defaultdict(float)
        self.stage_device_time: dict = collections.defaultdict(float)

    # -- request state (the hidden-state cache) ------------------------
    def register(self, task, request) -> None:
        """Admit ``task``'s state into the cache (raw inputs until the
        first stage commits a hidden row)."""
        self.states[task.tid] = [request, request.inputs, None]
        self.peak_cached = max(self.peak_cached, len(self.states))

    def pop_state(self, task):
        """Evict on retire — the other end of the cache lifecycle."""
        self.evictions += 1
        return self.states.pop(task.tid)

    def cache_stats(self) -> dict:
        return dict(live=len(self.states), peak=self.peak_cached,
                    evictions=self.evictions)

    def device_time_stats(self) -> dict:
        """Measured per-stage host vs device seconds (and their totals)."""
        return dict(
            host_time=float(sum(self.stage_host_time.values())),
            device_time=float(sum(self.stage_device_time.values())),
            stage_host_time={int(s): float(v)
                             for s, v in sorted(self.stage_host_time.items())},
            stage_device_time={int(s): float(v) for s, v in
                               sorted(self.stage_device_time.items())})

    # -- stage dispatch (subclass seam) --------------------------------
    def _dispatch_stage(self, stage: int, tasks: list):
        """Run the batched stage, returning the window's payload (opaque
        to the core; ``_commit_from`` consumes it)."""
        hs = [self.states[t.tid][1] for t in tasks]
        h_out, logits, conf, _mask = self.stage_fns.run(stage, self.params,
                                                        hs)
        return h_out, logits, conf

    def _block_on(self, payload) -> None:
        jax.block_until_ready(payload[0])

    def _finalize(self, payload):
        h_out, logits, conf = payload
        return h_out, np.asarray(logits), np.asarray(conf)

    # -- Executor contract ---------------------------------------------
    @property
    def busy(self) -> bool:
        return bool(self._inflight)

    @property
    def accepting(self) -> bool:
        """May the core submit another window while ``busy``?"""
        return len(self._inflight) < self.max_inflight

    def wcet(self, stage: int, n: int) -> float:
        return self.time_model.wcet(stage, n)

    def submit(self, stage: int, tasks: list, now: float) -> None:
        w0 = time.perf_counter()
        with span("repro.executor.launch", stage=stage, n=len(tasks)):
            payload = self._dispatch_stage(stage, tasks)
        self.stage_host_time[stage] += time.perf_counter() - w0
        self._inflight.append((stage, tasks, payload, now))

    def finish_time(self):
        # real devices do not announce completion times — the core must
        # block (None), unlike the oracle executor's known virtual finish
        return None if self.busy else math.inf

    def complete(self, clock):
        stage, tasks, payload, t0 = self._inflight.popleft()
        w0 = time.perf_counter()
        with span("repro.executor.wait"):
            self._block_on(payload)
        self.stage_device_time[stage] += time.perf_counter() - w0
        self.total_busy += clock.now() - t0
        with span("repro.executor.readback"):
            self._done = (stage, self._finalize(payload))
        return stage, tasks

    def commit(self, task, k: int) -> float:
        stage, (h_out, logits, conf) = self._done
        w0 = time.perf_counter()
        c = float(np.max(conf[k]))
        lg = logits[k]
        pred = int(np.argmax(lg[0], -1)) if lg.ndim >= 2 else int(np.argmax(lg))
        st = self.states[task.tid]
        st[1] = jax.tree.map(lambda x: x[k:k + 1], h_out)
        st[2] = (pred, c)
        self.stage_host_time[stage] += time.perf_counter() - w0
        return c

    def running_tasks(self) -> list:
        return [t for (_s, tasks, _p, _t0) in self._inflight for t in tasks]
