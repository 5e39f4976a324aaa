"""Host spans and counters the benchmark puts around its calls into the
program's layers.  Only ``--trace 1`` runs install the wrappers; the
window clock's span is the one thing every run opens (it costs one
annotation per run).

Span names (read by :mod:`bench.trace` for the idle-gap attribution):

* ``perfbench.window``: the measured window;
* ``perfbench.scheduler``: the policy's ``on_arrival``, ``on_stage_done``,
  ``next_task`` and ``batch_rank`` calls;
* ``perfbench.dispatch``: batch staging and launch of one stage;
* ``perfbench.wait_device``: the executor blocking on a window's results;
* ``perfbench.commit``: the executor committing one request's exit;
* ``perfbench.await_arrival``: the engine sleeping toward the next event.
"""
from __future__ import annotations

import time

from jax.profiler import TraceAnnotation


class Span:
    """A named host span that can be opened and closed apart."""

    def __init__(self, name: str):
        self.name = name
        self._ann = None

    def open(self):
        if self._ann is None:
            self._ann = TraceAnnotation(self.name)
            self._ann.__enter__()

    def close(self):
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None


def window_clock(base_cls, *, annotate_sleep: bool = False):
    """A wall clock of class ``base_cls`` whose start opens the window span
    and records the start on ``perf_counter``."""

    class WindowClock(base_cls):
        def __init__(self):
            super().__init__()
            self.span = Span("perfbench.window")
            self.started_at = None

        def start(self):
            if self.started_at is None:
                self.span.open()
                self.started_at = time.perf_counter()
            super().start()

        def advance_to(self, t):
            if not annotate_sleep:
                return super().advance_to(t)
            with TraceAnnotation("perfbench.await_arrival"):
                return super().advance_to(t)

    return WindowClock()


class TimedPolicy:
    """Delegates every policy call to ``base``, timing the scheduler's
    own work and annotating it.  ``seconds`` and ``calls`` accumulate."""

    _TIMED = ("on_arrival", "on_stage_done", "next_task", "batch_rank")

    def __init__(self, base):
        self.__dict__["base"] = base
        self.__dict__["seconds"] = 0.0
        self.__dict__["calls"] = 0

    def __getattr__(self, item):
        attr = getattr(self.base, item)
        if item not in self._TIMED:
            return attr

        def timed(*a, **k):
            t0 = time.perf_counter()
            with TraceAnnotation("perfbench.scheduler"):
                out = attr(*a, **k)
            self.__dict__["seconds"] += time.perf_counter() - t0
            self.__dict__["calls"] += 1
            return out
        return timed

    def __setattr__(self, item, value):
        setattr(self.base, item, value)


class RecordingStageFns:
    """Wraps a stage-fns object: each ``run`` is annotated and recorded as
    ``(stage, valid rows, bucket rows, sequence length)``."""

    def __init__(self, inner, buckets, seq_len_of):
        self.__dict__["inner"] = inner
        self.__dict__["log"] = []
        self.__dict__["_buckets"] = tuple(sorted(buckets))
        self.__dict__["_seq_len_of"] = seq_len_of

    def __getattr__(self, item):
        return getattr(self.inner, item)

    def __setattr__(self, item, value):
        setattr(self.inner, item, value)

    def run(self, stage, params, pytrees):
        n = len(pytrees)
        bucket = next(b for b in self._buckets if b >= n)
        self.log.append((int(stage), n, bucket, self._seq_len_of(pytrees[0])))
        with TraceAnnotation("perfbench.dispatch"):
            return self.inner.run(stage, params, pytrees)


def annotate_method(obj, name: str, span: str) -> None:
    """Wrap ``obj.<name>`` (an instance attribute from then on) in a span."""
    fn = getattr(obj, name)

    def wrapped(*a, **k):
        with TraceAnnotation(span):
            return fn(*a, **k)
    setattr(obj, name, wrapped)


def time_method(obj, name: str, totals: dict) -> None:
    """Wrap ``obj.<name>`` so that its seconds add up in ``totals[name]``."""
    fn = getattr(obj, name)
    totals.setdefault(name, 0.0)

    def wrapped(*a, **k):
        t0 = time.perf_counter()
        try:
            return fn(*a, **k)
        finally:
            totals[name] += time.perf_counter() - t0
    setattr(obj, name, wrapped)


class CompileCounter:
    """Counts XLA compilations while ``active`` (JAX's monitoring events)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.active and event == self.EVENT:
            self.count += 1
