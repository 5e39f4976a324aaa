"""Host spans on the profiler's clock — the tracing system's second plane.

The :class:`~repro.serving.obs.tracer.Tracer` records each request's life
on the *engine's* clock.  :func:`span` marks what the host is doing, as a
``jax.profiler.TraceAnnotation``: while a profiler session is open
(``jax.profiler.trace``) each span lands on the trace's host plane, on the
same timeline as the device's ops; otherwise it records nothing and costs
well under a microsecond.  Spans never read or charge the engine's clock,
so a virtual-clock run is bit-for-bit the same with a profiler session
open or not.  They are host-side only: never open one inside jitted code.

Names are ``repro.<layer>.<what>``:

=============================  ==============================================
``repro.engine.run``           ``EngineCore.run``, opened just before the
                               clock starts: on a wall clock, engine time
                               *t* lies at this span's start + *t*
``repro.engine.admit``         ``EngineCore._admit``
``repro.engine.retire``        ``EngineCore._retire`` (recorder + source)
``repro.scheduler``            each policy call the engine times for
                               ``sched_charged``, and the batch re-formed at
                               a pre-selection's re-validation
``repro.source.advance``       the token loop's cache swap and sampling
``repro.executor.launch``      everything in ``submit`` that enqueues device
                               work (``decode``: metadata ``depth``,
                               ``hit``, ``chained``)
``repro.executor.stage_inputs``  ``StagingBuffers.stage`` (host batch rows)
``repro.executor.wait``        blocking on the device in ``complete``
``repro.executor.readback``    device→host reads of a window's results; the
                               ``decode`` executor's reads only a copy
                               ``submit`` started, dispatching nothing
                               (metadata ``depth``, ``overlapped``)
=============================  ==============================================
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

__all__ = ["span"]


def span(name: str, **meta) -> TraceAnnotation:
    """A context manager that records ``name`` (with ``meta`` as the
    event's metadata) on the profiler's host plane while a profiler
    session is open."""
    return TraceAnnotation(name, **meta)
