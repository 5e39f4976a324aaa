"""The program's own host spans (``repro.*``) over the device's idle time.

The program marks its host work with ``jax.profiler.TraceAnnotation``
spans named ``repro.<layer>.<what>`` (``repro.serving.obs.span``); they
share the device trace's clock.  This module reads them, with the device
ops, from the newest ``.xplane.pb`` of the traced run (once per run: the
readers of ``metrics/`` share the run's context, which keeps the result),
clips both to the measured window, and gives each device-idle interval to
the innermost program span open over it, with
:func:`bench.trace.attribute_gaps`.

The groups the metrics read:

* ``launch``: ``repro.executor.launch`` (enqueueing device work);
* ``readback``: ``repro.executor.readback`` (device-to-host reads);
* ``engine``: ``repro.engine.*``, ``repro.scheduler`` and ``repro.source.*``
  (the serving loop, the policy, the token loop's sampling).

Idle time under any other program span (``repro.executor.wait``,
``repro.executor.stage_inputs``), or under none, belongs to no group.  A
trace without program spans (a program that does not open them) gives
``None``, never an error.
"""
from __future__ import annotations

import glob
import os

from bench import trace as trace_mod

PREFIX = "repro."
LAUNCH = "repro.executor.launch"
READBACK = "repro.executor.readback"
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: where ``run.py`` writes a traced run's profile
TRACE_DIR = os.path.join(os.path.dirname(HERE), ".perfbench_out", "trace")


def group(name: str):
    """The idle group a program span's name belongs to, or ``None``."""
    if name == LAUNCH:
        return "launch"
    if name == READBACK:
        return "readback"
    if (name.startswith(("repro.engine.", "repro.source."))
            or name == "repro.scheduler"):
        return "engine"
    return None


def newest(trace_dir: str):
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return files[-1] if files else None


def load(path: str) -> dict:
    """Device ops per device plane and the ``repro.*`` host spans, as
    ``(name, start_ns, end_ns)`` on the profiler's clock."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith(trace_mod.DEVICE_PLANE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name != trace_mod.OP_LINE:
                    continue
                for e in line.events:
                    s = float(e.start_ns)
                    ops.append((e.name, s, s + float(e.duration_ns)))
            if ops:
                device[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        s = float(e.start_ns)
                        host.append((e.name, s, s + float(e.duration_ns)))
    return {"device": device, "host": host}


def clip(spans, lo: float, hi: float) -> list:
    """Spans cut to ``[lo, hi]``; those wholly outside it dropped."""
    out = []
    for n, s, e in spans:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((n, s, e))
    return out


def reduce(tr: dict, lo: float, hi: float):
    """``{"idle_s": {group: seconds}, "launch_s": [seconds, ...]}`` of the
    window ``[lo, hi]``: idle seconds averaged over the devices, and the
    duration of each launch span.  ``None`` without program spans or
    device ops in the window."""
    spans = clip(tr["host"], lo, hi)
    devs = list(tr["device"].values())
    if not spans or not devs:
        return None
    idle = {"launch": 0.0, "readback": 0.0, "engine": 0.0}
    for ops in devs:
        by_span = trace_mod.attribute_gaps(trace_mod.gaps(ops, lo, hi),
                                           spans)
        for name, sec in by_span.items():
            g = group(name)
            if g is not None:
                idle[g] += sec / len(devs)
    return {"idle_s": idle,
            "launch_s": [(e - s) * 1e-9 for n, s, e in spans if n == LAUNCH]}


def read(ctx):
    """This run's reduction (see :func:`reduce`), read from the run's
    trace on the first call and kept on ``ctx``; ``None`` where there is
    nothing to read."""
    if not hasattr(ctx, "program_spans"):
        path = newest(getattr(ctx, "trace_dir", TRACE_DIR))
        ctx.program_spans = None if path is None else reduce(
            load(path), ctx.summary["lo"], ctx.summary["hi"])
    return ctx.program_spans


def idle_ms_per_token(ctx, which: str):
    """Idle milliseconds per served token under the group ``which``."""
    n = ctx.out.get("n_tokens")
    r = read(ctx)
    if not n or r is None:
        return None
    return 1e3 * r["idle_s"][which] / n
