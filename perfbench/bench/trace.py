"""Reduction of a profiler trace to device busy time, op time and idle gaps.

A trace is read once into plain interval lists (nanoseconds on the
profiler's clock, which the host spans and the device ops share):

* ``device``: per device plane, ``(name, start, end)`` of every XLA op;
* ``host``: ``(name, start, end)`` of the host spans the benchmark opened
  (``jax.profiler.TraceAnnotation`` names starting with ``perfbench.``).

Everything below works on those lists, so the tests feed it synthetic
intervals.
"""
from __future__ import annotations

import glob
import os
import re

SPAN_PREFIX = "perfbench."
WINDOW_SPAN = "perfbench.window"
DEVICE_PLANE_PREFIX = "/device:"
OP_LINE = "XLA Ops"


def load(trace_dir: str) -> dict:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    device, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name != OP_LINE:
                    continue
                for e in line.events:
                    s = float(e.start_ns)
                    ops.append((e.name, s, s + float(e.duration_ns)))
            if ops:
                device[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        s = float(e.start_ns)
                        host.append((e.name, s, s + float(e.duration_ns)))
    return {"device": device, "host": host, "file": files[-1]}


def window(tr: dict):
    """``(start, end)`` of the measured window's host span."""
    spans = [(s, e) for n, s, e in tr["host"] if n == WINDOW_SPAN]
    if not spans:
        raise ValueError("the trace holds no window span")
    return min(s for s, _ in spans), max(e for _, e in spans)


def merge(intervals, lo: float, hi: float) -> list:
    """Union of ``(start, end)`` intervals clipped to ``[lo, hi]``."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def busy_ns(ops, lo: float, hi: float) -> float:
    return sum(e - s for s, e in merge([(s, e) for _, s, e in ops], lo, hi))


def gaps(ops, lo: float, hi: float) -> list:
    """Idle intervals of one device inside ``[lo, hi]``."""
    out, t = [], lo
    for s, e in merge([(s, e) for _, s, e in ops], lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def op_time(ops, lo: float, hi: float) -> dict:
    """Seconds per op name inside the window."""
    tot = {}
    for n, s, e in ops:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            tot[n] = tot.get(n, 0.0) + d * 1e-9
    return tot


NO_SPAN = "no benchmark span"


def flatten(spans) -> list:
    """Nested host spans as non-overlapping ``(owner, start, end)`` pieces,
    each owned by the innermost span open over it."""
    evs = []
    for i, (_n, s, e) in enumerate(spans):
        evs.append((s, 1, -e, i))
        evs.append((e, 0, 0.0, i))
    evs.sort()                      # ends before starts; outer starts first
    stack, out, t_prev = [], [], None
    for t, kind, _neg_end, i in evs:
        if stack and t > t_prev:
            out.append((spans[stack[-1]][0], t_prev, t))
        if kind:
            stack.append(i)
        elif i in stack:
            stack.remove(i)
        t_prev = t
    return out


def attribute_gaps(idle, spans) -> dict:
    """Seconds of ``idle`` time covered by each host span name (the
    innermost open span takes it); idle time under none goes to
    :data:`NO_SPAN`."""
    out = {}
    pieces = flatten(spans)
    j = 0
    for gs, ge in sorted(idle):
        while j < len(pieces) and pieces[j][2] <= gs:
            j += 1
        covered, k = 0.0, j
        while k < len(pieces) and pieces[k][1] < ge:
            n, s, e = pieces[k]
            d = min(e, ge) - max(s, gs)
            if d > 0:
                out[n] = out.get(n, 0.0) + d * 1e-9
                covered += d
            k += 1
        rest = (ge - gs) - covered
        if rest > 0:
            out[NO_SPAN] = out.get(NO_SPAN, 0.0) + rest * 1e-9
    return out


_OP = re.compile(r"^%(\S+?) = (.+?) ([\w\-]+)\(")
#: ops that only contain other ops (a scanned layer stack is one ``while``)
CONTAINERS = ("while", "conditional", "call")


def op_label(name: str) -> str:
    """``"<opcode> <result type>"`` of an XLA op's text, layouts dropped:
    stable across compiles that renumber the ops."""
    m = _OP.match(name)
    if not m:
        return name[:100]
    typ = re.sub(r"\{[^}]*\}", "", m.group(2))
    return f"{m.group(3)} {typ}"[:100]


def top(d: dict, k: int = 10) -> list:
    return [[n, v] for n, v in sorted(d.items(), key=lambda x: -x[1])[:k]]


def summarize(tr: dict) -> dict:
    """Busy and window seconds averaged over devices, and the breakdown."""
    lo, hi = window(tr)
    devs = list(tr["device"].values())
    if not devs:
        raise ValueError("the trace holds no device ops")
    busy = [busy_ns(ops, lo, hi) * 1e-9 for ops in devs]
    ops_t, labels, gap_t = {}, {}, {}
    spans = [x for x in tr["host"] if x[0] != WINDOW_SPAN]
    for ops in devs:
        for n, v in op_time(ops, lo, hi).items():
            ops_t[n] = ops_t.get(n, 0.0) + v / len(devs)
            lab = op_label(n)
            if lab.split(" ")[0] not in CONTAINERS:
                labels[lab] = labels.get(lab, 0.0) + v / len(devs)
        for n, v in attribute_gaps(gaps(ops, lo, hi), spans).items():
            gap_t[n] = gap_t.get(n, 0.0) + v / len(devs)
    return {"lo": lo, "hi": hi, "window_s": (hi - lo) * 1e-9,
            "busy_s": sum(busy) / len(busy), "op_seconds": ops_t,
            "gap_seconds": gap_t,
            "breakdown": {"device_ops": top(labels),
                          "idle_gaps": top(gap_t)}}
