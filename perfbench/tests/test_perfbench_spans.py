"""The program-span readers on the CPU: idle time given to the innermost
``repro.*`` span, clipped to the window, and the four metrics that read
it, on synthetic intervals and on a real profile."""
from __future__ import annotations

import pytest

import perfbench_tiny  # noqa: F401  (puts perfbench/ and src/ on the path)
from bench import cell, spans  # noqa: E402

DEV = "/device:TPU:0"


def _reduce(ops, host, lo=0, hi=100):
    return spans.reduce({"device": {DEV: ops}, "host": host}, lo, hi)


def test_the_innermost_program_span_takes_the_idle_time():
    host = [("repro.engine.run", 0, 100),
            ("repro.engine.retire", 60, 90),
            ("repro.source.advance", 65, 80),
            ("repro.executor.launch", 10, 30),
            ("repro.executor.readback", 40, 50)]
    ops = [("op", 20, 40), ("op", 45, 60)]
    r = _reduce(ops, host)
    # idle: [0,20) [40,45) [60,100)
    assert r["idle_s"]["launch"] == pytest.approx(10e-9)     # [10,20)
    assert r["idle_s"]["readback"] == pytest.approx(5e-9)    # [40,45)
    # [0,10) under the run span, [60,100) under retire/advance/run
    assert r["idle_s"]["engine"] == pytest.approx(50e-9)
    assert r["launch_s"] == [pytest.approx(20e-9)]


def test_a_program_span_inside_a_launch_is_not_the_launch():
    host = [("repro.executor.launch", 0, 50),
            ("repro.executor.stage_inputs", 10, 30),
            ("repro.scheduler", 60, 70)]
    r = _reduce([("op", 50, 60)], host)
    assert r["idle_s"]["launch"] == pytest.approx(30e-9)     # [0,10) [30,50)
    assert r["idle_s"]["engine"] == pytest.approx(10e-9)     # [60,70)
    assert r["idle_s"]["readback"] == 0.0


@pytest.mark.parametrize("left_out", ["repro.executor.wait", None])
def test_idle_under_no_group_is_left_out_of_all_three(left_out):
    host = [("repro.executor.readback", 0, 10)]
    if left_out:
        host.append((left_out, 20, 60))
    r = _reduce([("op", 90, 100)], host)
    # idle [0,90): 10 under readback, the rest under wait or no span
    assert r["idle_s"] == {"launch": 0.0, "readback": pytest.approx(10e-9),
                           "engine": 0.0}


def test_spans_and_ops_are_clipped_to_the_window():
    host = [("repro.executor.launch", -50, 30),
            ("repro.executor.launch", 40, 60),
            ("repro.executor.readback", 90, 150),
            ("repro.engine.admit", 200, 300)]
    ops = [("op", -100, 10), ("op", 60, 90)]
    r = _reduce(ops, host, lo=0, hi=100)
    assert r["idle_s"]["launch"] == pytest.approx((20 + 20) * 1e-9)
    assert r["idle_s"]["readback"] == pytest.approx(10e-9)
    assert r["idle_s"]["engine"] == 0.0
    assert r["launch_s"] == [pytest.approx(30e-9), pytest.approx(20e-9)]


def test_idle_is_averaged_over_devices():
    host = [("repro.executor.launch", 0, 100)]
    tr = {"device": {DEV: [("op", 0, 50)], "/device:TPU:1": [("op", 0, 100)]},
          "host": host}
    assert spans.reduce(tr, 0, 100)["idle_s"]["launch"] == \
        pytest.approx(25e-9)


def test_a_trace_without_program_spans_reads_nothing():
    assert _reduce([("op", 0, 10)], []) is None
    assert _reduce([("op", 0, 10)], [("repro.scheduler", 200, 300)]) is None
    assert spans.reduce({"device": {}, "host": [("repro.scheduler", 0, 9)]},
                        0, 100) is None


class _Ctx:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def test_the_four_metrics_read_the_trace_once(tmp_path, monkeypatch):
    f = tmp_path / "plugins" / "profile" / "host.xplane.pb"
    f.parent.mkdir(parents=True)
    f.write_bytes(b"")
    loads = []
    host = [("repro.engine.run", 0, 1000),
            ("repro.executor.launch", 100, 300),
            ("repro.executor.launch", 500, 600),
            ("repro.executor.readback", 700, 800)]

    def fake_load(path):
        loads.append(path)
        return {"device": {DEV: [("op", 200, 500), ("op", 800, 1000)]},
                "host": host}

    monkeypatch.setattr(spans, "load", fake_load)
    ctx = _Ctx(trace_dir=str(tmp_path), summary={"lo": 0.0, "hi": 1000.0},
               out={"n_tokens": 2})
    got = {n: cell.metric_reader(n)(ctx) for n in
           ("idle_ms.launch", "idle_ms.readback", "idle_ms.engine",
            "launch_ms")}
    assert loads == [str(f)]
    # idle [0,200) [500,800): launch [100,200)+[500,600), readback
    # [700,800), the run span [0,100)+[600,700); per token of 2
    assert got["idle_ms.launch"] == pytest.approx(1e3 * 200e-9 / 2)
    assert got["idle_ms.readback"] == pytest.approx(1e3 * 100e-9 / 2)
    assert got["idle_ms.engine"] == pytest.approx(1e3 * 200e-9 / 2)
    assert got["launch_ms"] == pytest.approx(1e3 * 150e-9)
    assert cell.metric_reader("idle_ms.launch")(
        _Ctx(trace_dir=str(tmp_path), summary=ctx.summary,
             out={"n_tokens": 0})) is None


def test_a_real_profile_yields_the_program_spans_only(tmp_path):
    import jax
    import jax.numpy as jnp
    from repro.serving.obs import span
    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((8, 8))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with span("repro.engine.run"):
            with span("repro.executor.launch", depth=1, hit=False):
                y = f(x)
            with jax.profiler.TraceAnnotation("other.span"):
                y.block_until_ready()
    tr = spans.load(spans.newest(str(tmp_path)))
    assert sorted(n for n, _s, _e in tr["host"]) == [
        "repro.engine.run", "repro.executor.launch"]
    (_, s0, e0), = [x for x in tr["host"] if x[0] == "repro.engine.run"]
    (_, s1, e1), = [x for x in tr["host"] if x[0] != "repro.engine.run"]
    assert s0 <= s1 < e1 <= e0
    # the CPU backend writes no device plane: the readers read nothing
    ctx = _Ctx(trace_dir=str(tmp_path), summary={"lo": s0, "hi": e0},
               out={"n_tokens": 1})
    assert spans.read(ctx) is None
    assert cell.metric_reader("idle_ms.engine")(ctx) is None
