"""``correct`` fails where it must: the control (the plain reference in
float8 put in the bfloat16 program's place) and each fault the cells can
have, planted under a tiny run of the harness on the CPU."""
from __future__ import annotations

import copy

import pytest

import perfbench_tiny
from bench import cell, decode, faults, prefill, program, weights

TEXT = perfbench_tiny.TEXT


def _limits(driver):
    tr = perfbench_tiny.PREFILL if driver == "prefill" else perfbench_tiny.DECODE
    return tr["correct"]["limits"]


@pytest.mark.parametrize("driver", ["prefill", "decode"])
def test_the_control_fails_where_the_program_passes(driver):
    m = dict(TEXT)
    cfg = program.program_config(m, perfbench_tiny.TEXT_OVERRIDES)
    ref_mod = cell.reference_module(m)
    params = weights.make_weights(m, 5)
    if driver == "prefill":
        c = prefill.PrefillCell(m, copy.deepcopy(perfbench_tiny.PREFILL), cfg,
                                interpret=True)
        c.setup(params)
        reqs = c.requests(2.0, 5)
        e = prefill.end_to_end(c.serve(reqs, trace=False), reqs, 2.0)
        args = (ref_mod, m, params, e["served"], reqs, 16, 5)
        prog, ctl = prefill.check(*args), prefill.check(*args, rounding="fp8")
    else:
        c = decode.DecodeCell(m, copy.deepcopy(perfbench_tiny.DECODE), cfg)
        c.setup(params)
        out = c.serve(5.0, 5, trace=False)
        prog = decode.check(ref_mod, m, params, out, c.slots)
        ctl = decode.check(ref_mod, m, params, out, c.slots, rounding="fp8")
    import run as runner
    limits = _limits(driver)
    assert runner.judge(prog, limits)[0] is True, prog
    assert runner.judge(ctl, limits)[0] is False, ctl


BURST = dict(copy.deepcopy(perfbench_tiny.PREFILL),
             arrivals={"kind": "poisson", "rate": 150.0},
             correct={"sample": 64,
                      "limits": perfbench_tiny.PREFILL["correct"]["limits"]})


FAULT_CASES = [
    (faults.answer_altered, "tiny.prefill", None),
    (faults.prefill_half_batch, "tiny.prefill", BURST),
    (faults.token_altered, "tiny.decode", None),
    (faults.decode_half_batch, "tiny.decode", None),
    (faults.state_unchanged, "tiny.decode", None),
]


@pytest.mark.parametrize("fault,cell_name,traffic", FAULT_CASES,
                         ids=["answer-altered", "half-batch", "token-altered",
                              "decode-half-batch", "state-unchanged"])
def test_a_planted_fault_makes_correct_false(monkeypatch, fault, cell_name,
                                             traffic):
    fault(monkeypatch.setattr, TEXT)
    r = perfbench_tiny.run(cell_name, seed=9, seconds=1.5, traffic=traffic)
    assert r["correct"] is False, r["compared"]


def test_every_fault_of_every_driver_is_planted_above():
    planted = {f for d in faults.BY_DRIVER.values() for f in d.values()}
    assert planted == {f for f, _c, _t in FAULT_CASES}
