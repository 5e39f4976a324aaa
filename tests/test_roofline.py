"""Roofline machinery tests: HLO collective parser, probe fit math, and the
table row computation."""
import pytest

from repro.roofline.collectives import collective_bytes_from_hlo
from repro.roofline.probes import (_eval_linear, _eval_quad, _fit_linear,
                                   _fit_quad, METRICS)

HLO = """
HloModule jit_f

%region_1.0 (a: f32[], b: f32[]) -> f32[] {
  ROOT %add = f32[] add(%a, %b)
}

%while_body (arg: (s32[], f32[16,128])) -> (s32[], f32[16,128]) {
  %ag = f32[16,128]{1,0} all-gather(%x), channel_id=3, dimensions={1}
  ROOT %t = (s32[], f32[16,128]) tuple(%i, %ag)
}

ENTRY %main (p0: f32[8,128], p1: bf16[4,256]) -> f32[8,128] {
  %ar = f32[8,128]{1,0} all-reduce(%p0), channel_id=1, to_apply=%region_1.0
  %a2a = bf16[4,256]{1,0} all-to-all(%p1), channel_id=2
  %rs = f32[2,128]{1,0} reduce-scatter(%ar), channel_id=4
  %cp = f32[8,128]{1,0} collective-permute(%rs), channel_id=5
  ROOT %out = f32[8,128]{1,0} add(%ar, %cp)
}
"""


def test_collective_parser_counts_and_bytes():
    res = collective_bytes_from_hlo(HLO)
    assert res["count"] == 5
    by = res["by_op"]
    assert by["all-reduce"] == pytest.approx(2 * 8 * 128 * 4)   # 2x operand
    assert by["all-to-all"] == pytest.approx(4 * 256 * 2)       # bf16
    assert by["reduce-scatter"] == pytest.approx(2 * 128 * 4)
    assert by["collective-permute"] == pytest.approx(8 * 128 * 4)
    assert by["all-gather"] == pytest.approx(16 * 128 * 4)      # result bytes
    assert res["total"] == pytest.approx(sum(by.values()))


def test_collective_parser_attributes_computations():
    res = collective_bytes_from_hlo(HLO)
    comps = res["by_computation"]
    # the while-body all-gather is attributed separately from ENTRY
    assert any("while_body" in k for k in comps)
    assert sum(v for k, v in comps.items()) == pytest.approx(res["total"])


def test_fit_quad_exact_recovery():
    # cost = 3*S + 0.5*S^2 for every metric
    f = lambda S: {m: 3 * S + 0.5 * S * S for m in METRICS}
    fit = _fit_quad(f(128), 128, f(256), 256)
    got = _eval_quad(fit, 4096)
    for m in METRICS:
        assert got[m] == pytest.approx(3 * 4096 + 0.5 * 4096 ** 2, rel=1e-9)


def test_fit_linear_exact_recovery():
    f = lambda S: {m: 7.0 + 2.5 * S for m in METRICS}
    fit = _fit_linear(f(64), 64, f(128), 128)
    got = _eval_linear(fit, 1024)
    for m in METRICS:
        assert got[m] == pytest.approx(7.0 + 2.5 * 1024, rel=1e-9)


def test_fit_never_negative():
    # noisy points that would extrapolate negative are clamped at 0
    lo = {m: 100.0 for m in METRICS}
    hi = {m: 10.0 for m in METRICS}          # decreasing -> negative slope
    fit = _fit_linear(lo, 64, hi, 128)
    got = _eval_linear(fit, 4096)
    for m in METRICS:
        assert got[m] >= 0.0


def test_roofline_row_terms():
    from benchmarks.bench_roofline import roofline_row
    rec = {
        "arch": "qwen3-4b", "shape": "train_4k", "mesh": "16x16",
        "kind": "train", "moe_impl": "alltoall", "variant": "final",
        "probe": {"totals": {"flops": 197e12, "bytes": 819e9,
                             "coll": 50e9}},
        "memory": {"argument_bytes": 8e9, "temp_bytes": 4e9,
                   "output_bytes": 2e9},
    }
    row = roofline_row(rec)
    assert row["t_compute_s"] == pytest.approx(1.0)
    assert row["t_memory_s"] == pytest.approx(1.0)
    assert row["t_collective_s"] == pytest.approx(1.0)
    assert row["hbm_frac"] == pytest.approx(14 / 16)
    assert row["fits"]
    assert row["useful_ratio"] > 0


def test_peaks_table_keyed_by_device_kind():
    from repro.roofline.peaks import peaks
    v5e = peaks("TPU v5 lite")
    assert v5e["flops_bf16"] == 197e12 and v5e["hbm_bw"] == 819e9
    assert v5e["hbm_bytes"] == 16e9
    with pytest.raises(ValueError, match="no published peak rates"):
        peaks("cpu")
