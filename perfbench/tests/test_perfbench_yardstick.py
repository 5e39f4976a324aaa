"""The yardstick on the CPU: trace reduction, FLOPs and bytes, peaks,
traffic generation and the plain reference."""
from __future__ import annotations

import numpy as np
import pytest

import perfbench_tiny  # noqa: F401  (puts perfbench/ and src/ on the path)
from bench import flops, peaks, trace, traffic, weights  # noqa: E402

QWEN = {"hidden_size": 2560, "num_attention_heads": 32,
        "num_key_value_heads": 8, "head_dim": 128, "intermediate_size": 9728,
        "vocab_size": 151936, "num_hidden_layers": 36,
        "stage_ends": [12, 24, 36], "causal": True, "torch_dtype": "bfloat16"}


# -- trace reduction --------------------------------------------------------

def test_merge_and_busy_clip_to_window():
    ops = [("a", 0, 10), ("b", 5, 20), ("c", 30, 40), ("d", 90, 120)]
    assert trace.merge([(s, e) for _, s, e in ops], 0, 100) == \
        [(0, 20), (30, 40), (90, 100)]
    assert trace.busy_ns(ops, 10, 100) == 10 + 10 + 10
    assert trace.gaps(ops, 0, 100) == [(20, 30), (40, 90)]


def test_flatten_gives_innermost_span():
    spans = [("outer", 0, 100), ("inner", 10, 20), ("inner2", 50, 60)]
    assert trace.flatten(spans) == [("outer", 0, 10), ("inner", 10, 20),
                                    ("outer", 20, 50), ("inner2", 50, 60),
                                    ("outer", 60, 100)]


def test_attribute_gaps_to_host_spans():
    spans = [("perfbench.scheduler", 0, 30), ("perfbench.commit", 40, 45)]
    idle = [(20, 50), (60, 70)]
    got = trace.attribute_gaps(idle, spans)
    assert got["perfbench.scheduler"] == pytest.approx(10e-9)
    assert got["perfbench.commit"] == pytest.approx(5e-9)
    assert got[trace.NO_SPAN] == pytest.approx(25e-9)


def test_summarize_a_synthetic_trace():
    tr = {"device": {"/device:TPU:0": [
              ("%fusion.1 = bf16[8,256,9728]{2,1,0} fusion(x)", 1000, 5000),
              ("%f.1 = f32[8,4]{1,0} custom-call(h, s, w)", 6000, 7000),
              ("%while.2 = (s32[]) while(t)", 1000, 5000)]},
          "host": [("perfbench.window", 0, 10000),
                   ("perfbench.dispatch", 0, 1000),
                   ("perfbench.wait_device", 5000, 6000)]}
    s = trace.summarize(tr)
    assert s["window_s"] == pytest.approx(1e-5)
    assert s["busy_s"] == pytest.approx(5e-6)
    gaps = dict(map(tuple, s["breakdown"]["idle_gaps"]))
    assert gaps["perfbench.dispatch"] == pytest.approx(1e-6)
    assert gaps["perfbench.wait_device"] == pytest.approx(1e-6)
    assert gaps[trace.NO_SPAN] == pytest.approx(3e-6)
    labels = [n for n, _ in s["breakdown"]["device_ops"]]
    assert labels[0] == "fusion bf16[8,256,9728]"
    assert not any(n.startswith("while") for n in labels)


def test_op_label():
    assert trace.op_label("%pad.0 = bf16[2560,152064]{1,0:T(8,128)} pad(x)") \
        == "pad bf16[2560,152064]"
    assert trace.op_label("%copy-start.3 = (f32[64]{0}, u32[]) copy-start(c)") \
        == "copy-start (f32[64], u32[])"


def test_exit_ops_are_the_kernel_and_the_vocabulary_pad():
    import os
    from bench import cell
    is_exit = cell.load_module(
        os.path.join(cell.HERE, "metrics", "exit_roofline.py"), "er").is_exit_op
    k = "%f.1 = f32[8,4]{1,0:T(8,128)S(1)} custom-call(bf16[8,2560]{1,0} %x)"
    pad = "%pad.0 = bf16[2560,152064]{1,0:T(8,128)(2,1)} pad(bf16[2560,151936])"
    other = "%pad.3 = bf16[8,1024]{1,0} pad(bf16[8,1000]{1,0} %y)"
    assert is_exit(k, 2560, 151936) and is_exit(pad, 2560, 151936)
    assert not is_exit(other, 2560, 151936)
    assert not is_exit(pad, 2560, 152064)


# -- FLOPs, bytes and peaks -------------------------------------------------

def test_qwen3_4b_stage_flops_on_known_shapes():
    per_layer_token = 2 * (2560 * 4096 + 2 * 2560 * 1024 + 4096 * 2560
                           + 3 * 2560 * 9728)
    assert flops.layer_matmul_flops(QWEN) == per_layer_token
    # one stage, one sequence of 256: 12 layers of projections, causal
    # attention over 256*257/2 pairs, one exit row
    attn = 4 * 32 * 128 * 256 * 257 / 2
    want = 12 * (per_layer_token * 256 + attn) + 2 * 2560 * 151936
    assert flops.stage_flops(QWEN, 0, 1, 256) == want
    assert flops.stage_flops(QWEN, 1, 8, 1024) == pytest.approx(
        8 * 12 * (per_layer_token * 1024 + 4 * 32 * 128 * 1024 * 1025 / 2)
        + 8 * 2 * 2560 * 151936)


def test_exit_bytes_at_vocab_151936():
    b = flops.exit_bytes(QWEN, 8)
    assert b == 2560 * 151936 * 2 + 8 * 2560 * 2 + 2560 * 2 + 8 * 16
    v5e = peaks.peaks("TPU v5 lite")
    # 0.78 GB at 819 GB/s: bandwidth-bound at 8 rows
    assert flops.exit_bound_s(QWEN, 8, v5e) == pytest.approx(b / 819e9)
    assert flops.exit_bound_s(QWEN, 4096, v5e) == pytest.approx(
        flops.exit_flops(QWEN, 4096) / 197e12)


def test_decode_token_flops_counts_one_whole_depth_pass():
    f = flops.decode_token_flops(QWEN, 4, 100)
    want = 4 * 36 * (flops.layer_matmul_flops(QWEN) + 4 * 32 * 128 * 100) \
        + 4 * 2 * 2560 * 151936
    assert f == want


def test_peaks_table_refuses_unknown_devices():
    assert peaks.peaks("TPU v5 lite")["hbm_bw"] == 819e9
    with pytest.raises(ValueError):
        peaks.peaks("cpu")


# -- traffic ------------------------------------------------------------------

TRAFFIC = {"arrivals": {"kind": "poisson", "rate": 50.0},
           "prompt_len": {"kind": "lognormal", "median": 256, "sigma": 0.8,
                          "min": 32, "max": 1024},
           "len_buckets": [256, 1024],
           "deadline": {"lo_ms": 100.0, "hi_ms": 300.0}}


def test_every_seed_offers_the_same_work_in_another_order():
    a = traffic.make_requests(TRAFFIC, 10.0, 1, vocab=1000)
    b = traffic.make_requests(TRAFFIC, 10.0, 2 ** 31 + 7, vocab=1000)
    again = traffic.make_requests(TRAFFIC, 10.0, 1, vocab=1000)
    assert len(a) == len(b) == 500
    for key in ("seq_len", "rel"):
        assert sorted(r[key] for r in a) == sorted(r[key] for r in b)
        assert [r[key] for r in a] != [r[key] for r in b]
    gaps = [sorted(np.diff([r["offset"] for r in x], prepend=0.0))
            for x in (a, b)]
    assert np.allclose(gaps[0], gaps[1], rtol=1e-9)
    assert all((x["inputs"] == y["inputs"]).all() for x, y in zip(a, again))
    assert all(0 <= r["offset"] < 10.0 for r in a)
    for r in a:
        assert r["inputs"].shape == (r["bucket"],)
        assert (r["inputs"][:r["bucket"] - r["seq_len"]] == 0).all()
        assert (r["inputs"][r["bucket"] - r["seq_len"]:] > 0).all()


def test_flash_crowd_puts_the_crowd_in_its_span():
    spec = {"kind": "flash-crowd", "base_rate": 10.0, "spike_rate": 100.0,
            "spike_at": 0.4, "spike_len": 0.2}
    t = traffic.arrivals(spec, 10.0, traffic.seed_rng(3, "arrivals"))
    inside = ((t >= 4.0) & (t < 6.0)).sum()
    assert inside == 200 and len(t) == 280


# -- weights and the plain reference ------------------------------------------

def test_weights_match_the_program_layout_and_the_reference_matches_it():
    import jax
    import jax.numpy as jnp
    from bench import cell, program
    from repro.models import init_params, stage_forward
    # float32, so that the two agree to rounding
    m = dict(perfbench_tiny.TEXT, torch_dtype="float32")
    cfg = program.program_config(
        m, dict(perfbench_tiny.TEXT_OVERRIDES, dtype="float32"))
    weights.check_layout(m, init_params, cfg)
    params = weights.make_weights(m, 7)
    ref = cell.reference_module(m).Reference(m, params)
    x = np.random.default_rng(0).integers(1, 512, size=(2, 16)).astype(
        np.int32)
    h = {"tokens": jnp.asarray(x)}
    with jax.default_matmul_precision("highest"):
        for s, h_ref in ref.hidden_by_stage(x):
            h, logits, _conf = stage_forward(cfg, params, s, h)
            np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                                       rtol=2e-4, atol=2e-4)
            lg = ref.exit_logits(h_ref[:, -1], s)
            np.testing.assert_allclose(np.asarray(logits)[:, -1],
                                       np.asarray(lg), rtol=2e-4, atol=2e-4)


def test_layout_check_refuses_another_layout():
    from bench import program
    from repro.models import init_params
    m = dict(perfbench_tiny.TEXT)
    cfg = program.program_config(m, perfbench_tiny.TEXT_OVERRIDES)
    with pytest.raises(RuntimeError):
        weights.check_layout(dict(m, stage_ends=[2, 3, 4]), init_params, cfg)
