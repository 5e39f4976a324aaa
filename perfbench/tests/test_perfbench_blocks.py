"""The harness reads a configuration's block from its file: GQA or latent
attention (MLA), dense or routed-expert feed-forward.  The qwen3-4b
family's weights stay what they were; a tiny MLA + routed-expert block
passes the block and layout checks and serves tokens; the counts of work
hold at DeepSeek-V3's published widths."""
from __future__ import annotations

import copy
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import perfbench_tiny
from bench import decode, flops, program, weights

MLA_MOE = perfbench_tiny.MLA_MOE


# -- qwen3-4b family: the weights are bit for bit the earlier builder's ------

def _frozen_build(key, m: dict):
    """The weight builder as it was for the dense GQA block alone."""
    dt = jnp.dtype(m["torch_dtype"])
    d, V = m["hidden_size"], m["vocab_size"]
    H, KV, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    f = m["intermediate_size"]
    mixer = {"ln": (d,), "wq": (d, H * hd), "wk": (d, KV * hd),
             "wv": (d, KV * hd), "wo": (H * hd, d)}
    if m["qk_norm"]:
        mixer.update(q_norm=(hd,), k_norm=(hd,))
    shapes = {"mixer": mixer,
              "ffn": {"ln": (d,), "w_up": (d, f), "w_down": (f, d),
                      "w_gate": (d, f)}}

    def scale(name):
        if name in ("ln", "q_norm", "k_norm"):
            return 0.1
        if name in ("wo", "w_down"):
            return 0.02 / float(m["num_hidden_layers"]) ** 0.5
        return 0.02

    def draw(tag, shape, s):
        k = jax.random.fold_in(key, zlib.crc32(tag.encode()) & 0x7FFFFFFF)
        return jax.random.normal(k, shape, dt) * jnp.asarray(s, dt)

    ends = list(m["stage_ends"])
    stages = []
    for s, (a, b) in enumerate(zip([0] + ends[:-1], ends)):
        def group(n, tag):
            lead = (n,) if n else ()
            return {part: {name: draw(f"{tag}/{part}/{name}", lead + shp,
                                      scale(name))
                           for name, shp in leaves.items()}
                    for part, leaves in shapes.items()}
        if b - a >= 2:
            stages.append({"prefix": [], "scan": (group(b - a, f"s{s}"),),
                           "tail": []})
        else:
            stages.append({"prefix": [group(0, f"l{i}") for i in range(a, b)],
                           "tail": []})
    return {"embed": {"tok": draw("embed/tok", (V, d), 0.02)},
            "stages": stages,
            "exits": [{"ln": draw(f"exit{s}/ln", (d,), 0.1)}
                      for s in range(len(stages))],
            "exit_shared": {"w_out": draw("exit/w_out", (d, V), 0.02)}}


@pytest.mark.parametrize("seed,stage_ends", [
    (7, [1, 2, 4]), (2 ** 33 + 1, [2, 3, 4]), (5, [4])])
def test_qwen3_family_weights_equal_the_frozen_builder(seed, stage_ends):
    m = dict(perfbench_tiny.TEXT, stage_ends=stage_ends)
    ours = weights.make_weights(m, seed)
    frozen = jax.jit(lambda k: _frozen_build(k, m))(weights.seed_key(seed))
    a, ta = jax.tree.flatten(ours)
    b, tb = jax.tree.flatten(frozen)
    assert ta == tb
    for x, y in zip(a, b):
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# -- a tiny MLA + routed-expert block through the harness ---------------------

def _mla_moe_cfg(m=None):
    return program.program_config(dict(m or MLA_MOE),
                                  perfbench_tiny.mla_moe_overrides())


def test_a_tiny_mla_moe_block_serves_tokens_through_the_decode_cell():
    from repro.models import init_params
    m = dict(MLA_MOE)
    cfg = _mla_moe_cfg(m)
    assert cfg.attention == "mla" and cfg.moe.num_experts == 8
    assert cfg.num_layers == 5 and cfg.stage_boundaries() == (3, 4, 5)
    weights.check_layout(m, init_params, cfg)
    params = weights.make_weights(m, 2 ** 31 + 3)
    assert params["stages"][0]["prefix"][0]["ffn"]["w_up"].shape == (64, 128)
    scan = params["stages"][0]["scan"][0]["ffn"]
    assert scan["router"].dtype == jnp.float32
    assert scan["we_gate"].shape == (2, 8, 64, 32)
    assert "mtp" in params
    c = decode.DecodeCell(m, copy.deepcopy(perfbench_tiny.DECODE), cfg)
    c.setup(params)
    out = c.serve(float("inf"), 2 ** 31 + 3, trace=False, max_tokens=3)
    assert out["n_tokens"] == 3
    assert out["tokens"].shape == (c.batch, 3)
    assert ((out["tokens"] >= 0) & (out["tokens"] < 512)).all()
    assert set(out["depths"].tolist()) <= {1, 2, 3}


@pytest.mark.parametrize("stage_ends", [[1, 3, 5], [2, 5], [5]])
def test_the_layout_check_follows_the_program_grouping(stage_ends):
    """Leading dense layers in ``prefix``, a scan over two or more periods,
    a stage of fewer periods as single layers: as ``stage_layouts``."""
    from repro.models import init_params
    m = dict(MLA_MOE, stage_ends=stage_ends)
    weights.check_layout(m, init_params, _mla_moe_cfg(m))


@pytest.mark.parametrize("change", [
    {"drop": "n_routed_experts"}, {"drop": "num_experts_per_tok"},
    {"drop": "moe_intermediate_size"}, {"drop": "n_shared_experts"},
    {"drop": "first_k_dense_replace"}, {"drop": "qk_rope_head_dim"},
    {"set": {"kv_lora_rank": 32}}, {"set": {"n_routed_experts": 16}},
    {"set": {"attention": "gqa", "head_dim": 16}},
    {"set": {"num_nextn_predict_layers": 0}},
    {"set": {"moe_layer_freq": 2}},
    {"set": {"program_fields": {"capacity_factor": "moe.capacity_factor"},
             "capacity_factor": 2.0}},
    {"set": {"program_fields": {"n_group": "moe.n_group"}, "n_group": 8}},
    {"set": {"reduced": ["num_hidden_layers", "vocab_size", "hidden_size"],
             "hidden_size": 32}},
    {"set": {"reduced": ["num_hidden_layers", "vocab_size", "torch_dtype"],
             "torch_dtype": "float32"}},
    {"set": {"reduced": ["num_hidden_layers", "vocab_size", "kv_lora_rank"],
             "kv_lora_rank": 8}},
], ids=lambda c: "-".join(c.get("drop", "") and ["drop", c["drop"]]
                          or list(c["set"])))
def test_program_config_refuses_a_file_that_misstates_the_block(change):
    m = copy.deepcopy(MLA_MOE)
    m.pop(change.get("drop", ""), None)
    m.update(change.get("set", {}))
    why = "not a cut" if "reduced" in change.get("set", {}) else "disagree"
    with pytest.raises(RuntimeError, match=why):
        _mla_moe_cfg(m)


def test_program_fields_extend_the_table():
    m = dict(MLA_MOE, capacity_factor=1.25,
             program_fields={"capacity_factor": "moe.capacity_factor"})
    assert _mla_moe_cfg(m).moe.capacity_factor == 1.25


@pytest.mark.parametrize("drop", ["qk_norm", "head_dim"])
def test_program_config_refuses_a_dense_file_silent_on_its_block(drop):
    m = dict(perfbench_tiny.TEXT)
    m.pop(drop)
    with pytest.raises(RuntimeError, match=drop):
        program.program_config(m, perfbench_tiny.TEXT_OVERRIDES)


# -- counts of work at DeepSeek-V3's published widths ---------------------------

DSV3 = {"attention": "mla", "hidden_size": 7168, "intermediate_size": 18432,
        "num_attention_heads": 128, "num_hidden_layers": 61,
        "q_lora_rank": 1536, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "n_routed_experts": 256,
        "num_experts_per_tok": 8, "moe_intermediate_size": 2048,
        "n_shared_experts": 1, "first_k_dense_replace": 3,
        "moe_layer_freq": 1, "vocab_size": 129280, "causal": True,
        "torch_dtype": "bfloat16", "stage_ends": [21, 41, 61]}


def test_hand_counts_at_deepseek_v3_widths():
    assert flops.mixer_matmul_flops(DSV3) == 340_656_128
    assert flops.swiglu_flops(DSV3, 2048) == 88_080_384
    assert flops.router_flops(DSV3) == 3_670_016
    # a leading dense layer: the projections and an 18432-wide SwiGLU
    assert flops.layer_matmul_flops(DSV3, 2) == 340_656_128 + 792_723_456
    # an expert layer: router, one shared, 8 routed
    assert flops.layer_matmul_flops(DSV3, 3) == \
        340_656_128 + 3_670_016 + 9 * 88_080_384


def test_mla_attention_counts_prefill_expanded_and_decode_absorbed():
    # prefill over S=4: wkv_b for 4 positions, scores over 192 and values
    # over 128 on 10 causal pairs, per head
    assert flops.layer_attention_flops(DSV3, 4) == \
        2 * 4 * 512 * 128 * 256 + 2 * 128 * (192 + 128) * 10
    # decode: each head's q_nope through W_uk and output through W_uv,
    # then 1088 per context position (576 scores, 512 values)
    assert flops.decode_attention_flops(DSV3, 100) == \
        2 * 128 * 512 * 256 + 2 * 128 * 1088 * 100
    f = flops.decode_token_flops(DSV3, 2, 100)
    attn = flops.decode_attention_flops(DSV3, 100)
    want = 2 * (3 * (340_656_128 + 792_723_456 + attn)
                + 58 * (340_656_128 + 3_670_016 + 9 * 88_080_384 + attn)) \
        + 2 * 2 * 7168 * 129280
    assert f == want
    assert flops.stage_flops(DSV3, 0, 1, 8) == pytest.approx(
        3 * (1_133_379_584 * 8 + flops.layer_attention_flops(DSV3, 8))
        + 18 * ((340_656_128 + 796_393_472) * 8
                + flops.layer_attention_flops(DSV3, 8))
        + 2 * 7168 * 129280)
