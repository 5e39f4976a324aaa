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


# -- one chip's share of DeepSeek-V3's expert layers ---------------------------

#: DeepSeek-V3's published ``config.json``, cut to one chip as section 4 of
#: the model-configs guide cuts it: 1 dense and 4 expert layers, 8 of the
#: 256 routed experts held (``ep_size`` 32, one of 32 chips that share each
#: expert layer), an eighth of the vocabulary, no MTP block.  Every width,
#: the router's 256 outputs and its 8 experts a token are as published.
V3_CHIP = {
    "name": "deepseek-v3-chip", "registry": "deepseek-v3-671b",
    "attention": "mla", "attention_bias": False, "hidden_act": "silu",
    "hidden_size": 7168, "intermediate_size": 18432,
    "num_attention_heads": 128, "num_key_value_heads": 128,
    "q_lora_rank": 1536, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "n_routed_experts": 256,
    "num_experts_per_tok": 8, "moe_intermediate_size": 2048,
    "n_shared_experts": 1, "moe_layer_freq": 1, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "n_group": 8, "topk_group": 4,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "max_position_embeddings": 163840, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "tie_word_embeddings": False,
    "torch_dtype": "bfloat16", "causal": True, "modality": "text",
    "mandatory_stages": 1, "stage_ends": [1, 3, 5],
    "num_hidden_layers": 5, "first_k_dense_replace": 1,
    "num_nextn_predict_layers": 0, "vocab_size": 16160, "ep_size": 32,
    "reduced": ["num_hidden_layers", "first_k_dense_replace",
                "num_nextn_predict_layers", "vocab_size", "ep_size"],
}

ROUTING = ("scoring_func", "topk_method", "n_group", "topk_group",
           "routed_scaling_factor", "norm_topk_prob")

#: one expert layer's feed-forward at V3's widths: a SwiGLU of 2048 in
#: bfloat16, and the float32 router with its bias
V3_SWIGLU_BYTES = 3 * 7168 * 2048 * 2
V3_ROUTER_BYTES = 4 * (7168 * 256 + 256)


def _bytes(tree) -> int:
    return sum(x.size * jnp.dtype(x.dtype).itemsize
               for x in jax.tree.leaves(tree))


def _v3_without(*keys, **changes) -> dict:
    m = copy.deepcopy(V3_CHIP)
    for k in keys:
        m.pop(k)
    m["reduced"] = [k for k in m["reduced"] if k in m]
    m.update(changes)
    return m


@pytest.mark.parametrize("layers,stage_ends,want", [
    (5, [1, 3, 5], 6_327_533_568), (8, [1, 4, 8], 9_850_454_016)])
def test_v3_chip_layout_bytes_pinned_by_hand(layers, stage_ends, want):
    """Per layer MLA 187,114,496 bfloat16 parameters; then a dense SwiGLU
    of 18432 (396,368,896 with its norm) or the norm, a float32 router
    (7168 x 256), one shared and 8 held experts of 2048 (396,368,896 in
    bfloat16 and 7,340,032 bytes of router); embedding and exit projection
    16160 x 7168 each.  The pinned sum leaves out the exit norms and the
    router bias; each expert layer past 4 adds 1,174,306,816 bytes."""
    m = dict(V3_CHIP, num_hidden_layers=layers, stage_ends=stage_ends)
    shapes = jax.eval_shape(lambda k: weights._build(k, m),
                            weights.seed_key(0))
    scan = shapes["stages"][1]["scan"][0]["ffn"]
    assert scan["we_gate"].shape[1:] == (8, 7168, 2048)
    assert scan["router"].shape[1:] == (7168, 256)
    assert scan["router_bias"].shape[1:] == (256,)
    assert scan["router_bias"].dtype == jnp.float32
    assert shapes["stages"][0]["prefix"][0]["ffn"]["w_up"].shape == \
        (7168, 18432)
    assert "mtp" not in shapes
    exit_norms = 3 * 7168 * 2
    bias = (layers - 1) * 256 * 4
    assert _bytes(shapes) - exit_norms - bias == want
    mixer = 2 * 187_114_496
    assert want == 5 * mixer + 2 * 396_368_896 + 2 * 16160 * 7168 * 2 \
        + (layers - 1) * (2 * 396_368_896 + 7_340_032) \
        + (layers - 5) * mixer


def test_hand_counts_with_ep_size_32():
    """8 of 256 experts held: a token's 8 routed experts give this chip a
    quarter of one expert's SwiGLU; the router and the shared expert count
    whole."""
    assert weights.held_experts(V3_CHIP) == 8
    assert flops.routed_per_token(V3_CHIP) == 0.25
    assert flops.expert_layer_flops(V3_CHIP) == \
        3_670_016 + 88_080_384 + 22_020_096
    assert flops.layer_matmul_flops(V3_CHIP, 0) == 340_656_128 + 792_723_456
    assert flops.layer_matmul_flops(V3_CHIP, 1) == 340_656_128 + 113_770_496
    attn = flops.decode_attention_flops(V3_CHIP, 100)
    assert flops.decode_token_flops(V3_CHIP, 128, 100) == \
        128 * (340_656_128 + 792_723_456 + attn
               + 4 * (340_656_128 + 113_770_496 + attn)) \
        + 2 * 128 * 7168 * 16160
    # ep_size 1 holds every expert and counts as a file without it
    assert flops.expert_layer_flops(dict(V3_CHIP, ep_size=1)) == \
        flops.expert_layer_flops(DSV3) == 3_670_016 + 9 * 88_080_384


@pytest.mark.parametrize("rows,touched,want_touched", [
    (1, None, 0.25), (4, None, 8 * (1 - (31 / 32) ** 4)),
    (128, None, 8 * (1 - (31 / 32) ** 128)), (128, 3, 3), (128, 0, 0)])
def test_expert_layer_bytes_of_a_step(rows, touched, want_touched):
    """The router and the shared expert are read whole every step; of the
    8 held experts, those the step's rows touch: the expected count under
    uniform routing, or the count a reader observed."""
    got = flops.expert_layer_bytes(V3_CHIP, rows, touched)
    want = V3_ROUTER_BYTES + (1 + want_touched) * V3_SWIGLU_BYTES
    assert got == pytest.approx(want, rel=1e-12)
    assert flops.experts_touched(V3_CHIP, 10 ** 4) == pytest.approx(8)
    assert flops.experts_touched(DSV3, 1) == pytest.approx(8)


@pytest.mark.parametrize("seed", [3, 77, 2 ** 33 + 5])
def test_router_bias_as_drawn_changes_the_selection(seed):
    """At V3's router widths, over 64 seeded rows of unit variance, adding
    the drawn bias to the sigmoid scores changes the top-8 set of some
    rows and keeps it in others: a program that ignores the bias, or one
    that routes by the bias alone, computes other experts."""
    shapes = weights.ffn_shapes(V3_CHIP, True)
    leaves = weights._leaves(
        weights.seed_key(seed), V3_CHIP, jnp.bfloat16, "s1/ffn",
        {k: shapes[k] for k in ("router", "router_bias")})
    w = np.asarray(leaves["router"], np.float64)
    b = np.asarray(leaves["router_bias"], np.float64)
    assert leaves["router_bias"].dtype == jnp.float32 and np.all(b != 0)
    x = np.random.default_rng(seed).standard_normal((64, 7168))
    score = 1.0 / (1.0 + np.exp(-(x @ w)))

    def top8(s):
        return np.sort(np.argsort(-s, axis=-1)[:, :8], axis=-1)

    changed = np.mean(np.any(top8(score) != top8(score + b), axis=-1))
    assert 0.1 <= changed <= 0.9


def test_v3_chip_passes_the_block_check_without_the_keys_it_lacks():
    """The control for the refusals below: without ``ep_size`` and the
    routing keys, the cut file agrees with the registered DeepSeek-V3."""
    cfg = program.program_config(_v3_without("ep_size", *ROUTING))
    assert cfg.num_layers == 5 and cfg.vocab_size == 16160
    assert cfg.moe.first_dense_layers == 1 and not cfg.mtp
    assert cfg.stage_boundaries() == (1, 3, 5)


@pytest.mark.parametrize("m,named", [
    (_v3_without(*ROUTING, ep_size=24), "ep_size 24 does not divide"),
    (_v3_without(*ROUTING, ep_size=64), "ep_size 64 leaves 4 of 256"),
    (_v3_without(*ROUTING), "the program has no moe.ep_size"),
    (_v3_without(*ROUTING, reduced=["num_hidden_layers", "vocab_size",
                                    "first_k_dense_replace",
                                    "num_nextn_predict_layers"]),
     "the program has no moe.ep_size"),
    (_v3_without("ep_size", q_lora_rank=None), "q_lora_rank"),
] + [(_v3_without("ep_size", *[r for r in ROUTING if r != k]),
      f"the program has no moe.{k}") for k in ROUTING],
    ids=["ep_size-divides", "ep_size-floor", "ep_size-reduced",
         "ep_size-stated", "q_lora_rank-null"] + list(ROUTING))
def test_program_config_refuses_what_the_program_lacks(m, named):
    with pytest.raises(RuntimeError, match=named):
        program.program_config(m)


def test_program_config_names_every_key_the_program_lacks():
    with pytest.raises(RuntimeError) as e:
        program.program_config(copy.deepcopy(V3_CHIP))
    for key in ("ep_size",) + ROUTING:
        assert f"the program has no moe.{key}" in str(e.value)


def test_held_experts_refuses_a_share_under_the_floor_or_uneven():
    for ep, why in ((3, "does not divide"), (64, "under 8")):
        with pytest.raises(ValueError, match=why):
            weights.held_experts(dict(V3_CHIP, ep_size=ep))
    with pytest.raises(ValueError, match="q_lora_rank"):
        weights.mixer_shapes(dict(V3_CHIP, q_lora_rank=None))


def test_a_tiny_held_share_builds_its_experts_and_the_router_bias():
    """16 routed experts over 2 chips: 8 held, the router over all 16."""
    m = dict(MLA_MOE, n_routed_experts=16, ep_size=2,
             topk_method="noaux_tc",
             reduced=MLA_MOE["reduced"] + ["ep_size"])
    params = weights.make_weights(m, 2 ** 31 + 9)
    ffn = params["stages"][0]["scan"][0]["ffn"]
    assert ffn["we_gate"].shape == (2, 8, 64, 32)
    assert ffn["we_down"].shape == (2, 8, 32, 64)
    assert ffn["router"].shape == (2, 64, 16)
    assert ffn["router_bias"].shape == (2, 16)
    assert ffn["router_bias"].dtype == jnp.float32
    assert np.all(np.asarray(ffn["router_bias"]) != 0)
    assert "router_bias" not in params["stages"][0]["prefix"][0]["ffn"]
    assert "router_bias" not in weights.ffn_shapes(MLA_MOE, True)
