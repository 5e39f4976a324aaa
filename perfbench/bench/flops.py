"""Operations and bytes the algorithm needs, from shapes alone.

``m`` is a configuration's sizes (the keys of its file under
``perfbench/configs``).  A multiply-add counts two operations.  Attention
counts the query-key and probability-value products over the pairs the
mask keeps: ``S * (S + 1) / 2`` for causal attention, ``S * S`` otherwise.
Padding rows of a batch bucket are not needed work and are never counted.

Each layer counts by its kind.  The mixer is GQA or, with ``attention``
``"mla"``, latent attention: its prefill expands keys and values from
the latent at every position, its decode takes the absorbed form (the
least work that computes the same equations).  The feed-forward is a
dense SwiGLU of ``intermediate_size`` or, from ``first_k_dense_replace``
on, an expert layer: the router over all routed experts, the shared
experts, and the SwiGLU of the routed experts this chip holds.  Of a
token's ``num_experts_per_tok`` routed experts a chip that holds ``held``
of the ``n_routed_experts`` computes ``num_experts_per_tok * held /
n_routed_experts``, the expected share under uniform routing; where the
file states no ``ep_size`` it holds them all.
"""
from __future__ import annotations

from bench.weights import held_experts, is_moe_layer


def itemsize(m: dict) -> int:
    return {"bfloat16": 2, "float32": 4, "float16": 2}[m["torch_dtype"]]


def _mla(m: dict) -> bool:
    return m.get("attention", "gqa") == "mla"


def mixer_matmul_flops(m: dict) -> float:
    """The mixer's projections per token (MLA: ``wq_a``, ``wq_b``,
    ``wkv_a``, ``wo``; its ``wkv_b`` is attention's, below)."""
    d, H = m["hidden_size"], m["num_attention_heads"]
    if _mla(m):
        q, kv = m["q_lora_rank"], m["kv_lora_rank"]
        nope, rope, v = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                         m["v_head_dim"])
        return 2.0 * (d * q + q * H * (nope + rope) + d * (kv + rope)
                      + H * v * d)
    KV, hd = m["num_key_value_heads"], m["head_dim"]
    return 2.0 * (d * H * hd + 2 * d * KV * hd + H * hd * d)


def swiglu_flops(m: dict, width: int) -> float:
    return 2.0 * 3 * m["hidden_size"] * width


def router_flops(m: dict) -> float:
    return 2.0 * m["hidden_size"] * m["n_routed_experts"]


def routed_per_token(m: dict) -> float:
    """The routed experts of one token that this chip computes."""
    return m["num_experts_per_tok"] * held_experts(m) / m["n_routed_experts"]


def expert_layer_flops(m: dict) -> float:
    """An expert layer's feed-forward per token: the router, the shared
    experts, and the held routed experts' SwiGLU."""
    fe = m["moe_intermediate_size"]
    return router_flops(m) + swiglu_flops(m, fe * m["n_shared_experts"]) \
        + routed_per_token(m) * swiglu_flops(m, fe)


def experts_touched(m: dict, rows: int) -> float:
    """The expected number of held experts that ``rows`` tokens route to,
    each token to ``num_experts_per_tok`` distinct experts of the
    ``n_routed_experts`` at uniform: ``held * (1 - (1 - k/E) ** rows)``."""
    k, E = m["num_experts_per_tok"], m["n_routed_experts"]
    return held_experts(m) * (1.0 - (1.0 - k / E) ** rows)


def expert_layer_bytes(m: dict, rows: int,
                       touched: float | None = None) -> float:
    """Weight bytes one expert layer's feed-forward reads in a step of
    ``rows`` tokens: the float32 router (with its ``router_bias``), the
    shared experts, and the SwiGLU of each held expert the step touches:
    ``touched`` where a reader observed it, else :func:`experts_touched`."""
    d, E = m["hidden_size"], m["n_routed_experts"]
    if touched is None:
        touched = experts_touched(m, rows)
    router = d * E + (E if m.get("topk_method") == "noaux_tc" else 0)
    swiglu = 3 * d * m["moe_intermediate_size"] * itemsize(m)
    return 4 * router + (m["n_shared_experts"] + touched) * swiglu


def ffn_flops(m: dict, layer: int = 0) -> float:
    if is_moe_layer(m, layer):
        return expert_layer_flops(m)
    return swiglu_flops(m, m["intermediate_size"])


def layer_matmul_flops(m: dict, layer: int = 0) -> float:
    """Projection operations of one layer per token."""
    return mixer_matmul_flops(m) + ffn_flops(m, layer)


def attention_pairs(m: dict, S: int) -> float:
    return S * (S + 1) / 2.0 if m["causal"] else float(S * S)


def layer_attention_flops(m: dict, S: int) -> float:
    """Attention of one layer over one sequence of S: the score and value
    products (MLA: scores over ``qk_nope + qk_rope``, values over
    ``v_head_dim``, after ``wkv_b`` expands each position's latent)."""
    H = m["num_attention_heads"]
    if _mla(m):
        nope, rope, v = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                         m["v_head_dim"])
        expand = 2.0 * S * m["kv_lora_rank"] * H * (nope + v)
        return expand + 2.0 * H * (nope + rope + v) * attention_pairs(m, S)
    return 4.0 * H * m["head_dim"] * attention_pairs(m, S)


def decode_attention_flops(m: dict, context: int) -> float:
    """Attention of one layer for one new token over ``context`` positions
    (MLA absorbed: each head's ``q_nope`` through ``wkv_b``'s key half and
    its output through the value half; scores over ``kv_lora + qk_rope``,
    values over ``kv_lora`` at each position)."""
    H = m["num_attention_heads"]
    if _mla(m):
        kv, nope, rope, v = (m["kv_lora_rank"], m["qk_nope_head_dim"],
                             m["qk_rope_head_dim"], m["v_head_dim"])
        return 2.0 * H * kv * (nope + v) + 2.0 * H * (2 * kv + rope) * context
    return 4.0 * H * m["head_dim"] * context


def exit_flops(m: dict, rows: int) -> float:
    return 2.0 * rows * m["hidden_size"] * m["vocab_size"]


def exit_bytes(m: dict, rows: int) -> float:
    """Bytes the fused exit must move: the vocabulary projection once, the
    rows and the norm scale in, four float32 numbers per row out."""
    d, V, b = m["hidden_size"], m["vocab_size"], itemsize(m)
    return d * V * b + rows * d * b + d * b + rows * 4 * 4


def exit_bound_s(m: dict, rows: int, peak: dict) -> float:
    """Least time of one fused exit call on a chip with ``peak`` rates."""
    return max(exit_flops(m, rows) / peak["flops_bf16"],
               exit_bytes(m, rows) / peak["hbm_bw"])


def stage_range(m: dict, stage: int) -> range:
    ends = [0] + list(m["stage_ends"])
    return range(ends[stage], ends[stage + 1])


def stage_flops(m: dict, stage: int, rows: int, S: int) -> float:
    """One stage over ``rows`` sequences of length S, exit included."""
    attn = layer_attention_flops(m, S)
    per_seq = sum(layer_matmul_flops(m, i) * S + attn
                  for i in stage_range(m, stage))
    return rows * per_seq + exit_flops(m, rows)


def decode_token_flops(m: dict, rows: int, context: int) -> float:
    """One whole-depth decode step: every layer for one new token that
    attends to ``context`` positions, and the last exit."""
    attn = decode_attention_flops(m, context)
    per_row = sum(layer_matmul_flops(m, i) + attn
                  for i in range(m["num_hidden_layers"]))
    return rows * per_row + exit_flops(m, rows)
