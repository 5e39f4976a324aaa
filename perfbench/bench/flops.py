"""Operations and bytes the algorithm needs, from shapes alone.

``m`` is a configuration's sizes (the keys of its file under
``perfbench/configs``).  A multiply-add counts two operations.  Attention
counts the query-key and probability-value products over the pairs the
mask keeps: ``S * (S + 1) / 2`` for causal attention, ``S * S`` otherwise.
Padding rows of a batch bucket are not needed work and are never counted.
"""
from __future__ import annotations


def itemsize(m: dict) -> int:
    return {"bfloat16": 2, "float32": 4, "float16": 2}[m["torch_dtype"]]


def layer_matmul_flops(m: dict) -> float:
    """Projection operations of one layer per token."""
    d, H, KV, hd = (m["hidden_size"], m["num_attention_heads"],
                    m["num_key_value_heads"], m["head_dim"])
    f = m["intermediate_size"]
    return 2.0 * (d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * f)


def attention_pairs(m: dict, S: int) -> float:
    return S * (S + 1) / 2.0 if m["causal"] else float(S * S)


def layer_attention_flops(m: dict, S: int) -> float:
    """Score and value products of one layer over one sequence of S."""
    return 4.0 * m["num_attention_heads"] * m["head_dim"] \
        * attention_pairs(m, S)


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


def stage_layers(m: dict, stage: int) -> int:
    ends = [0] + list(m["stage_ends"])
    return ends[stage + 1] - ends[stage]


def stage_flops(m: dict, stage: int, rows: int, S: int) -> float:
    """One stage over ``rows`` sequences of length S, exit included."""
    per_seq = stage_layers(m, stage) * (layer_matmul_flops(m) * S
                                        + layer_attention_flops(m, S))
    return rows * per_seq + exit_flops(m, rows)


def decode_token_flops(m: dict, rows: int, context: int) -> float:
    """One whole-depth decode step: every layer for one new token that
    attends to ``context`` positions, and the last exit."""
    L = m["num_hidden_layers"]
    attn = 4.0 * m["num_attention_heads"] * m["head_dim"] * context
    return rows * (L * (layer_matmul_flops(m) + attn)) + exit_flops(m, rows)
