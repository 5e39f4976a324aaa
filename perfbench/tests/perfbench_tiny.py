"""A tiny configuration and traffic for running the harness on the CPU.

The sizes are the qwen3-4b family's block (bfloat16, like the chip cells)
at a width the CPU runs in seconds; the program's registered config is
replaced by the same sizes.  The limits here are this size's own: on the
CPU the program reads a ``conf_rel_err`` of 0.0011-0.0024 and a decode
``logit_gap`` of 0-0.0023 over seeds 1-3, the float8 control 0.019-0.027
and 0.011-0.016.
The harness runs as on the chip, except that it is handed the CPU devices
and the kernels run in interpret mode.  :data:`MLA_MOE` is a second block,
latent attention and routed experts, for the harness's block and layout
checks and the decode cell; it has no cell and no reference here.
"""
from __future__ import annotations

import copy
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(PERFBENCH)
for p in (PERFBENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TEXT = {
    "name": "tiny-text", "registry": "qwen3-4b",
    "reference": "dense_anytime_reference.py",
    "head_dim": 16, "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_hidden_layers": 4,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-6, "rope_theta": 1e6,
    "torch_dtype": "bfloat16", "vocab_size": 512, "qk_norm": True,
    "causal": True, "modality": "text", "stage_ends": [1, 2, 4],
    "mandatory_stages": 1,
}

TEXT_OVERRIDES = dict(name="tiny-text", num_layers=4, d_model=64,
                      num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
                      vocab_size=512, stage_ends=(1, 2, 4), dtype="bfloat16")

PREFILL = {
    "driver": "service_prefill",
    "arrivals": {"kind": "poisson", "rate": 20.0},
    "prompt_len": {"kind": "lognormal", "median": 12, "sigma": 0.8,
                   "min": 4, "max": 32},
    "len_buckets": [16, 32],
    "batch_buckets": [1, 2, 4],
    "deadline": {"lo_ms": 2000.0, "hi_ms": 4000.0},
    "pipeline_depth": 2,
    "policy": {"name": "rtdeepiot",
               "args": {"predictor": "exp", "prior_curve": [0.5, 0.7, 0.85]}},
    "wcet_runs": 2,
    "correct": {"sample": 16,
                "limits": {"logit_gap": 0.05, "conf_rel_err": 0.006}},
}

DECODE = {
    "driver": "token_decode", "batch": 2, "cache_slots": 16,
    "speculate": True,
    "policy": {"name": "conf-target", "args": {"target": 0.7}},
    "correct": {"limits": {"logit_gap": 0.006}},
}

#: a tiny latent-attention + routed-expert block: the registered
#: DeepSeek-V3 at CPU widths, its file keyed as the published config.json
#: is.  Depth and vocabulary are cut through ``reduced``, as a chip
#: configuration would cut them; the widths are the overrides below.
MLA_MOE = {
    "name": "tiny-mla-moe", "registry": "deepseek-v3-671b",
    "attention": "mla", "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "num_hidden_layers": 5, "q_lora_rank": 32, "kv_lora_rank": 16,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "n_routed_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "n_shared_experts": 1,
    "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "num_nextn_predict_layers": 1, "rms_norm_eps": 1e-6,
    "rope_theta": 10000, "torch_dtype": "bfloat16", "vocab_size": 512,
    "causal": True, "modality": "text", "stage_ends": [3, 4, 5],
    "mandatory_stages": 1, "reduced": ["num_hidden_layers", "vocab_size"],
}


def mla_moe_overrides() -> dict:
    """The tiny widths of :data:`MLA_MOE` as fields of the registered
    config (the depth, the vocabulary and the stage ends come from the
    file's ``reduced``)."""
    import dataclasses
    from repro.configs import get_config
    from repro.configs.base import MLAConfig
    m = MLA_MOE
    moe = get_config(m["registry"]).moe
    return dict(
        name="tiny-mla-moe", d_model=m["hidden_size"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], d_ff=m["intermediate_size"],
        mla=MLAConfig(**{k: m[k] for k in (
            "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim")}),
        moe=dataclasses.replace(
            moe, num_experts=m["n_routed_experts"],
            top_k=m["num_experts_per_tok"],
            d_ff_expert=m["moe_intermediate_size"],
            first_dense_layers=m["first_k_dense_replace"]))


CELLS = {
    "tiny.prefill": ("tiny-text", "tiny-prefill", PREFILL),
    "tiny.decode": ("tiny-text", "tiny-decode", DECODE),
}


#: the tiny cell that stands in for each path driver
TINY_FOR = {"service_prefill": "tiny.prefill", "token_decode": "tiny.decode"}

#: the prefill path's metrics, for a ``BENCHMARK.json`` that has no
#: prefill cell to name them
PREFILL_E2E = [
    {"name": n, "unit": u, "better": b, "bound": 0.1, "source": "host_clock"}
    for n, u, b in (("goodput", "req/s", "higher"),
                    ("latency_p95", "ms", "lower"),
                    ("depth_mean", "stages", "higher"))]
PREFILL_LAYER = [
    {"name": n, "unit": u, "better": "higher", "source": "device_trace",
     "layer": "tiny", "moves": mv}
    for n, u, mv in (("queue_wait_p50", "ms", "latency_p95"),
                     ("sched_ms", "ms", "latency_p95"),
                     ("batch_occupancy", "%", "goodput"),
                     ("host_ms_per_dispatch", "ms", "latency_p95"),
                     ("prefill_mfu", "%", "depth_mean"),
                     ("exit_roofline", "%", "depth_mean"),
                     ("idle_share.prefill", "%", "depth_mean"))]


def bench() -> dict:
    """``BENCHMARK.json`` with the tiny cells in place of its cells: each
    metric names the tiny cells of the drivers its own cells run on, and
    the prefill metrics it lacks are added for ``tiny.prefill``."""
    import json
    from bench import cell
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = copy.deepcopy(json.load(f))
    driver = {w["name"]: cell.traffic(w["traffic"])["driver"]
              for w in b["workloads"]}

    def retarget(m):
        if "workloads" not in m:
            return m
        return dict(m, workloads=sorted({TINY_FOR[driver[w]]
                                         for w in m["workloads"]}))
    b["workloads"] = [{"name": n, "config": c, "traffic": t, "chips": 1,
                       "why": "CPU test"} for n, (c, t, _tr) in CELLS.items()]
    named = {m["name"] for m in b["end_to_end"] + b["per_layer"]}
    b["end_to_end"] = [retarget(m) for m in b["end_to_end"]] + [
        dict(m, workloads=["tiny.prefill"]) for m in PREFILL_E2E
        if m["name"] not in named]
    b["per_layer"] = [retarget(m) for m in b["per_layer"]] + [
        dict(m, workloads=["tiny.prefill"]) for m in PREFILL_LAYER
        if m["name"] not in named]
    return b


def run(cell: str, *, seed: int = 123, seconds: float = 2.0,
        trace: bool = False, traffic: dict = None) -> dict:
    """One run of a tiny cell on the CPU, as ``perfbench/run.py`` makes it."""
    import jax
    import run as runner
    b = bench()
    w = next(x for x in b["workloads"] if x["name"] == cell)
    tr = copy.deepcopy(traffic or CELLS[cell][2])
    return runner.run_cell(w, dict(TEXT), tr, seed=seed, seconds=seconds,
                           trace=trace, bench=b, devs=jax.devices()[:1],
                           program_overrides=TEXT_OVERRIDES, interpret=True)
