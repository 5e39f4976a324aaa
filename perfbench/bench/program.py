"""The system under test, reached through the program's public entry points.

Everything the benchmark takes from the program is imported here: the
registered model configuration, ``Service``/``ServeSpec`` and the
executors, policies and sources they resolve.  The configuration file's
sizes are checked against the registered configuration before anything
runs, so the benchmark never serves a model other than the one its file
states.
"""
from __future__ import annotations

import dataclasses
import os
import sys

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "src")

#: configuration-file key -> the program's ``ModelConfig`` field
FIELDS = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim", "intermediate_size": "d_ff",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "torch_dtype": "dtype", "qk_norm": "qk_norm",
    "causal": "causal", "modality": "modality",
    "mandatory_stages": "mandatory_stages",
}


def import_program():
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    import repro  # noqa: F401  (raises where the program is absent)


def program_config(m: dict, overrides: dict | None = None):
    """The registered ``ModelConfig`` named by ``m["registry"]`` (with
    ``overrides``, the CPU tests' tiny sizes, replacing its fields),
    checked key by key against ``m``: the program serves exactly the
    sizes the file states, or nothing runs."""
    import_program()
    from repro.configs import get_config
    cfg = get_config(m["registry"])
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    bad = []
    for key, field in FIELDS.items():
        if key in m and getattr(cfg, field) != m[key]:
            bad.append((key, m[key], getattr(cfg, field)))
    if tuple(cfg.stage_boundaries()) != tuple(m["stage_ends"]):
        bad.append(("stage_ends", m["stage_ends"], cfg.stage_boundaries()))
    if cfg.ffn_type != "swiglu" or cfg.attention != "gqa" \
            or set(cfg.period) != {"attn"} or cfg.moe is not None:
        bad.append(("block", "dense swiglu gqa", cfg))
    if bad:
        raise RuntimeError(f"configuration file and program disagree: {bad}")
    return cfg

