"""The system under test, reached through the program's public entry points.

Everything the benchmark takes from the program is imported here: the
registered model configuration, ``Service``/``ServeSpec`` and the
executors, policies and sources they resolve.  The configuration file's
sizes are checked against the registered configuration before anything
runs, so the benchmark never serves a model other than the one its file
states.

The check is a table: each configuration-file key (the published
``config.json`` name) maps to an attribute of the program's
``ModelConfig``, dotted where it is nested (``mla.kv_lora_rank``).  Which
rows apply follows from the block: the GQA or the MLA sizes by the file's
``attention`` (``"gqa"`` where absent), the expert sizes where the
program's config has experts or the file states them.  A file extends
the table with ``"program_fields": {file key: dotted attribute}``.

Rows whose value where the file is silent is ``None`` are checked only
where a file states them: the experts a chip holds (``ep_size``, each of
that many chips holding ``n_routed_experts / ep_size``) and the router's
keys (``scoring_func``, ``topk_method``, ``n_group``, ``topk_group``,
``routed_scaling_factor``, ``norm_topk_prob``).  A file that states one
is refused while the program has no such attribute.
"""
from __future__ import annotations

import dataclasses
import os
import sys

from bench.weights import held_experts

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "src")

#: stands for "the file must state this key"
REQUIRED = object()

#: file key -> (the program's attribute, the value where the file is silent)
FIELDS = {
    "num_hidden_layers": ("num_layers", REQUIRED),
    "hidden_size": ("d_model", REQUIRED),
    "num_attention_heads": ("num_heads", REQUIRED),
    "intermediate_size": ("d_ff", REQUIRED),
    "vocab_size": ("vocab_size", REQUIRED),
    "rope_theta": ("rope_theta", REQUIRED),
    "rms_norm_eps": ("norm_eps", REQUIRED),
    "torch_dtype": ("dtype", REQUIRED),
    "mandatory_stages": ("mandatory_stages", REQUIRED),
    "attention": ("attention", "gqa"),
    "qk_norm": ("qk_norm", False),
    "causal": ("causal", True),
    "modality": ("modality", "text"),
    "num_nextn_predict_layers": ("mtp", 0),
}

GQA_FIELDS = {
    "num_key_value_heads": ("num_kv_heads", REQUIRED),
    "head_dim": ("resolved_head_dim", REQUIRED),
}

MLA_FIELDS = {k: ("mla." + k, REQUIRED) for k in (
    "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim")}

MOE_FIELDS = {
    "n_routed_experts": ("moe.num_experts", REQUIRED),
    "num_experts_per_tok": ("moe.top_k", REQUIRED),
    "moe_intermediate_size": ("moe.d_ff_expert", REQUIRED),
    "n_shared_experts": ("moe.num_shared_experts", REQUIRED),
    "first_k_dense_replace": ("moe.first_dense_layers", REQUIRED),
    "moe_layer_freq": ("moe.moe_every", 1),
    "ep_size": ("moe.ep_size", None),
}
MOE_FIELDS.update({k: ("moe." + k, None) for k in (
    "scoring_func", "topk_method", "n_group", "topk_group",
    "routed_scaling_factor", "norm_topk_prob")})

#: the keys a file may cut under ``reduced``: depth (with the MTP block
#: and the leading dense layers), the vocabulary and the routed experts
#: held (``ep_size``).  A width, a precision or a block kind listed there
#: is refused.
CUTS = ("num_hidden_layers", "first_k_dense_replace",
        "num_nextn_predict_layers", "vocab_size", "ep_size")

_MISSING = object()


def import_program():
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    import repro  # noqa: F401  (raises where the program is absent)


def table(m: dict, cfg) -> dict:
    """The rows of the check that apply to ``m`` and the program's ``cfg``."""
    rows = dict(FIELDS)
    if m.get("attention", "gqa") == "mla":
        rows.update(MLA_FIELDS)
        rows["num_key_value_heads"] = ("num_kv_heads", None)
    else:
        rows.update(GQA_FIELDS)
    if cfg.moe is not None or any(k in m for k in MOE_FIELDS):
        rows.update(MOE_FIELDS)
    rows.update({k: (a, REQUIRED)
                 for k, a in m.get("program_fields", {}).items()})
    return rows


def _get(obj, path: str):
    for part in path.split("."):
        obj = getattr(obj, part, _MISSING)
        if obj is _MISSING or obj is None:
            return _MISSING
    return obj


def _has(obj, path: str) -> bool:
    for part in path.split("."):
        if not hasattr(obj, part):
            return False
        obj = getattr(obj, part)
    return True


def _set(obj, path: str, value):
    head, _, rest = path.partition(".")
    if rest:
        value = _set(getattr(obj, head), rest, value)
    return dataclasses.replace(obj, **{head: value})


def program_config(m: dict, overrides: dict | None = None):
    """The registered ``ModelConfig`` named by ``m["registry"]``, checked
    row by row against ``m``: the program serves exactly the sizes and
    the block the file states, or nothing runs.

    Before the check, the file's own value replaces the registered one
    for each key of :data:`CUTS` that it lists under ``reduced``; where
    the depth is cut, the file's ``stage_ends`` too.  Any other key of
    the table listed there is refused.  ``overrides``
    (the CPU tests' tiny sizes) replace fields of the registered config
    first."""
    import_program()
    from repro.configs import get_config
    cfg = get_config(m["registry"])
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    rows = table(m, cfg)
    bad = []
    for key in [k for k in m.get("reduced", []) if k in rows]:
        if key not in CUTS:
            bad.append((key, m.get(key), "not a cut of depth, vocabulary "
                        "or experts held"))
            continue
        if not _has(cfg, rows[key][0]):
            continue            # refused below: the program has no such field
        try:
            cfg = _set(cfg, rows[key][0], m[key])
        except (AttributeError, KeyError, TypeError) as e:
            bad.append((key, m.get(key), f"not applied: {e!r}"))
    if "num_hidden_layers" in m.get("reduced", []):
        cfg = dataclasses.replace(cfg, stage_ends=tuple(m["stage_ends"]))
    if "ep_size" in m and "n_routed_experts" in m:
        try:
            held_experts(m)
        except ValueError as e:
            bad.append(("ep_size", m["ep_size"], str(e)))
    for key, (attr, default) in rows.items():
        want = m.get(key, default)
        if want is REQUIRED:
            bad.append((key, "not stated", _get(cfg, attr)))
        elif want is None:
            if key in m and default is REQUIRED:
                bad.append((key, None, "stated null; the harness builds "
                            "this part only at a stated size"))
        elif not _has(cfg, attr):
            bad.append((key, want, f"the program has no {attr}"))
        elif _get(cfg, attr) != want:
            bad.append((key, want, _get(cfg, attr)))
    if tuple(cfg.stage_boundaries()) != tuple(m["stage_ends"]):
        bad.append(("stage_ends", m["stage_ends"], cfg.stage_boundaries()))
    if cfg.ffn_type != "swiglu" or set(cfg.period) != {"attn"} \
            or cfg.sliding_window is not None \
            or (cfg.moe is not None and cfg.moe.moe_offset != 0):
        bad.append(("block", "swiglu, full attention, experts from "
                    "first_k_dense_replace on", cfg))
    if bad:
        raise RuntimeError(f"configuration file and program disagree: {bad}")
    return cfg
