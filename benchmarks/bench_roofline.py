"""Roofline table — derives the three terms per (arch × shape × mesh) from
the dry-run artifacts (assignment §ROOFLINE ANALYSIS).

  compute    = probe_FLOPs_per_chip / 197 TFLOP/s          [seconds]
  memory     = probe_bytes_per_chip / 819 GB/s             [seconds]
  collective = probe_coll_bytes_per_chip / 50 GB/s ICI     [seconds]
               (collectives crossing the pod axis use 25 GB/s DCN — the
               multi-pod table notes the dominant-axis assumption)

cost_analysis() is per-device after SPMD partitioning (verified by
calibration), so probe totals are already per-chip.  MODEL_FLOPS uses
6·N·D (train) / 2·N·D (inference) with N_active for MoE; the ratio
MODEL_FLOPS / (HLO_FLOPs × chips) flags remat/redundancy waste.
"""
from __future__ import annotations

import glob
import json
import os

from repro.roofline.peaks import peaks

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts", "dryrun")

# the dry-run compiles for the production TPU v5e pod
CHIP = peaks("TPU v5 lite")


def model_flops(arch: str, shape_kind: str, tokens: int) -> float:
    from repro.configs import get_config
    from repro.models import count_params_analytic
    cfg = get_config(arch)
    n = count_params_analytic(cfg, active_only=cfg.moe is not None)
    per_tok = 6 * n if shape_kind == "train" else 2 * n
    return per_tok * tokens


def tokens_of(shape_name: str) -> int:
    from repro.configs import get_shape
    s = get_shape(shape_name)
    return s.global_batch * (1 if s.kind == "decode" else s.seq_len)


def load_records(pattern="*.json"):
    recs = []
    for path in sorted(glob.glob(os.path.join(ART, pattern))):
        with open(path) as f:
            rec = json.load(f)
        base = os.path.basename(path)[:-5]
        parts = base.split("__")
        rec["variant"] = parts[4] if len(parts) > 4 else (
            parts[3] if len(parts) > 3 and parts[3] not in
            ("alltoall", "gather") else "baseline")
        recs.append(rec)
    return recs


def roofline_row(rec):
    chips = 512 if rec["mesh"] == "2x16x16" else 256
    probe = rec.get("probe", {}).get("totals")
    if probe is None:
        return None
    t_comp = probe["flops"] / CHIP["flops_bf16"]
    t_mem = probe["bytes"] / CHIP["hbm_bw"]
    t_coll = probe["coll"] / CHIP["ici_bw_per_link"]
    terms = {"compute": t_comp, "memory": t_mem, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    mf = model_flops(rec["arch"], rec["kind"], tokens_of(rec["shape"]))
    hlo_total = probe["flops"] * chips
    mem = rec["memory"]
    # (t_mem_lb computed below from the same buffer stats)
    hbm_used = (mem["argument_bytes"] + mem["temp_bytes"]
                + mem["output_bytes"]) / CHIP["hbm_bytes"]
    # memory-traffic LOWER bound from real buffer sizes (args read once,
    # outputs written once, temps written+read) — brackets the op-level
    # upper bound in t_memory_s
    t_mem_lb = (mem["argument_bytes"] + mem["output_bytes"]
                + 2 * mem["temp_bytes"]) / CHIP["hbm_bw"]
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "variant": rec.get("variant", "baseline"),
        "moe_impl": rec.get("moe_impl", "gather"),
        "t_compute_s": t_comp, "t_memory_s": t_mem,
        "t_memory_lb_s": t_mem_lb, "t_collective_s": t_coll,
        "dominant": dominant,
        "model_flops": mf,
        "useful_ratio": mf / hlo_total if hlo_total else 0.0,
        "hbm_frac": hbm_used,
        "fits": hbm_used <= 1.0,
        "swa_variant": rec.get("swa_variant", False),
        "n_micro": rec.get("n_micro"),
    }


def main(pattern="*.json"):
    rows = [r for r in (roofline_row(rec) for rec in load_records(pattern))
            if r is not None]
    hdr = ("arch,shape,mesh,variant,compute_s,memory_s,collective_s,"
           "dominant,useful_ratio,hbm_frac,fits")
    print(hdr)
    for r in sorted(rows, key=lambda r: (r["arch"], r["shape"], r["mesh"],
                                         r["variant"])):
        print(f"{r['arch']},{r['shape']},{r['mesh']},{r['variant']},"
              f"{r['t_compute_s']:.3e},{r['t_memory_s']:.3e},"
              f"{r['t_collective_s']:.3e},{r['dominant']},"
              f"{r['useful_ratio']:.3f},{r['hbm_frac']:.2f},"
              f"{int(r['fits'])}")
    out = os.path.join(os.path.dirname(ART), "roofline_table.json")
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    return rows


if __name__ == "__main__":
    main()
