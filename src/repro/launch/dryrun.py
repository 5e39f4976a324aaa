import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × input-shape × mesh).

The two lines above MUST stay first — jax locks the device count at first
init, and the production meshes need 512 host placeholder devices.  Do not
import this module from tests; run it as a subprocess:

  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b \
      --shape train_4k --mesh single --out artifacts/dryrun

For every combination it jits the appropriate step (train_step for train_4k,
prefill_step for prefill_32k, serve_step for decode shapes) with explicit
in/out shardings, runs .lower().compile(), and records memory_analysis() +
cost_analysis() + the optimized-HLO collective byte census to a JSON
artifact consumed by benchmarks/roofline.
"""

import argparse
import json
import time
import traceback

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P


def _sharding_tree(avals, shardings):
    return jax.tree.map(lambda s: s, shardings)


def run_combo(arch: str, shape_name: str, multi_pod: bool, *,
              moe_impl: str = "gather", attn_impl: str = "grouped",
              seq_parallel: bool = False, collect_hlo: bool = True,
              probes: bool = True, q_chunk: int = 1024):
    from repro.configs import get_config, get_shape
    from repro.launch import steps as S
    from repro.launch.mesh import make_production_mesh
    from repro.launch.shardings import (batch_shardings, cache_shardings,
                                        decode_weight_layout,
                                        expert_templates_for, opt_shardings,
                                        param_shardings)
    from repro.roofline.collectives import collective_bytes_from_hlo

    cfg = get_config(arch)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    ctx = S.make_ctx(mesh, shape, multi_pod=multi_pod, moe_impl=moe_impl,
                     seq_parallel=seq_parallel)
    if attn_impl != "grouped":
        import dataclasses as _dc
        ctx = _dc.replace(ctx, attn_impl=attn_impl)
    rec = {"arch": arch, "shape": shape_name, "attn_impl": attn_impl,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "moe_impl": moe_impl, "kind": shape.kind,
           "swa_variant": S.uses_swa_variant(cfg, shape)}
    t0 = time.time()

    params = S.abstract_params(cfg)
    etpl = expert_templates_for(cfg, mesh, ctx.dp, moe_impl)
    layout = decode_weight_layout(cfg, mesh) if shape.kind == "decode" \
        else "2d"
    rec["weight_layout"] = layout
    p_sh = param_shardings(mesh, params, etpl, layout=layout)
    specs = S.input_specs(cfg, shape)

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            n_micro = S.pick_microbatches(cfg, ctx, shape.global_batch,
                                          shape.seq_len)
            rec["n_micro"] = n_micro
            step, opt = S.make_train_step_fn(cfg, ctx, q_chunk=q_chunk,
                                             n_micro=n_micro)
            opt_state = S.abstract_opt_state(opt, params)
            o_sh = opt_shardings(mesh, opt_state, etpl)
            b_sh = {"inputs": batch_shardings(mesh, specs["inputs"], ctx.dp),
                    "labels": batch_shardings(mesh, {"l": specs["labels"]},
                                              ctx.dp)["l"]}
            fn = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                         out_shardings=(p_sh, o_sh, NamedSharding(mesh, P())),
                         donate_argnums=(0, 1))
            lowered = fn.lower(params, opt_state,
                               {"inputs": specs["inputs"],
                                "labels": specs["labels"]})
        elif shape.kind == "prefill":
            step = S.make_prefill_step_fn(cfg, ctx, q_chunk=q_chunk)
            b_sh = batch_shardings(mesh, specs["inputs"], ctx.dp)
            fn = jax.jit(step, in_shardings=(p_sh, b_sh))
            lowered = fn.lower(params, specs["inputs"])
        else:
            step = S.make_serve_step_fn(cfg, ctx)
            c_sh = cache_shardings(mesh, specs["cache"], ctx.dp,
                                   ctx.seq_axes)
            bdp = tuple(a for a in ctx.dp if a not in ctx.seq_axes) or None
            tok_sh = NamedSharding(mesh, P(bdp, *([None] * (specs["token"].ndim - 1))))
            pos_sh = NamedSharding(mesh, P(bdp))
            fn = jax.jit(step, in_shardings=(p_sh, c_sh, tok_sh, pos_sh),
                         donate_argnums=(1,))
            lowered = fn.lower(params, specs["cache"], specs["token"],
                               specs["cur_pos"])
        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)

        ma = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "code_bytes": int(ma.generated_code_size_in_bytes),
        }
        ca = compiled.cost_analysis() or {}
        rec["cost"] = {k: float(v) for k, v in ca.items()
                       if isinstance(v, (int, float)) and
                       k in ("flops", "bytes accessed")}
        if collect_hlo:
            txt = compiled.as_text()
            rec["collectives_fullhlo"] = collective_bytes_from_hlo(txt)
    if probes:
        from repro.roofline.probes import probe_combo
        rec["probe"] = probe_combo(cfg, shape, mesh, ctx, q_chunk=q_chunk)
    rec["wall_s"] = round(time.time() - t0, 2)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True,
                    help="architecture id or 'all'")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--moe-impl", default="gather",
                    choices=["gather", "alltoall"])
    ap.add_argument("--attn-impl", default="grouped",
                    choices=["grouped", "flat"])
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--no-probes", action="store_true")
    ap.add_argument("--q-chunk", type=int, default=1024)
    ap.add_argument("--tag", default="")
    args = ap.parse_args(argv)

    from repro.configs import SHAPES, all_arch_ids
    archs = list(all_arch_ids()) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                name = f"{arch}__{shape}__{'multi' if mp else 'single'}"
                if args.moe_impl != "gather":
                    name += f"__{args.moe_impl}"
                if args.tag:
                    name += f"__{args.tag}"
                path = os.path.join(args.out, name + ".json")
                print(f"=== {name}", flush=True)
                try:
                    rec = run_combo(arch, shape, mp, moe_impl=args.moe_impl,
                                    attn_impl=args.attn_impl,
                                    seq_parallel=args.seq_parallel,
                                    probes=not args.no_probes,
                                    q_chunk=args.q_chunk)
                    with open(path, "w") as f:
                        json.dump(rec, f, indent=1)
                    mem = rec["memory"]
                    per_dev = (mem["argument_bytes"] + mem["temp_bytes"] +
                               mem["output_bytes"]) / 512e9 if mp else \
                        (mem["argument_bytes"] + mem["temp_bytes"] +
                         mem["output_bytes"]) / 256e9
                    print(f"    ok lower={rec['lower_s']}s "
                          f"compile={rec['compile_s']}s "
                          f"args={mem['argument_bytes']/2**30:.1f}GiB "
                          f"temp={mem['temp_bytes']/2**30:.1f}GiB "
                          f"flops={rec['cost'].get('flops', 0):.3e}",
                          flush=True)
                except Exception as e:  # noqa: BLE001
                    failures += 1
                    print(f"    FAIL {type(e).__name__}: {e}", flush=True)
                    traceback.print_exc()
                    with open(path + ".fail", "w") as f:
                        f.write(traceback.format_exc())
    print(f"done, {failures} failures")
    return failures


if __name__ == "__main__":
    raise SystemExit(main())
