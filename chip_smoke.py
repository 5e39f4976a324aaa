#!/usr/bin/env python3
"""Chip smoke test: the anytime serving paths, once each, on a TPU.

    python chip_smoke.py             # one chip: classifier + qwen3-4b decode
    python chip_smoke.py --chips 4   # only the device-sharded meshes, 4 chips

Run it from the repository root (it imports ``src/``) as the only process
on the chip.  It fails — non-zero exit, no result line — unless JAX's first
device is a TPU, and every phase lets its exception propagate.  Params are
random from ``--seed`` and the data is the seeded synthetic
``DifficultyDataset``: nothing is read from disk.  Times printed here are
smoke timings (compile included where said), not benchmark numbers.  The
last line of stdout is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``.

Tolerances, and why:

* ``CONF_TOL`` — ``device-batched`` computes the exit head with XLA, whose
  default TPU precision rounds f32 matmul operands to bf16 (8 significant
  bits, relative error up to 2^-8); ``device-kernel`` computes it inside
  the Mosaic kernel.  For logits of order one the heads then differ by
  about 1e-2, and a max-softmax confidence moves by at most
  ``conf * max|dlogit|`` — so 2e-2 absolute.
* ``TIE_TOL`` — an argmax may flip only where the top-2 logits are closer
  than twice that logit error.
* ``SHARDED_H_RTOL`` — a tp-sharded stage sums its contractions in another
  order (partial sums, then an all-reduce) in f32: 1e-3 of the hidden
  scale leaves three orders of magnitude over f32 rounding.
* Ragged decode: the ragged batch must be bit-for-bit a same-shape batch
  of each request (no row reads another row's cache), and give each
  request its batch-1 prediction.  ``RAGGED_H_RTOL`` bounds the batch-1
  hidden state: batch shape changes XLA's rounding, qwen3-4b's residual
  stream is bf16 (a rounding moves a value by up to 2^-8 of its
  magnitude, about 0.7% of the largest hidden value in one ulp), and a
  12-layer stage compounds flipped roundings; 2^-4 of the hidden scale
  allows about ten ulps (measured on a TPU v5e: 0.234 at hidden scale
  5.72).
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

CONF_TOL = 2e-2
TIE_TOL = 5e-2
SHARDED_H_RTOL = 1e-3
RAGGED_H_RTOL = 2.0 ** -4
RAGGED_CONF_TOL = 1e-3

BUCKETS = (1, 2, 4, 8)
N_CLIENTS = 8
N_REQUESTS = 64
DECODE_ARCH = "qwen3-4b"
DECODE_TOKENS = 16
DECODE_BATCH = 4
RAGGED_POSITIONS = (3, 9, 14)
RAGGED_SLOTS = 16


def peak_gb() -> float:
    import jax
    return jax.local_devices()[0].memory_stats()["peak_bytes_in_use"] / 1e9


def in_use_gb() -> float:
    import jax
    return jax.local_devices()[0].memory_stats()["bytes_in_use"] / 1e9


def classifier_data(seed: int, n: int):
    from repro.configs import get_config
    from repro.training import DifficultyDataset
    cfg = get_config("anytime-classifier")
    test = DifficultyDataset(num_classes=cfg.vocab_size,
                             seed=seed).sample(n, seed=seed + 1)
    return cfg, test


def compare_stage(name, s, ref_logits, ref_conf, pred, conf, *, ref_h=None,
                  h=None, h_rtol=None):
    """One stage of one fixed padded batch against the reference path:
    preds equal except near-ties, confidences within ``CONF_TOL`` and,
    where given, hidden rows within ``h_rtol`` of the hidden scale."""
    import numpy as np
    logits = np.asarray(ref_logits, np.float32)
    top2 = np.sort(logits, axis=-1)[:, -2:]
    gap = top2[:, 1] - top2[:, 0]
    flips = logits.argmax(-1) != np.asarray(pred)
    bad = flips & (gap >= TIE_TOL)
    conf_err = float(np.max(np.abs(np.asarray(ref_conf, np.float32)
                                   - np.asarray(conf, np.float32))))
    msg = (f"{name} stage {s}: pred flips {int(flips.sum())} "
           f"(outside ties {int(bad.sum())}), max|dconf| {conf_err:.3e}")
    h_ok = True
    if h is not None:
        ref = np.asarray(ref_h, np.float32)
        h_err = float(np.max(np.abs(ref - np.asarray(h, np.float32))))
        scale = float(np.max(np.abs(ref)))
        h_ok = h_err <= h_rtol * max(1.0, scale)
        msg += f", max|dh| {h_err:.3e} (scale {scale:.3e})"
    print(msg)
    if bad.any() or conf_err > CONF_TOL or not h_ok:
        raise AssertionError(f"{name} disagrees with the reference: {msg}")


def classifier_phase(seed: int) -> None:
    """``anytime-classifier`` served by ``Service`` on the wall clock, once
    on ``device-batched`` and once on ``device-kernel`` (pipeline depth 3,
    compiled kernels), then both executors on one fixed padded batch."""
    import jax
    import numpy as np

    import repro.launch.serve  # noqa: F401 — registers device-kernel
    from repro.launch.kernel import KernelStageFns
    from repro.models import init_params
    from repro.serving import (BatchedStageFns, ServeSpec, Service,
                               closed_loop_stream, pad_batch,
                               profile_batched_stages, profile_host_overhead)

    cfg, test = classifier_data(seed, 4 * N_REQUESTS)
    params = init_params(cfg, jax.random.PRNGKey(seed))
    labels = np.asarray(test["labels"])
    sample = jax.tree.map(lambda x: x[:1], test["inputs"])

    bfns = BatchedStageFns(cfg, BUCKETS)
    kfns = KernelStageFns(cfg, BUCKETS)
    if kfns.interpret:
        raise AssertionError("device-kernel would interpret on this backend")
    for name, fns in (("device-batched", bfns), ("device-kernel", kfns)):
        t0 = time.perf_counter()
        fns.warmup(params, sample)
        print(f"classifier {name}: compile {time.perf_counter() - t0:.3f}s "
              f"({cfg.num_stages} stages x buckets {BUCKETS})")
    tm, bmat = profile_batched_stages(cfg, params, bfns, sample, n_runs=20)
    host_overhead = profile_host_overhead(n_runs=50)
    one = float(np.max(bmat[:, 0]))
    d_lo, d_hi = 4.0 * one, 14.0 * one
    print(f"classifier: p99 stage x bucket times (ms) "
          f"{np.round(bmat * 1e3, 4).tolist()}, host overhead "
          f"{host_overhead * 1e6:.1f}us, deadlines U[{d_lo * 1e3:.3f}, "
          f"{d_hi * 1e3:.3f}] ms")
    stream = closed_loop_stream(test["inputs"], test["labels"],
                                n_clients=N_CLIENTS, d_lo=d_lo, d_hi=d_hi,
                                n_requests=N_REQUESTS, seed=1)

    for name, fns, depth in (("device-batched", bfns, 1),
                             ("device-kernel", kfns, 3)):
        spec = ServeSpec(
            policy="rtdeepiot",
            policy_args={"predictor": "exp", "prior_curve": [.5, .7, .85]},
            executor=name, clock="wall", source="stream", batching={},
            host_overhead=host_overhead, pipeline_depth=depth)
        svc = Service.from_spec(spec, cfg=cfg, params=params, stage_fns=fns,
                                time_model=tm)
        t0 = time.perf_counter()
        svc.run(list(stream))
        wall = time.perf_counter() - t0
        rs = svc.responses
        ex = svc.executor
        cache = ex.cache_stats()
        served = [r for r in rs if not r.missed]
        acc = sum(r.prediction == labels[r.sample] for r in served) / len(rs)
        print(f"classifier {name} (pipeline_depth={depth}): n={len(rs)} "
              f"acc={acc:.3f} miss={np.mean([r.missed for r in rs]):.3f} "
              f"mean_depth={np.mean([r.depth for r in served] or [0]):.2f} "
              f"mean_latency={np.mean([r.latency for r in rs]) * 1e3:.3f}ms "
              f"wall={wall:.3f}s")
        print(f"classifier {name}: device_time_stats={ex.device_time_stats()}"
              f" cache_stats={cache} peak={peak_gb():.4f}GB")
        if len(rs) != N_REQUESTS or cache["live"] != 0:
            raise AssertionError(f"classifier {name}: served {len(rs)} of "
                                 f"{N_REQUESTS}, cache {cache}")

    rows = [jax.tree.map(lambda x, i=i: x[i:i + 1], test["inputs"])
            for i in range(BUCKETS[-1])]
    h, _mask = pad_batch(rows, BUCKETS[-1])
    txt = kfns.fn(0).lower(params, h).compile().as_text()
    print(f"classifier device-kernel stage 0: compiled program has "
          f"{txt.count('tpu_custom_call')} tpu_custom_call")
    if "tpu_custom_call" not in txt:
        raise AssertionError("device-kernel stage fn holds no Pallas kernel")
    for s in range(cfg.num_stages):
        h_b, logits, conf_b = bfns.fn(s)(params, h)
        _h_k, pred_k, conf_k = kfns.fn(s)(params, h)
        compare_stage("device-kernel vs device-batched", s, logits, conf_b,
                      pred_k, conf_k)
        h = h_b


def decode_phase(seed: int) -> None:
    """qwen3-4b at its registered width through ``repro.launch.serve.main``
    (plain and ``--pipeline``), then the ragged-decode route through the
    compiled Pallas decode kernel."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.launch import serve
    from repro.launch.kernel import KernelDecodeStageFns, ragged_decode_check
    from repro.launch.mesh import make_serving_mesh
    from repro.models import ParallelCtx, init_decode_cache, init_params

    for extra in ([], ["--pipeline"]):
        gc.collect()
        t0 = time.perf_counter()
        met = serve.main(["--arch", DECODE_ARCH, "--tokens",
                          str(DECODE_TOKENS), "--batch", str(DECODE_BATCH),
                          "--seed", str(seed)] + extra)
        label = "decode " + " ".join(["plain"] + extra)
        print(f"{label}: {met.n_requests} tokens, mean depth "
              f"{met.mean_depth:.2f}, mean conf {met.mean_conf:.4g}, phase "
              f"wall {time.perf_counter() - t0:.3f}s (init + compile + "
              f"decode), peak {peak_gb():.3f}GB")
        if met.n_requests != DECODE_TOKENS or not 1 <= met.mean_depth <= 3:
            raise AssertionError(f"{label}: {met}")

    gc.collect()
    cfg = get_config(DECODE_ARCH)
    params = init_params(cfg, jax.random.PRNGKey(seed))
    live = sum(a.nbytes for a in jax.live_arrays()) / 1e9
    print(f"ragged decode: {cfg.name} params built; device bytes in use "
          f"{in_use_gb():.3f}GB, live arrays {live:.3f}GB")
    ctx = ParallelCtx(mesh=make_serving_mesh(1, 1), decode_attn="kernel")
    fns = KernelDecodeStageFns(cfg, (1, 2, 4), ctx)
    st = init_decode_cache(cfg, 1, RAGGED_SLOTS)[1]
    txt = fns.fn(1).lower(params, jnp.zeros((1, cfg.d_model), cfg.dtype), st,
                          jnp.zeros((1,), jnp.int32)).compile().as_text()
    n_calls = txt.count("tpu_custom_call")
    print(f"ragged decode stage 1: compiled program has {n_calls} "
          f"tpu_custom_call (decode attention + fused exit)")
    if n_calls < 2 or fns.interpret:
        raise AssertionError("ragged decode does not run compiled kernels")
    t0 = time.perf_counter()
    r = ragged_decode_check(fns, params, RAGGED_POSITIONS, RAGGED_SLOTS,
                            seed=seed)
    print(f"ragged decode at positions {list(RAGGED_POSITIONS)}, "
          f"{RAGGED_SLOTS} slots: {r} ({time.perf_counter() - t0:.3f}s incl."
          f" compile), peak {peak_gb():.3f}GB")
    if not (r["same_shape_equal"] and r["pred_equal"]
            and r["h_err"] <= RAGGED_H_RTOL * max(1.0, r["h_scale"])
            and r["conf_err"] <= RAGGED_CONF_TOL):
        raise AssertionError(f"ragged decode batched != alone: {r}")


def sharded_phase(seed: int) -> None:
    """``device-sharded`` on dp=4,tp=1 and dp=2,tp=2 against
    ``device-batched`` on one chip of the same host, on the same fixed
    padded batch; then one short ``Service`` run per mesh."""
    import jax
    import numpy as np

    import repro.launch.serve  # noqa: F401 — registers device-sharded
    from repro.launch.mesh import make_serving_mesh
    from repro.launch.sharded import ShardedStageFns
    from repro.launch.shardings import param_shardings
    from repro.models import init_params
    from repro.serving import (BatchedStageFns, ServeSpec, Service,
                               closed_loop_stream, pad_batch)

    cfg, test = classifier_data(seed, 64)
    params = init_params(cfg, jax.random.PRNGKey(seed))
    n = BUCKETS[-1]
    rows = [jax.tree.map(lambda x, i=i: x[i:i + 1], test["inputs"])
            for i in range(n)]
    h0, _mask = pad_batch(rows, n)
    bfns = BatchedStageFns(cfg, (n,))
    ref, h = [], h0
    for s in range(cfg.num_stages):
        out = bfns.fn(s)(params, h)
        ref.append((h, out))
        h = out[0]
    stream = closed_loop_stream(test["inputs"], test["labels"], n_clients=4,
                                d_lo=0.2, d_hi=0.5, n_requests=16, seed=1)
    for dp, tp in ((4, 1), (2, 2)):
        mesh = make_serving_mesh(dp, tp)
        name = f"device-sharded {dp}x{tp}"
        sfns = ShardedStageFns(cfg, (n // dp,), mesh)
        sp = jax.device_put(params, param_shardings(mesh, params, layout="tp"))
        t0 = time.perf_counter()
        for s, (h_in, (h_b, logits, conf_b)) in enumerate(ref):
            h_s, logits_s, conf_s = sfns.fn(s)(sp, h_in)
            compare_stage(f"{name} vs device-batched", s, logits, conf_b,
                          np.asarray(logits_s).argmax(-1), conf_s, ref_h=h_b,
                          h=h_s, h_rtol=SHARDED_H_RTOL)
        print(f"{name}: parity on {n} rows x {cfg.num_stages} stages "
              f"{time.perf_counter() - t0:.3f}s incl. compile")
        spec = ServeSpec(
            policy="rtdeepiot",
            policy_args={"predictor": "exp", "prior_curve": [.5, .7, .85]},
            executor="device-sharded", executor_args={"dp": dp, "tp": tp},
            clock="virtual", source="stream",
            batching={"buckets": [1, 2], "stage_times": [.002, .003, .004],
                      "marginal": 0.25})
        svc = Service.from_spec(spec, cfg=cfg, params=params)
        met = svc.run(list(stream))
        ex = svc.executor
        print(f"{name} Service: n={met.n_requests} mean_depth="
              f"{met.mean_depth:.2f} miss={met.miss_rate:.3f} "
              f"buckets={ex.stage_fns.buckets} cache={ex.cache_stats()}")
        if (met.n_requests != 16 or ex.cache_stats()["live"] != 0
                or (ex.dp, ex.tp) != (dp, tp)):
            raise AssertionError(f"{name} Service run: {met}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the device-sharded meshes")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    sys.path.insert(0, SRC)
    from repro.launch.compile_cache import enable_compile_cache
    limit = (dev.memory_stats() or {}).get("bytes_limit", 0) / 1e9
    print(f"device {dev.device_kind} x{len(devices)} ({limit:.3f}GB limit), "
          f"jax {jax.__version__}, compile cache {enable_compile_cache()}")

    phases = ([("sharded", sharded_phase)] if args.chips == 4 else
              [("classifier", classifier_phase), ("decode", decode_phase)])
    for name, phase in phases:
        t0 = time.perf_counter()
        phase(args.seed)
        print(f"phase {name}: ok in {time.perf_counter() - t0:.3f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
