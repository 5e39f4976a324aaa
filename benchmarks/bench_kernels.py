"""Kernel microbenchmarks: wall time as the backend runs the kernels
(interpret mode on the CPU at correctness scale, compiled on a TPU) plus
the analytic VMEM working set per BlockSpec tile — the quantity that
determines whether a tile choice fits v5e VMEM (128 MiB/core budget split
across buffers).  Prints name,us_per_call,derived CSV.

``--smoke`` runs every kernel once at reduced shapes (single timing rep) —
the CI bench-smoke leg that keeps all five kernel dispatch paths alive
without the full-shape interpret-mode cost.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

#: --smoke shrinks the dominant shape axes and times a single rep; full
#: runs keep the VMEM-analysis shapes
SMOKE = False


def _shape(full, small):
    return small if SMOKE else full


def _reps():
    return 1 if SMOKE else 3


def _time(fn, *args, n=None):
    n = n or _reps()
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / n * 1e6


def bench_flash_attention():
    from repro.kernels.flash_attention import flash_attention_op
    B, H, KV, S, dh = 1, 4, 2, _shape(256, 64), 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, S, dh))
    k = jax.random.normal(ks[1], (B, KV, S, dh))
    v = jax.random.normal(ks[2], (B, KV, S, dh))
    us = _time(lambda *a: flash_attention_op(*a, block_q=128, block_k=128), q, k, v)
    # VMEM per grid step: q tile + k tile + v tile + fp32 acc
    vmem = (128 * dh * 2) * 3 + 128 * dh * 4 + 2 * 128 * 4
    print(f"flash_attention,{us:.0f},vmem_tile_bytes={vmem}")


def bench_decode_attention():
    from repro.kernels.decode_attention import decode_attention_op
    B, H, KV, S, dh = 4, 8, 2, _shape(1024, 128), 64
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, H, dh))
    kc = jax.random.normal(ks[1], (B, KV, S, dh))
    vc = jax.random.normal(ks[2], (B, KV, S, dh))
    sp = jnp.broadcast_to(jnp.arange(S), (B, S))
    cur = jnp.full((B,), S - 1)
    us = _time(lambda *a: decode_attention_op(*a, block_k=256), q, kc, vc, sp, cur)
    vmem = 256 * dh * 2 * 2 + dh * 4 + 256 * 4
    print(f"decode_attention,{us:.0f},vmem_tile_bytes={vmem}")


def bench_exit_confidence():
    from repro.kernels.exit_confidence import exit_confidence_op
    N, d, V = 8, 256, _shape(32768, 2048)
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    h = jax.random.normal(ks[0], (N, d))
    sc = 0.1 * jax.random.normal(ks[1], (d,))
    w = 0.3 * jax.random.normal(ks[2], (d, V))
    us = _time(lambda *a: exit_confidence_op(*a, block_rows=8, block_v=512),
               h, sc, w)
    vmem = 8 * d * 4 + d * 512 * 2 + 8 * 512 * 4
    print(f"exit_confidence,{us:.0f},vmem_tile_bytes={vmem}")


def bench_rmsnorm():
    from repro.kernels.rmsnorm import rmsnorm_op
    x = jax.random.normal(jax.random.PRNGKey(3), (_shape(1024, 128), 512))
    s = 0.1 * jax.random.normal(jax.random.PRNGKey(4), (512,))
    us = _time(lambda *a: rmsnorm_op(*a, block_rows=256), x, s)
    print(f"rmsnorm,{us:.0f},vmem_tile_bytes={256 * 512 * 4}")


def bench_mlstm_chunk():
    from repro.kernels.mlstm_chunk import mlstm_chunk_op
    import jax.numpy as jnp
    B, H, L, dh = 2, 4, _shape(128, 32), 64
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    q = jax.random.normal(ks[0], (B, H, L, dh))
    k = jax.random.normal(ks[1], (B, H, L, dh))
    v = jax.random.normal(ks[2], (B, H, L, dh))
    ip = jax.random.normal(ks[3], (B, H, L))
    fp = jax.random.normal(ks[4], (B, H, L)) + 2
    C0 = jnp.zeros((B, H, dh, dh))
    n0 = jnp.zeros((B, H, dh))
    m0 = jnp.full((B, H), -1e30)
    us = _time(lambda *a: mlstm_chunk_op(*a)[0], q, k, v, ip, fp, C0, n0, m0)
    vmem = 3 * L * dh * 4 + L * L * 4 + dh * dh * 4
    print(f"mlstm_chunk,{us:.0f},vmem_tile_bytes={vmem}")


def main(argv=None):
    global SMOKE
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced shapes, one timing rep (CI)")
    SMOKE = ap.parse_args(argv).smoke
    bench_flash_attention()
    bench_decode_attention()
    bench_exit_confidence()
    bench_rmsnorm()
    bench_mlstm_chunk()


if __name__ == "__main__":
    main()
