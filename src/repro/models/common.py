"""Shared model utilities: norms, RoPE, init, parallel context, sharding."""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ParallelCtx:
    """Distribution context threaded through model apply functions.

    dp: mesh axis name(s) carrying the batch (tuple — ('pod','data') multi-pod).
    tp: mesh axis name carrying tensor/expert/head parallelism.
    seq_axes: axes over which decode KV caches are sequence-sharded.
    """
    mesh: object
    dp: tuple = ("data",)
    tp: str = "model"
    seq_axes: tuple = ("model",)
    # feature toggles (hillclimbing knobs; see EXPERIMENTS.md §Perf)
    moe_impl: str = "gather"          # gather | alltoall
    decode_attn: str = "flash_decode"  # flash_decode | kernel | naive
    attn_impl: str = "grouped"        # grouped | flat (§Perf iteration 1:
                                      # flat repeats KV->H so the head axis
                                      # shards evenly over tp, killing GSPMD
                                      # involuntary full remats when KV < tp)
    seq_parallel: bool = False        # §Perf iteration 2: residual stream
                                      # sequence-sharded over tp between
                                      # blocks -> row-parallel psums become
                                      # reduce-scatters (Megatron-SP)
    remat: bool = True

    @property
    def tp_size(self) -> int:
        return self.mesh.shape[self.tp]


@dataclasses.dataclass(frozen=True)
class CommittedCache:
    """A decode cache that a step reads and does not write.

    Given a layer's or a stage's cache in this type, a decode step returns
    the token's *slot* in its place (what it would have written: the new
    key/value or latent, its position and the write index; a recurrent
    layer's whole new state) instead of a new cache, and the cache's owner
    writes the slot itself (:func:`repro.models.write_decode_slots`)."""
    cache: object


jax.tree_util.register_dataclass(CommittedCache, data_fields=["cache"],
                                 meta_fields=[])


def shard(x, ctx: Optional[ParallelCtx], *spec):
    """Apply a sharding constraint if running distributed."""
    if ctx is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(ctx.mesh, P(*spec)))


def shard_residual(x, ctx: Optional[ParallelCtx], name: Optional[str] = None):
    """Constraint for the residual stream (B, S, d) between blocks:
    sequence-sharded over tp when seq_parallel (full mode only).

    §Perf iteration 4: (a) an optimization barrier pins the bf16 dtype at
    the block output so XLA cannot hoist the fp32 convert of the next
    norm above the row-parallel all-reduce (halves its volume); (b) a
    checkpoint_name makes the psum'd output saveable across remat so the
    backward does not re-execute the all-reduce.
    """
    if ctx is None:
        return x
    if name is not None:
        from jax.ad_checkpoint import checkpoint_name
        x = jax.lax.optimization_barrier(x)
        x = checkpoint_name(x, name)
    if x.ndim == 3 and ctx.seq_parallel and x.shape[1] % ctx.tp_size == 0:
        return shard(x, ctx, ctx.dp, ctx.tp, None)
    if x.ndim == 3:
        return shard(x, ctx, ctx.dp, None, None)
    return shard(x, ctx, ctx.dp, None)


def rms_norm(x, scale, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dt)


def rms_norm_head(x, scale, eps: float = 1e-6):
    """Per-head qk-norm: normalize over the trailing head_dim."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., seq, heads, head_dim); positions: (..., seq) int32."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]                 # broadcast over heads
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(1, 2))
def _scale_cast(x, scale, dtype):
    return (scale * x).astype(dtype)


def dense_init(key, shape, dtype, scale: float = 0.02):
    # scale and cast in one program: the f32 draw is the only transient
    # beside the result (eager ops would also hold its scaled f32 copy —
    # 1.6 GB more for a full-width vocab matrix)
    return _scale_cast(jax.random.normal(key, shape, jnp.float32),
                       float(scale), jnp.dtype(dtype))


def zeros_init(shape, dtype):
    return jnp.zeros(shape, dtype)


class KeyGen:
    """Split keys on demand (keeps init code linear)."""

    def __init__(self, key):
        self._key = key

    def __call__(self):
        self._key, sub = jax.random.split(self._key)
        return sub


def param_dtype(cfg):
    return jnp.dtype(cfg.dtype)
