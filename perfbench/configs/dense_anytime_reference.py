"""Plain float32 reference of a dense anytime transformer (Qwen3 family).

What it computes, from the published description (Qwen3 technical report,
hf:Qwen/Qwen3-4B config): pre-norm RMSNorm blocks, grouped-query attention
with per-head RMSNorm on q and k (``qk_norm``), rotary position embedding
(``rope_theta``, half-split rotation), SwiGLU feed-forward, residual adds.
The anytime construction adds one exit head after each stage: RMSNorm with
the stage's own scale, then a vocabulary projection shared by all exits,
read at the last position.

Departures from the published model, all shared with the system under
test: RMSNorm scales act as ``1 + scale`` (random weights make the two
parametrizations equivalent), the vocabulary projection is its own matrix
(Qwen3-4B ties it to the embedding), and the exit heads exist at all.

This file imports nothing of the program.  Weights come in as a pytree in
the program's layout (``params["stages"][s]`` with a stacked ``scan`` group
or a ``prefix`` list of layers); :func:`layers_of` reads it.  Matmuls run
at ``Precision.HIGHEST``.  ``rounding`` selects the control arithmetic:
``None`` (float32), ``"bf16"`` (every operand and result rounded to
bfloat16) or ``"fp8"`` (matmul operands rounded to float8 e4m3 with a
per-tensor scale, the rest in bfloat16).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _round(x, rounding):
    if rounding in ("bf16", "fp8"):
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return x


def _operand(x, rounding):
    if rounding == "fp8":
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return _round(x, rounding)


def mm(a, b, rounding=None):
    a = _operand(a.astype(jnp.float32), rounding)
    b = _operand(b.astype(jnp.float32), rounding)
    return _round(jnp.matmul(a, b, precision=HIGHEST), rounding)


def rms_norm(x, scale, eps, rounding=None):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return _round(y * (1.0 + scale.astype(jnp.float32)), rounding)


def rope(x, pos, theta):
    """x: (B, S, heads, hd); pos: (S,)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def block(h, p, m, rounding=None):
    """One transformer layer.  h: (B, S, d) float32; p: the layer's
    ``{"mixer": ..., "ffn": ...}``; m: the configuration's sizes."""
    B, S, _ = h.shape
    H, KV, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    G = H // KV
    eps = m["rms_norm_eps"]
    a = p["mixer"]
    x = rms_norm(h, a["ln"], eps, rounding)
    q = mm(x, a["wq"], rounding).reshape(B, S, H, hd)
    k = mm(x, a["wk"], rounding).reshape(B, S, KV, hd)
    v = mm(x, a["wv"], rounding).reshape(B, S, KV, hd)
    if m["qk_norm"]:
        q = rms_norm(q, a["q_norm"], eps, rounding)
        k = rms_norm(k, a["k_norm"], eps, rounding)
    pos = jnp.arange(S)
    q = _round(rope(q, pos, m["rope_theta"]), rounding)
    k = _round(rope(k, pos, m["rope_theta"]), rounding)
    # query head j reads key/value head j // G
    qg = q.reshape(B, S, KV, G, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", _operand(qg, rounding),
                   _operand(k, rounding), precision=HIGHEST) * hd ** -0.5
    if m["causal"]:
        s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    w = _round(jax.nn.softmax(s, axis=-1), rounding)
    o = jnp.einsum("bkgqs,bskh->bqkgh", _operand(w, rounding),
                   _operand(v, rounding), precision=HIGHEST)
    o = _round(o, rounding).reshape(B, S, H * hd)
    h = _round(h + mm(o, a["wo"], rounding), rounding)
    f = p["ffn"]
    x = rms_norm(h, f["ln"], eps, rounding)
    g = mm(x, f["w_gate"], rounding)
    u = mm(x, f["w_up"], rounding)
    act = _round(jax.nn.silu(g) * u, rounding)
    return _round(h + mm(act, f["w_down"], rounding), rounding)


def embed(params, inputs, m, rounding=None):
    """Token ids (B, S) -> (B, S, d) float32."""
    e = params["embed"]
    return _round(jnp.take(e["tok"], jnp.asarray(inputs), axis=0)
                  .astype(jnp.float32), rounding)


def exit_rows(h, m):
    """The rows an exit reads: the last position's."""
    return h[:, -1]


def exit_logits(rows, params, stage, m, rounding=None):
    """(N, d) rows -> (N, V) float32 logits of stage ``stage``'s exit."""
    x = rms_norm(rows, params["exits"][stage]["ln"], m["rms_norm_eps"],
                 rounding)
    return mm(x, params["exit_shared"]["w_out"], rounding)


def layers_of(params, stage: int):
    """Yield the stage's layers in order, each a ``{"mixer", "ffn"}``
    pytree (a stacked group is sliced one layer at a time)."""
    sp = params["stages"][stage]
    yield from sp.get("prefix", [])
    if sp.get("scan") is not None:
        (stacked,) = sp["scan"]
        for j in range(jax.tree.leaves(stacked)[0].shape[0]):
            yield jax.tree.map(lambda x, j=j: x[j], stacked)
    yield from sp.get("tail", [])


class Reference:
    """Stage-by-stage reference over blocks of rows, one jitted layer
    program per input shape (the weights are arguments)."""

    def __init__(self, m: dict, params, rounding=None):
        self.m = m
        self.params = params
        self.rounding = rounding
        self._block = jax.jit(lambda h, p: block(h, p, m, rounding))
        self._embed = jax.jit(lambda p, x: embed(p, x, m, rounding))
        self._exit = jax.jit(lambda rows, p, s: exit_logits(rows, p, s, m,
                                                            rounding),
                             static_argnums=2)

    def hidden_by_stage(self, inputs):
        """Yield ``(stage, h)`` after each stage for a (B, S[, F]) block."""
        h = self._embed(self.params, inputs)
        for s in range(len(self.params["stages"])):
            for p in layers_of(self.params, s):
                h = self._block(h, p)
            yield s, h

    def exit_logits(self, rows, stage: int):
        return self._exit(rows, self.params, stage)


def logit_gap(logits, served) -> np.ndarray:
    """How far each served id's logit lies below the row's best: (N,)."""
    lg = np.asarray(logits, np.float32)
    served = np.asarray(served, np.int64)
    return lg.max(-1) - lg[np.arange(len(served)), served]


def confidence(logits) -> np.ndarray:
    """Max-softmax probability of each row (float64 on the host)."""
    lg = np.asarray(logits, np.float64)
    z = lg - lg.max(-1, keepdims=True)
    return 1.0 / np.exp(z).sum(-1)
