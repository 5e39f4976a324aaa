"""Early-exit heads — the imprecise-computation interface of every model.

Each stage ends in a thin classifier (paper Fig. 1): RMSNorm → linear to the
output vocabulary → softmax.  Its (prediction, confidence) tuple is what the
RTDeepIoT scheduler consumes; confidence = (optionally temperature-calibrated)
max-softmax probability [21].

The TPU-target fused version of `confidence_from_logits` (online softmax over
vocab blocks, never materializing the probability vector) lives in
repro.kernels.exit_confidence.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import KeyGen, dense_init, param_dtype, rms_norm, shard


def init_exit(cfg, key, dtype=None, shared=False):
    """Per-stage exit params.  The (large, vocab-sized) output projection is
    *shared* across stages (paper: exits are "thin" classifiers; sharing the
    unembedding is the standard anytime-LM construction) — each stage owns
    only its norm scale.  `shared=True` initializes the shared projection."""
    kg = KeyGen(key)
    dt = dtype or param_dtype(cfg)
    d, V = cfg.d_model, cfg.vocab_size
    if shared:
        if cfg.modality == "audio_stub":
            return {"w_out": dense_init(kg(), (cfg.num_codebooks, d, V), dt)}
        return {"w_out": dense_init(kg(), (d, V), dt)}
    return {"ln": jnp.zeros((d,), dt)}


def apply_exit(cfg, params, h, *, ctx=None):
    """h: (B, S, d) -> logits.

    text/vlm:   (B, S, V)     next-token logits
    audio_stub: (B, S, ncb, V)
    features:   (B, V)        mean-pooled classification logits
    """
    hn = rms_norm(h, params["ln"], cfg.norm_eps)
    if cfg.modality == "features":
        # classification readout = cell 0 (the anchor position); mean-pool
        # dilutes position-routed information
        hn = hn[:, 0]
        return hn @ params["w_out"]
    if cfg.modality == "audio_stub":
        logits = jnp.einsum("bsd,cdv->bscv", hn, params["w_out"])
    else:
        logits = hn @ params["w_out"]
    if ctx is not None:
        lead = (ctx.dp,) + (None,) * (logits.ndim - 2)
        logits = shard(logits, ctx, *lead, ctx.tp)
    return logits


def exit_rows(cfg, h):
    """The rows the exit head actually reads: (B, d).

    features: the anchor cell (position 0); decode callers pass the
    current-token hidden state directly.  RMSNorm is per-position, so
    norming the selected rows equals selecting from the normed tensor —
    this is what lets the fused kernel skip the rest of the sequence."""
    if h.ndim == 2:
        return h
    return h[:, 0] if cfg.modality == "features" else h[:, -1]


def exit_stats_unfused(h_rows, scale, w_out, *, eps: float = 1e-6,
                       temperature: float = 1.0):
    """Unfused reference for the fused exit kernel — materializes the full
    (N, V) logits row, then reduces with the *same* finisher arithmetic as
    the kernel (running max m, normalizer l = sum exp(logits - m),
    conf = 1/l, lse = m + log l).  With a single vocab block the kernel's
    online pass folds exactly once, so in interpret mode the fused path is
    bit-for-bit equal to this function — the equality the kernel-serving
    figure asserts.

    h_rows: (N, d); scale: (d,); w_out: (d, V).
    Returns (conf (N,), pred (N,) int32, max_logit (N,), lse (N,)).
    """
    h = h_rows.astype(jnp.float32)
    var = jnp.mean(jnp.square(h), axis=-1, keepdims=True)
    hn = h * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    logits = jax.lax.dot_general(hn, w_out.astype(jnp.float32),
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    logits = logits / temperature
    m = jnp.max(logits, axis=1)
    l = jnp.maximum(jnp.sum(jnp.exp(logits - m[:, None]), axis=1), 1e-30)
    conf = 1.0 / l
    pred = jnp.argmax(logits, axis=1).astype(jnp.int32)
    return conf, pred, m, m + jnp.log(l)


def exit_stats_fused(h_rows, scale, w_out, *, eps: float = 1e-6,
                     temperature: float = 1.0, block_rows: int = 8,
                     block_v: int = 512, interpret: bool | None = None):
    """Fused exit epilogue: RMSNorm -> matmul -> online (max, lse, argmax)
    in one Pallas dispatch (repro.kernels.exit_confidence) — the V-sized
    logits row never leaves the kernel.  Same signature/returns as
    :func:`exit_stats_unfused`."""
    from repro.kernels.exit_confidence.kernel import exit_confidence
    return exit_confidence(h_rows, scale, w_out, eps=eps,
                           temperature=temperature, block_rows=block_rows,
                           block_v=block_v, interpret=interpret)


def confidence_from_logits(logits, temperature: float = 1.0):
    """Max-softmax confidence over the trailing class axis (fp32).

    Pure-jnp oracle for the fused Pallas kernel; audio codebook confidences
    are averaged.
    """
    lg = logits.astype(jnp.float32) / temperature
    conf = jnp.exp(jnp.max(lg, -1) - jax.nn.logsumexp(lg, -1))
    # average any remaining non-batch axes (codebooks / positions handled by
    # callers; this reduces exactly the codebook axis for audio)
    return conf


def exit_prediction(cfg, logits):
    """argmax class / token id at the last position (serving path)."""
    return jnp.argmax(logits, axis=-1)
