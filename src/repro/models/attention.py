"""Attention blocks: GQA (full / sliding-window local) and MLA (DeepSeek).

Three execution paths:
  * mode="full"  — training / prefill over a whole sequence.  Full attention
    uses a q-chunk scanned online-softmax (flash pattern, O(S) memory);
    sliding-window layers use a block-local path (each chunk attends to
    itself + the previous chunk) that never touches far context.
  * mode="step"  — decode: one new token against a KV cache.  Distributed
    decode uses the shard_map flash-decode in repro.models.flash_decode
    (sequence-sharded cache, (m, l) logsumexp combine).
  * Pallas kernels in repro.kernels are the TPU-target versions of the same
    math, validated against these pure-jnp paths.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.common import (CommittedCache, KeyGen, apply_rope,
                                 dense_init, param_dtype, rms_norm,
                                 rms_norm_head, shard, shard_residual)

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# core softmax-attention primitives (pure jnp)
# ---------------------------------------------------------------------------

def _grouped_scores(q, k):
    """q: (B,Sq,KV,G,hd)  k: (B,Sk,KV,hd)  -> (B,KV,G,Sq,Sk)."""
    return jnp.einsum("bqkgh,bskh->bkgqs", q, k)


def _grouped_out(p, v):
    """p: (B,KV,G,Sq,Sk)  v: (B,Sk,KV,hd) -> (B,Sq,KV,G,hd)."""
    return jnp.einsum("bkgqs,bskh->bqkgh", p, v)


def attend_dense(q, k, v, *, causal: bool, q_pos, k_pos,
                 window: Optional[int] = None, softmax_scale: float):
    """Unchunked reference attention with GQA grouping.

    q: (B, Sq, KV, G, hd); k, v: (B, Sk, KV, hd); *_pos: int32 positions.
    """
    scores = _grouped_scores(q, k).astype(jnp.float32) * softmax_scale
    mask = jnp.ones((q.shape[1], k.shape[1]), bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        mask &= q_pos[:, None] - k_pos[None, :] < window
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return _grouped_out(p, v)


def attend_chunked(q, k, v, *, q_pos, k_pos, window: Optional[int],
                   softmax_scale: float, q_chunk: int = 1024,
                   causal: bool = True):
    """Causal online-softmax attention, scanned over query chunks.

    Memory is O(q_chunk * Sk) instead of O(Sq * Sk).  Each chunk's scores are
    computed against the full key range with causal (+ optional window)
    masking — FLOPs match the dense path, memory does not.
    """
    B, Sq, KV, G, hd = q.shape
    if Sq <= q_chunk:
        return attend_dense(q, k, v, causal=causal, q_pos=q_pos, k_pos=k_pos,
                            window=window, softmax_scale=softmax_scale)
    assert Sq % q_chunk == 0, (Sq, q_chunk)
    n = Sq // q_chunk
    qs = q.reshape(B, n, q_chunk, KV, G, hd).transpose(1, 0, 2, 3, 4, 5)
    qp = q_pos.reshape(n, q_chunk)

    def body(_, x):
        qc, qpc = x
        out = attend_dense(qc, k, v, causal=causal, q_pos=qpc, k_pos=k_pos,
                           window=window, softmax_scale=softmax_scale)
        return None, out

    _, outs = jax.lax.scan(body, None, (qs, qp))
    hd_v = v.shape[-1]                     # MLA: v head dim != q head dim
    return outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, KV, G, hd_v)


def attend_local(q, k, v, *, q_pos, k_pos, window: int, softmax_scale: float):
    """Block-local sliding-window attention (window <= block).

    Chunks the sequence into `window`-sized blocks; each block attends to
    itself and its predecessor with exact causal+window masking.  FLOPs are
    O(S * 2*window) — this is the sub-quadratic path used by local layers.
    """
    B, S, KV, G, hd = q.shape
    if S <= window:
        return attend_dense(q, k, v, causal=True, q_pos=q_pos, k_pos=k_pos,
                            window=window, softmax_scale=softmax_scale)
    assert S % window == 0, (S, window)
    n = S // window
    qb = q.reshape(B, n, window, KV, G, hd)
    kb = k.reshape(B, n, window, KV, hd)
    vb = v.reshape(B, n, window, KV, hd)
    # previous block (zero-padded at the front)
    pad = lambda x: jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], 1)
    k2 = jnp.concatenate([pad(kb), kb], axis=2)         # (B,n,2w,KV,hd)
    v2 = jnp.concatenate([pad(vb), vb], axis=2)
    qpb = q_pos.reshape(n, window)
    kpb = k_pos.reshape(n, window)
    kp2 = jnp.concatenate(
        [jnp.concatenate([jnp.full((1, window), -10**9, k_pos.dtype),
                          kpb[:-1]], 0), kpb], axis=1)  # (n, 2w)

    scores = jnp.einsum("bnqkgh,bnskh->bnkgqs", qb, k2).astype(jnp.float32)
    scores = scores * softmax_scale
    mask = (qpb[:, :, None] >= kp2[:, None, :]) & \
           (qpb[:, :, None] - kp2[:, None, :] < window)
    scores = jnp.where(mask[None, :, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bnkgqs,bnskh->bnqkgh", p, v2)
    return out.reshape(B, S, KV, G, hd)


def decode_attend(q, k_cache, v_cache, k_pos, cur_pos, *, window, softmax_scale):
    """Single-token decode attention against a cache (single-shard path).

    q: (B, KV, G, hd); caches: (B, S, KV, hd); k_pos: (S,) positions stored at
    each cache slot (ring buffers store non-monotonic positions); cur_pos: (B,)
    """
    scores = jnp.einsum("bkgh,bskh->bkgs", q, k_cache).astype(jnp.float32)
    scores = scores * softmax_scale
    valid = (k_pos[None] <= cur_pos[:, None]) & (k_pos[None] >= 0)
    if window is not None:
        valid &= cur_pos[:, None] - k_pos[None] < window
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bkgs,bskh->bkgh", p, v_cache)


def decode_attend_committed(scores, score_new, v_cache, v_new, k_pos,
                            write_idx, cur_pos, *, window, dtype):
    """:func:`decode_attend` over a cache the step does not write.

    The keys are the cache's slots, less the slot the token would
    overwrite (``write_idx``: on a ring cache it holds a stale position),
    and the token's own key, with one softmax over both.  scores: (B, KV,
    G, S) and score_new: (B, KV, G), scaled, float32; v_cache: (B, S, KV,
    hd); v_new: (B, KV, hd); k_pos: (S,) positions held by the slots.
    """
    S = scores.shape[-1]
    valid = (k_pos[None] <= cur_pos[:, None]) & (k_pos[None] >= 0)
    if window is not None:
        valid &= cur_pos[:, None] - k_pos[None] < window
    valid &= jnp.arange(S)[None] != write_idx[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(jnp.concatenate([scores, score_new[..., None]], -1),
                       axis=-1).astype(dtype)
    out = jnp.einsum("bkgs,bskh->bkgh", p[..., :S], v_cache,
                     preferred_element_type=jnp.float32)
    out += p[..., S:].astype(jnp.float32) * v_new[:, :, None].astype(
        jnp.float32)
    return out.astype(dtype)


def _committed_route(ctx):
    if ctx is not None and ctx.decode_attn in ("kernel", "flash_decode"):
        raise ValueError(f"decode_attn={ctx.decode_attn!r} runs on a plain "
                         "cache, not a CommittedCache")


# ---------------------------------------------------------------------------
# GQA block
# ---------------------------------------------------------------------------

def init_gqa(cfg, key, dtype=None):
    kg = KeyGen(key)
    dt = dtype or param_dtype(cfg)
    d, hd = cfg.d_model, cfg.resolved_head_dim
    H, KV = cfg.num_heads, cfg.num_kv_heads
    p = {
        "ln": jnp.zeros((d,), dt),
        "wq": dense_init(kg(), (d, H * hd), dt),
        "wk": dense_init(kg(), (d, KV * hd), dt),
        "wv": dense_init(kg(), (d, KV * hd), dt),
        "wo": dense_init(kg(), (H * hd, d), dt, scale=0.02 / max(1, cfg.num_layers) ** 0.5),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dt)
        p["k_norm"] = jnp.zeros((hd,), dt)
    return p


def _project_qkv(cfg, params, x, positions, ctx, allow_flat=True):
    B = x.shape[0]
    S = x.shape[1] if x.ndim == 3 else 1
    hd = cfg.resolved_head_dim
    H, KV = cfg.num_heads, cfg.num_kv_heads
    x2 = x.reshape(B, S, -1)
    q = (x2 @ params["wq"]).reshape(B, S, KV, H // KV, hd)
    k = (x2 @ params["wk"]).reshape(B, S, KV, hd)
    v = (x2 @ params["wv"]).reshape(B, S, KV, hd)
    if cfg.qk_norm:
        q = rms_norm_head(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm_head(k, params["k_norm"], cfg.norm_eps)
    pos = positions if positions.ndim == 2 else positions[None].repeat(B, 0)
    q = apply_rope(q.reshape(B, S, KV * (H // KV), hd), pos, cfg.rope_theta)
    q = q.reshape(B, S, KV, H // KV, hd)
    k = apply_rope(k, pos, cfg.rope_theta)
    if ctx is not None and allow_flat and ctx.attn_impl == "flat" \
            and H % ctx.tp_size == 0:
        # §Perf iteration 1 (flat-head attention): repeat KV heads to H and
        # treat as MHA so every attention operand shards exactly H/tp-way —
        # no (KV=8 vs tp=16) mismatch, no involuntary full remats.  The
        # repeated K/V shard over heads, so per-device bytes are H/tp*hd
        # (<= the replicated KV heads of the grouped layout).
        G = H // KV
        k = jnp.repeat(k, G, axis=2)               # (B,S,H,hd)
        v = jnp.repeat(v, G, axis=2)
        q = q.reshape(B, S, H, 1, hd)
        k = shard(k, ctx, ctx.dp, None, ctx.tp, None)
        v = shard(v, ctx, ctx.dp, None, ctx.tp, None)
        q = shard(q, ctx, ctx.dp, None, ctx.tp, None, None)
        return q, k, v
    if ctx is not None:
        if KV % ctx.tp_size == 0:
            q = shard(q, ctx, ctx.dp, None, ctx.tp, None, None)
        # else: leave placement to GSPMD propagation (baseline behaviour)
    return q, k, v


def apply_gqa_full(cfg, params, x, *, positions, local: bool, ctx,
                   q_chunk: int = 1024):
    """Training/prefill attention over the full sequence.

    Returns (y, cache) where cache = (k, v) over the whole sequence.
    """
    B, S, d = x.shape
    hd = cfg.resolved_head_dim
    h = rms_norm(x, params["ln"], cfg.norm_eps)
    q, k, v = _project_qkv(cfg, params, h, positions, ctx)
    scale = hd ** -0.5
    window = cfg.sliding_window if local else None
    kp = positions if positions.ndim == 1 else positions[0]
    if local and window is not None and S > window:
        out = attend_local(q, k, v, q_pos=kp, k_pos=kp, window=window,
                           softmax_scale=scale)
    else:
        out = attend_chunked(q, k, v, q_pos=kp, k_pos=kp, window=window,
                             softmax_scale=scale, q_chunk=q_chunk,
                             causal=cfg.causal)
    out = out.reshape(B, S, cfg.num_heads * hd)
    y = out @ params["wo"]
    y = shard_residual(y, ctx)
    return x + y, (k, v)


def apply_gqa_step(cfg, params, x, *, cache, cur_pos, local: bool, ctx):
    """Decode one token.  cache: dict(k=(B,S,KV,hd), v=..., slot_pos=(B,S)).

    The cache layout is owned by repro.serving.kv_cache: a *full* cache has
    as many slots as max positions (write slot = position); a *ring* cache
    (sliding-window layers / swa-8192 long-context variant) has `window`
    slots and wraps — `slot_pos` records which position each slot holds so
    masking stays exact either way.  A :class:`CommittedCache` is read and
    not written: the token's slot (``k``, ``v``, ``slot_pos``, ``write_idx``)
    comes back in place of a new cache.
    """
    from repro.models import flash_decode

    B, d = x.shape[0], x.shape[-1]
    hd = cfg.resolved_head_dim
    H, KV = cfg.num_heads, cfg.num_kv_heads
    h = rms_norm(x, params["ln"], cfg.norm_eps)
    # decode writes KV heads into the cache: the flat-head repeat is a
    # full-mode (train/prefill) optimization only
    q, k, v = _project_qkv(cfg, params, h, cur_pos[:, None], ctx,
                           allow_flat=False)
    q = q[:, 0]                      # (B,KV,G,hd)
    k_new, v_new = k[:, 0], v[:, 0]  # (B,KV,hd)
    window = cfg.sliding_window if local else None
    scale = hd ** -0.5
    if isinstance(cache, CommittedCache):
        _committed_route(ctx)
        c = cache.cache
        write_idx = cur_pos % c["k"].shape[1]
        k_new = k_new.astype(c["k"].dtype)
        v_new = v_new.astype(c["v"].dtype)
        scores = jnp.einsum("bkgh,bskh->bkgs", q, c["k"]).astype(
            jnp.float32) * scale
        score_new = jnp.einsum("bkgh,bkh->bkg", q, k_new).astype(
            jnp.float32) * scale
        out = decode_attend_committed(
            scores, score_new, c["v"], v_new, c["slot_pos"][0], write_idx,
            cur_pos, window=window, dtype=q.dtype)
        y = out.reshape(B, H * hd) @ params["wo"]
        return x + y, {"k": k_new, "v": v_new, "slot_pos": cur_pos,
                       "write_idx": write_idx}

    n_slots = cache["k"].shape[1]
    write_idx = cur_pos % n_slots    # (B,) slot to overwrite (ring-aware)
    bidx = jnp.arange(B)
    k_cache = cache["k"].at[bidx, write_idx].set(k_new.astype(cache["k"].dtype))
    v_cache = cache["v"].at[bidx, write_idx].set(v_new.astype(cache["v"].dtype))
    slot_pos = cache["slot_pos"].at[bidx, write_idx].set(cur_pos)

    if ctx is not None and ctx.decode_attn == "kernel":
        # Pallas decode kernel over the (ring) cache: per-row slot_pos
        # masking makes ragged co-batched requests exact.  Head axis is
        # KV-major ((B, KV*G, hd)), matching the kernel's head->KV map
        from repro.kernels.decode_attention.kernel import decode_attention
        out = decode_attention(q.reshape(B, H, hd),
                               k_cache.transpose(0, 2, 1, 3),
                               v_cache.transpose(0, 2, 1, 3),
                               slot_pos, cur_pos,
                               window=window, softmax_scale=scale)
    elif ctx is not None and ctx.decode_attn == "flash_decode":
        out = flash_decode.flash_decode(q, k_cache, v_cache, slot_pos, cur_pos,
                                        window=window, softmax_scale=scale,
                                        ctx=ctx)
    else:
        out = decode_attend(q, k_cache, v_cache,
                            slot_pos[0] if slot_pos.ndim == 2 else slot_pos,
                            cur_pos, window=window, softmax_scale=scale)
    out = out.reshape(B, H * hd)
    y = out @ params["wo"]
    new_cache = dict(cache, k=k_cache, v=v_cache, slot_pos=slot_pos)
    return x + y, new_cache


# ---------------------------------------------------------------------------
# MLA block (DeepSeek-V3 multi-head latent attention)
# ---------------------------------------------------------------------------

def init_mla(cfg, key, dtype=None):
    kg = KeyGen(key)
    dt = dtype or param_dtype(cfg)
    m = cfg.mla
    d, H = cfg.d_model, cfg.num_heads
    qd = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "ln": jnp.zeros((d,), dt),
        "wq_a": dense_init(kg(), (d, m.q_lora_rank), dt),
        "q_norm": jnp.zeros((m.q_lora_rank,), dt),
        "wq_b": dense_init(kg(), (m.q_lora_rank, H * qd), dt),
        "wkv_a": dense_init(kg(), (d, m.kv_lora_rank + m.qk_rope_head_dim), dt),
        "kv_norm": jnp.zeros((m.kv_lora_rank,), dt),
        "wkv_b": dense_init(kg(), (m.kv_lora_rank,
                                   H * (m.qk_nope_head_dim + m.v_head_dim)), dt),
        "wo": dense_init(kg(), (H * m.v_head_dim, d), dt,
                         scale=0.02 / max(1, cfg.num_layers) ** 0.5),
    }


def _mla_qkv_full(cfg, params, h, positions):
    m = cfg.mla
    B, S, _ = h.shape
    H = cfg.num_heads
    q = rms_norm(h @ params["wq_a"], params["q_norm"], cfg.norm_eps) @ params["wq_b"]
    q = q.reshape(B, S, H, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    pos = positions if positions.ndim == 2 else positions[None].repeat(B, 0)
    q_rope = apply_rope(q_rope, pos, cfg.rope_theta)

    kv = h @ params["wkv_a"]
    latent, k_rope = jnp.split(kv, [m.kv_lora_rank], axis=-1)
    latent = rms_norm(latent, params["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], pos, cfg.rope_theta)[:, :, 0]
    kvb = (latent @ params["wkv_b"]).reshape(
        B, S, H, m.qk_nope_head_dim + m.v_head_dim)
    k_nope, v = jnp.split(kvb, [m.qk_nope_head_dim], axis=-1)
    return q_nope, q_rope, k_nope, k_rope, v, latent


def apply_mla_full(cfg, params, x, *, positions, ctx, q_chunk: int = 1024,
                   **_):
    m = cfg.mla
    B, S, d = x.shape
    H = cfg.num_heads
    h = rms_norm(x, params["ln"], cfg.norm_eps)
    q_nope, q_rope, k_nope, k_rope, v, latent = _mla_qkv_full(
        cfg, params, h, positions)
    # assemble per-head q/k with shared rope part; treat as KV=H GQA (G=1)
    q = jnp.concatenate([q_nope, q_rope], -1)[:, :, :, None, :]  # (B,S,H,1,qd)
    q = q.transpose(0, 1, 2, 3, 4)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (*k_nope.shape[:3], m.qk_rope_head_dim))], -1)
    q = q.reshape(B, S, H, 1, -1)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    kp = positions if positions.ndim == 1 else positions[0]
    out = attend_chunked(q, k, v, q_pos=kp, k_pos=kp, window=None,
                         softmax_scale=scale, q_chunk=q_chunk)
    out = out.reshape(B, S, H * m.v_head_dim)
    y = out @ params["wo"]
    y = shard_residual(y, ctx)
    # MLA cache = compressed latent + shared rope key (what makes MLA special)
    return x + y, (latent, k_rope)


def apply_mla_step(cfg, params, x, *, cache, cur_pos, ctx, **_):
    """Decode with the latent cache in the *absorbed* form.

    Production MLA decode never re-expands per-token K/V for the whole cache:
    the per-head nope-query is absorbed through wkv_b's key half
    (q_lat[h] = q_nope[h] @ W_bk[h]^T) so attention runs directly in the
    (kv_lora + rope) latent space against the compressed cache — structurally
    MQA with a single shared 576-dim "kv head".  The attention output (a
    weighted sum of latents) is then expanded once per head through wkv_b's
    value half.  A :class:`CommittedCache` is read and not written: the
    token's slot (``latent``, ``k_rope``, ``slot_pos``, ``write_idx``) comes
    back in place of a new cache.
    """
    from repro.models import flash_decode

    m = cfg.mla
    B, d = x.shape[0], x.shape[-1]
    H = cfg.num_heads
    h = rms_norm(x, params["ln"], cfg.norm_eps)
    h3 = h[:, None, :]
    q_nope, q_rope, _kn, k_rope_new, _v, latent_new = _mla_qkv_full(
        cfg, params, h3, cur_pos[:, None])
    q_nope, q_rope = q_nope[:, 0], q_rope[:, 0]              # (B,H,*)

    # absorb q through the key half of wkv_b: (B,H,nope) -> (B,H,kv_lora)
    wkv_b = params["wkv_b"].reshape(
        m.kv_lora_rank, H, m.qk_nope_head_dim + m.v_head_dim)
    w_bk = wkv_b[:, :, :m.qk_nope_head_dim]                  # (lora,H,nope)
    w_bv = wkv_b[:, :, m.qk_nope_head_dim:]                  # (lora,H,v)
    q_lat = jnp.einsum("bhn,lhn->bhl", q_nope, w_bk)         # (B,H,lora)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5

    def expand(out_lat):                                     # to v-space
        out = jnp.einsum("bhl,lhv->bhv", out_lat.reshape(B, H, -1), w_bv)
        return x + out.reshape(B, H * m.v_head_dim) @ params["wo"]

    if isinstance(cache, CommittedCache):
        _committed_route(ctx)
        c = cache.cache
        write_idx = cur_pos % c["latent"].shape[1]
        lat_new = latent_new[:, 0].astype(c["latent"].dtype)
        kr_new = k_rope_new[:, 0].astype(c["k_rope"].dtype)

        def score(lat, kr, spec):
            # the latent and rope halves of one (lora + rope)-wide dot
            f32 = jnp.float32
            s = (jnp.einsum(spec.format("l"), q_lat, lat,
                            preferred_element_type=f32)
                 + jnp.einsum(spec.format("r"), q_rope, kr,
                              preferred_element_type=f32))
            return s.astype(q_lat.dtype).astype(f32)[:, None] * scale

        out = decode_attend_committed(
            score(c["latent"], c["k_rope"], "bh{0},bs{0}->bhs"),
            score(lat_new, kr_new, "bh{0},b{0}->bh"),
            c["latent"][:, :, None], lat_new[:, None], c["slot_pos"][0],
            write_idx, cur_pos, window=None, dtype=q_lat.dtype)
        return expand(out), {"latent": lat_new, "k_rope": kr_new,
                             "slot_pos": cur_pos, "write_idx": write_idx}

    n_slots = cache["latent"].shape[1]
    write_idx = cur_pos % n_slots
    bidx = jnp.arange(B)
    latent_c = cache["latent"].at[bidx, write_idx].set(
        latent_new[:, 0].astype(cache["latent"].dtype))
    krope_c = cache["k_rope"].at[bidx, write_idx].set(
        k_rope_new[:, 0].astype(cache["k_rope"].dtype))
    slot_pos = cache["slot_pos"].at[bidx, write_idx].set(cur_pos)

    # MQA over the latent cache: KV=1, G=H, hd = lora + rope
    q_cat = jnp.concatenate([q_lat, q_rope], -1)[:, None, :, :]  # (B,1,H,hd)
    k_cat = jnp.concatenate([latent_c, krope_c], -1)[:, :, None, :]
    v_lat = latent_c[:, :, None, :]                          # (B,S,1,lora)
    if ctx is not None and ctx.decode_attn == "flash_decode":
        out = flash_decode.flash_decode(q_cat, k_cat, v_lat, slot_pos, cur_pos,
                                        window=None, softmax_scale=scale,
                                        ctx=ctx, shard_kv_heads=False)
    else:
        out = decode_attend(q_cat, k_cat, v_lat,
                            slot_pos[0] if slot_pos.ndim == 2 else slot_pos,
                            cur_pos, window=None, softmax_scale=scale)
    new_cache = dict(cache, latent=latent_c, k_rope=krope_c, slot_pos=slot_pos)
    return expand(out), new_cache
