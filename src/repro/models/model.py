"""AnytimeModel: stage-structured (imprecise-computation) model orchestration.

Every architecture is a stack of blocks partitioned into `cfg.num_stages`
*stages* — the paper's schedulable unit.  Each stage ends in an exit head
(repro.models.exits).  Within a stage, layers are grouped into scanned
periods (bounding HLO size / compile time for the 61–96-layer configs) plus
explicit prefix/tail layers where the block pattern breaks periodicity
(e.g. DeepSeek's leading dense layers, Gemma-3's 34 = 5×6+4 remainder).

Public API
----------
init_params(cfg, key)                  -> params pytree
forward(cfg, params, inputs, ...)      -> ExitsOut (train / prefill)
decode_step(cfg, params, cache, ...)   -> (exits, new_cache)
DecodeFrom(h, stage)                   -> decode_step's resume carry
CommittedCache(stage_cache)            -> a stage decode_step reads only
write_decode_slots(caches, slots)      -> caches with the steps' slots written
init_decode_cache(cfg, batch, slots)   -> cache pytree
stage_forward / stage_decode_step      -> the scheduler's dispatch unit
count_params_analytic(cfg)             -> N (for roofline MODEL_FLOPS)
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import attention, exits, ffn, moe, ssm, xlstm
from repro.models.common import (CommittedCache, KeyGen, dense_init,
                                 param_dtype, shard)

FEATURE_DIM = 32  # input feature width for the "features" modality


# ---------------------------------------------------------------------------
# layer layout
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Sig:
    kind: str      # attn | attn_local | mamba | mlstm | slstm
    is_moe: bool


@dataclasses.dataclass(frozen=True)
class StageLayout:
    start: int
    end: int
    prefix: tuple            # absolute layer indices
    scan_start: int
    n_scan: int              # number of scanned periods (0 = no scan group)
    scan_sigs: tuple         # Sig per slot of one period
    tail: tuple              # absolute layer indices


def layer_sig(cfg, idx: int) -> Sig:
    kinds = cfg.layer_kinds()
    return Sig(kinds[idx], cfg.is_moe_layer(idx))


def _effective_period(cfg) -> int:
    p = len(cfg.period)
    if cfg.moe is not None:
        p = math.lcm(p, cfg.moe.moe_every)
    return p


def stage_layouts(cfg):
    bounds = cfg.stage_boundaries()
    out = []
    start = 0
    E = _effective_period(cfg)
    fd = cfg.moe.first_dense_layers if cfg.moe else 0
    for end in bounds:
        g0 = max(start, fd)
        n_scan = max(0, (end - g0) // E)
        if n_scan < 2:                       # not worth a scan group
            out.append(StageLayout(start, end, tuple(range(start, end)),
                                   end, 0, (), ()))
        else:
            sigs = tuple(layer_sig(cfg, g0 + j) for j in range(E))
            tail_start = g0 + n_scan * E
            out.append(StageLayout(start, end, tuple(range(start, g0)),
                                   g0, n_scan, sigs,
                                   tuple(range(tail_start, end))))
        start = end
    return tuple(out)


# ---------------------------------------------------------------------------
# per-layer init / apply
# ---------------------------------------------------------------------------

def _init_mixer(cfg, sig: Sig, key):
    if sig.kind in ("attn", "attn_local"):
        if cfg.attention == "mla":
            return attention.init_mla(cfg, key)
        return attention.init_gqa(cfg, key)
    if sig.kind == "mamba":
        return ssm.init_mamba(cfg, key)
    if sig.kind == "mlstm":
        return xlstm.init_mlstm(cfg, key)
    if sig.kind == "slstm":
        return xlstm.init_slstm(cfg, key)
    raise ValueError(sig.kind)


def init_layer(cfg, sig: Sig, key):
    kg = KeyGen(key)
    p = {"mixer": _init_mixer(cfg, sig, kg())}
    if sig.is_moe:
        p["ffn"] = moe.init_moe(cfg, kg())
    elif cfg.ffn_type != "none" and cfg.d_ff > 0 and sig.kind in ("attn", "attn_local", "mamba"):
        p["ffn"] = ffn.init_ffn(cfg, kg())
    return p


def apply_layer(cfg, sig: Sig, params, h, *, mode, cache=None,
                positions=None, cur_pos=None, ctx=None, q_chunk=1024):
    """Returns (h, cache_out, aux)."""
    aux = jnp.zeros((), jnp.float32)
    local = sig.kind == "attn_local"
    if isinstance(cache, CommittedCache) and not sig.kind.startswith("attn"):
        cache = cache.cache     # a recurrent state's slot is its new state
    if sig.kind in ("attn", "attn_local"):
        if mode == "step":
            if cfg.attention == "mla":
                h, c = attention.apply_mla_step(cfg, params["mixer"], h,
                                                cache=cache, cur_pos=cur_pos,
                                                ctx=ctx)
            else:
                h, c = attention.apply_gqa_step(cfg, params["mixer"], h,
                                                cache=cache, cur_pos=cur_pos,
                                                local=local, ctx=ctx)
        else:
            if cfg.attention == "mla":
                h, c = attention.apply_mla_full(cfg, params["mixer"], h,
                                                positions=positions, ctx=ctx,
                                                q_chunk=q_chunk)
            else:
                h, c = attention.apply_gqa_full(cfg, params["mixer"], h,
                                                positions=positions,
                                                local=local, ctx=ctx,
                                                q_chunk=q_chunk)
    elif sig.kind == "mamba":
        fn = ssm.apply_mamba_step if mode == "step" else ssm.apply_mamba_full
        h, c = fn(cfg, params["mixer"], h, cache=cache, ctx=ctx)
    elif sig.kind == "mlstm":
        fn = xlstm.apply_mlstm_step if mode == "step" else xlstm.apply_mlstm_full
        h, c = fn(cfg, params["mixer"], h, cache=cache, ctx=ctx)
    elif sig.kind == "slstm":
        fn = xlstm.apply_slstm_step if mode == "step" else xlstm.apply_slstm_full
        h, c = fn(cfg, params["mixer"], h, cache=cache, ctx=ctx)
    else:
        raise ValueError(sig.kind)

    if "ffn" in params:
        if sig.is_moe:
            h, aux = moe.apply_moe(cfg, params["ffn"], h, ctx=ctx)
        else:
            h = ffn.apply_ffn(cfg, params["ffn"], h, ctx=ctx)
    return h, c, aux


# ---------------------------------------------------------------------------
# embeddings
# ---------------------------------------------------------------------------

def init_embed(cfg, key):
    kg = KeyGen(key)
    dt = param_dtype(cfg)
    d, V = cfg.d_model, cfg.vocab_size
    if cfg.modality == "features":
        return {"w_in": dense_init(kg(), (FEATURE_DIM, d), dt, scale=0.1)}
    if cfg.modality == "audio_stub":
        return {"tok": dense_init(kg(), (cfg.num_codebooks, V, d), dt)}
    return {"tok": dense_init(kg(), (V, d), dt)}


def apply_embed(cfg, params, inputs, ctx=None):
    """Returns (h (B,S,d), positions (S,))."""
    if cfg.modality == "features":
        h = inputs["features"] @ params["w_in"]
    elif cfg.modality == "audio_stub":
        toks = inputs["tokens"]                  # (B, ncb, S)
        h = jnp.zeros((*toks.shape[::2], cfg.d_model), params["tok"].dtype)
        parts = [jnp.take(params["tok"][c], toks[:, c], axis=0)
                 for c in range(cfg.num_codebooks)]
        h = sum(parts)
    elif cfg.modality == "vision_stub":
        tok_emb = jnp.take(params["tok"], inputs["tokens"], axis=0)
        h = jnp.concatenate(
            [inputs["patch_embeds"].astype(tok_emb.dtype), tok_emb], axis=1)
    else:
        h = jnp.take(params["tok"], inputs["tokens"], axis=0)
    S = h.shape[1]
    if ctx is not None:
        h = shard(h, ctx, ctx.dp, None, None)
    return h, jnp.arange(S, dtype=jnp.int32)


def embed_one(cfg, params_embed, token, cur_pos):
    """Decode-time embedding of a single token. token: (B,) or (B,ncb);
    features modality: a (B, FEATURE_DIM) frame (or {"features": ...})."""
    if cfg.modality == "features":
        feats = token["features"] if isinstance(token, dict) else token
        return feats @ params_embed["w_in"]
    if cfg.modality == "audio_stub":
        parts = [jnp.take(params_embed["tok"][c], token[:, c], axis=0)
                 for c in range(cfg.num_codebooks)]
        return sum(parts)
    return jnp.take(params_embed["tok"], token, axis=0)


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def _stack_consuming(trees: list):
    """``jnp.stack`` same-structured pytrees leaf by leaf, releasing each
    source leaf once it is stacked (``trees`` is emptied): peak memory is
    the inputs plus one stacked leaf, not two copies of a whole stage —
    what keeps a full-width stage init inside one chip's HBM."""
    treedef = jax.tree.structure(trees[0])
    cols = list(zip(*(jax.tree.leaves(t) for t in trees)))
    trees.clear()
    cols.reverse()
    out = []
    while cols:
        out.append(jnp.stack(cols.pop()))
    return jax.tree.unflatten(treedef, out)


def init_params(cfg, key):
    kg = KeyGen(key)
    layouts = stage_layouts(cfg)
    stages = []
    for lay in layouts:
        sp: dict = {"prefix": [init_layer(cfg, layer_sig(cfg, i), kg())
                               for i in lay.prefix]}
        if lay.n_scan:
            periods = []
            for _ in range(lay.n_scan):
                periods.append(tuple(init_layer(cfg, s, kg())
                                     for s in lay.scan_sigs))
            sp["scan"] = _stack_consuming(periods)
        sp["tail"] = [init_layer(cfg, layer_sig(cfg, i), kg())
                      for i in lay.tail]
        stages.append(sp)
    params = {
        "embed": init_embed(cfg, kg()),
        "stages": stages,
        "exits": [exits.init_exit(cfg, kg()) for _ in layouts],
        "exit_shared": exits.init_exit(cfg, kg(), shared=True),
    }
    if cfg.mtp:
        params["mtp"] = {
            "proj": dense_init(kg(), (2 * cfg.d_model, cfg.d_model),
                               param_dtype(cfg)),
            "block": init_layer(cfg, Sig("attn", False), kg()),
            "exit": exits.init_exit(cfg, kg()),
        }
    return params


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ExitsOut:
    logits: list               # per stage
    confidences: list          # per stage, (B,) or (B,S)
    aux: Any                   # router aux loss (scalar)
    h_final: Any
    caches: Optional[list]     # per stage: layer caches (prefill only)


jax.tree_util.register_dataclass(
    ExitsOut,
    data_fields=["logits", "confidences", "aux", "h_final", "caches"],
    meta_fields=[])


def _stage_apply_full(cfg, stage_params, lay: StageLayout, h, *, mode,
                      positions, ctx, collect_cache, q_chunk):
    aux_total = jnp.zeros((), jnp.float32)
    caches: dict = {"prefix": [], "scan": None, "tail": []}

    def one(idx, p, h):
        return apply_layer(cfg, layer_sig(cfg, idx), p, h, mode=mode,
                           positions=positions, ctx=ctx, q_chunk=q_chunk)

    for i, p in zip(lay.prefix, stage_params["prefix"]):
        h, c, aux = one(i, p, h)
        aux_total += aux
        caches["prefix"].append(c if collect_cache else None)

    if lay.n_scan:
        sigs = lay.scan_sigs

        def period_body(h, period_params):
            aux_p = jnp.zeros((), jnp.float32)
            cs = []
            hh = h
            for sig, p in zip(sigs, period_params):
                hh, c, aux = apply_layer(cfg, sig, p, hh, mode=mode,
                                         positions=positions, ctx=ctx,
                                         q_chunk=q_chunk)
                aux_p += aux
                cs.append(c if collect_cache else 0)
            return hh, (aux_p, tuple(cs))

        body = period_body
        if ctx is not None and ctx.remat and mode == "train":
            body = jax.checkpoint(period_body)

        def scan_body(carry, period_params):
            h, aux_acc = carry
            h, (aux_p, cs) = body(h, period_params)
            return (h, aux_acc + aux_p), cs

        (h, aux_total), scan_caches = jax.lax.scan(
            scan_body, (h, aux_total), stage_params["scan"])
        caches["scan"] = scan_caches if collect_cache else None

    for i, p in zip(lay.tail, stage_params["tail"]):
        h, c, aux = one(i, p, h)
        aux_total += aux
        caches["tail"].append(c if collect_cache else None)

    return h, aux_total, (caches if collect_cache else None)


def forward(cfg, params, inputs, *, ctx=None, mode="train", upto_stage=None,
            collect_cache=None, q_chunk=1024, conf_temperature=1.0,
            exit_last_only=False, aux_exit_stride=1):
    """Full-sequence forward through (up to) `upto_stage` stages.

    exit_last_only: compute exit heads on the final position only (prefill
    serving path — avoids materializing (B, S, V) logits per exit).
    aux_exit_stride: evaluate non-final exits every k-th position only
    (training FLOPs; see make_loss_fn)."""
    if collect_cache is None:
        collect_cache = mode == "prefill"
    layouts = stage_layouts(cfg)
    n_stages = len(layouts) if upto_stage is None else upto_stage
    h, positions = apply_embed(cfg, params["embed"], inputs, ctx)
    aux_total = jnp.zeros((), jnp.float32)
    logits_list, conf_list, cache_list = [], [], []
    for s in range(n_stages):
        h, aux, caches = _stage_apply_full(
            cfg, params["stages"][s], layouts[s], h, mode=mode,
            positions=positions, ctx=ctx, collect_cache=collect_cache,
            q_chunk=q_chunk)
        aux_total += aux
        h_exit = h
        if h.ndim == 3 and cfg.modality != "features":
            if exit_last_only:
                h_exit = h[:, -1:]
            elif (aux_exit_stride > 1 and s < n_stages - 1
                  and h.shape[1] % aux_exit_stride == 0):
                h_exit = h[:, ::aux_exit_stride]
        lg = exits.apply_exit(
            cfg, {**params["exits"][s], **params["exit_shared"]}, h_exit,
            ctx=ctx)
        logits_list.append(lg)
        conf = exits.confidence_from_logits(lg, conf_temperature)
        if conf.ndim > 1:   # reduce codebook axis for audio; keep (B,) / (B,S)
            while conf.ndim > 2:
                conf = conf.mean(-1)
        conf_list.append(conf)
        cache_list.append(caches)
    return ExitsOut(logits_list, conf_list, aux_total, h,
                    cache_list if collect_cache else None)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def _layer_cache_struct(cfg, sig: Sig, batch, slots, dtype):
    hd = cfg.resolved_head_dim
    if sig.kind in ("attn", "attn_local"):
        if sig.kind == "attn_local" and cfg.sliding_window:
            slots_l = min(slots, cfg.sliding_window)
        else:
            slots_l = slots
        if cfg.attention == "mla":
            m = cfg.mla
            return {"latent": jnp.zeros((batch, slots_l, m.kv_lora_rank), dtype),
                    "k_rope": jnp.zeros((batch, slots_l, m.qk_rope_head_dim), dtype),
                    "slot_pos": jnp.full((batch, slots_l), -1, jnp.int32)}
        return {"k": jnp.zeros((batch, slots_l, cfg.num_kv_heads, hd), dtype),
                "v": jnp.zeros((batch, slots_l, cfg.num_kv_heads, hd), dtype),
                "slot_pos": jnp.full((batch, slots_l), -1, jnp.int32)}
    if sig.kind == "mamba":
        di = ssm.d_inner_of(cfg)
        return {"ssm_state": jnp.zeros((batch, di, cfg.ssm_d_state), jnp.float32),
                "conv_state": jnp.zeros((batch, cfg.ssm_d_conv - 1, di), dtype)}
    if sig.kind == "mlstm":
        di = xlstm.mlstm_d_inner(cfg)
        return {"mlstm": xlstm.init_mlstm_state(cfg, batch),
                "conv_state": jnp.zeros((batch, 3, di), dtype)}
    if sig.kind == "slstm":
        return {"slstm": xlstm.init_slstm_state(cfg, batch)}
    raise ValueError(sig.kind)


def init_decode_cache(cfg, batch, slots, dtype=None):
    """Zero-initialized decode cache mirroring the stage/scan structure.

    `slots` = number of KV slots for full-attention layers; sliding-window
    layers allocate min(slots, window); the swa-8192 long-context variant
    passes slots=8192 for every full-attention layer.
    """
    dtype = dtype or param_dtype(cfg)
    layouts = stage_layouts(cfg)
    out = []
    for lay in layouts:
        st = {"prefix": [_layer_cache_struct(cfg, layer_sig(cfg, i), batch,
                                             slots, dtype)
                         for i in lay.prefix],
              "scan": None}
        if lay.n_scan:
            one_period = tuple(_layer_cache_struct(cfg, s, batch, slots, dtype)
                               for s in lay.scan_sigs)
            st["scan"] = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (lay.n_scan, *x.shape)).copy()
                if isinstance(x, jnp.ndarray) else x, one_period)
        st["tail"] = [_layer_cache_struct(cfg, layer_sig(cfg, i), batch,
                                          slots, dtype)
                      for i in lay.tail]
        out.append(st)
    return out


def slice_decode_cache(st_cache, i: int, j: int = None):
    """Rows [i:j) of one stage's decode cache (default the single row i).

    Structure-aware: prefix/tail layer caches carry batch on axis 0, scan
    caches are stacked over periods so batch sits on axis 1.  This is how
    a serving executor keeps per-request cache state while batching
    co-runners: slice rows out of a batched step, concat them back in
    (:func:`concat_decode_caches`) for the next dispatch.
    """
    j = i + 1 if j is None else j
    out = {"prefix": [jax.tree.map(lambda x: x[i:j], c)
                      for c in st_cache["prefix"]],
           "scan": None,
           "tail": [jax.tree.map(lambda x: x[i:j], c)
                    for c in st_cache["tail"]]}
    if st_cache["scan"] is not None:
        out["scan"] = jax.tree.map(lambda x: x[:, i:j], st_cache["scan"])
    return out


def concat_decode_caches(st_caches):
    """Concatenate same-stage decode caches along the batch axis (the
    inverse of :func:`slice_decode_cache`).  All members must share the
    same slot count — in serving terms, the same length bucket."""
    first = st_caches[0]
    cat = lambda axis: (lambda *xs: jnp.concatenate(xs, axis=axis))
    out = {"prefix": [jax.tree.map(cat(0), *[c["prefix"][k]
                                             for c in st_caches])
                      for k in range(len(first["prefix"]))],
           "scan": None,
           "tail": [jax.tree.map(cat(0), *[c["tail"][k]
                                           for c in st_caches])
                    for k in range(len(first["tail"]))]}
    if first["scan"] is not None:
        out["scan"] = jax.tree.map(cat(1), *[c["scan"] for c in st_caches])
    return out


@dataclasses.dataclass(frozen=True)
class DecodeFrom:
    """Where a decode step resumes: ``h`` is the (B, d) hidden state that
    stage ``stage`` takes as input — a shallower step's ``h_final``.
    ``stage`` is static, so each resume point is its own jitted program."""
    h: Any
    stage: int


jax.tree_util.register_dataclass(DecodeFrom, data_fields=["h"],
                                 meta_fields=["stage"])


def decode_step(cfg, params, cache, token, cur_pos, *, ctx=None,
                upto_stage=None, conf_temperature=1.0):
    """One decode step through (up to) `upto_stage` stages.

    token: (B,) int32 (or (B,ncb) audio), or a :class:`DecodeFrom` to
    resume at a stage boundary: the embedding and stages before
    ``token.stage`` are skipped, so a step of depth d+1 continues depth
    d's step from its ``h_final`` and new cache.  cur_pos: (B,) int32.
    Returns (ExitsOut with last-position logits per stage run, new_cache).
    From a token, new_cache forwards the plain stages not run; a resumed
    step, and a :class:`CommittedCache` stage not run, give None in their
    place, for the caller holds them already: a jitted output that is an
    unchanged input is a full copy on the device.

    A stage given as a :class:`CommittedCache` is read and not written:
    its entry of new_cache is the token's slot, the stage's cache
    structure with each layer's slot in place of its cache (what
    :func:`write_decode_slots` writes), and no output is as large as a
    layer's cache.
    """
    layouts = stage_layouts(cfg)
    n_stages = len(layouts) if upto_stage is None else upto_stage
    if isinstance(token, DecodeFrom):
        first, h = token.stage, token.h
        new_cache = [None] * len(layouts)
    else:
        first, h = 0, embed_one(cfg, params["embed"], token, cur_pos)
        new_cache = [None if isinstance(c, CommittedCache) else c
                     for c in cache]
    if ctx is not None:
        h = shard(h, ctx, ctx.dp, None)                      # (B, d)
    logits_list, conf_list = [], []
    for s in range(first, n_stages):
        h, st_cache = _stage_decode(cfg, params["stages"][s], layouts[s],
                                    cache[s], h, cur_pos, ctx)
        new_cache[s] = st_cache
        lg = exits.apply_exit(
            cfg, {**params["exits"][s], **params["exit_shared"]},
            h[:, None], ctx=ctx)
        lg = lg[:, 0]                                        # (B, V) / (B,ncb,V)
        logits_list.append(lg)
        conf = exits.confidence_from_logits(lg, conf_temperature)
        while conf.ndim > 1:
            conf = conf.mean(-1)
        conf_list.append(conf)
    return ExitsOut(logits_list, conf_list, jnp.zeros((), jnp.float32),
                    h, None), new_cache


def _stage_decode(cfg, stage_params, lay: StageLayout, st_cache, h, cur_pos,
                  ctx):
    """One stage of a decode step: (h, the stage's new cache), or its
    slots where ``st_cache`` is a :class:`CommittedCache`."""
    wrap = lambda c: c
    if isinstance(st_cache, CommittedCache):
        st_cache, wrap = st_cache.cache, CommittedCache

    def one(idx, p, c, h):
        h, c_new, _ = apply_layer(cfg, layer_sig(cfg, idx), p, h, mode="step",
                                  cache=wrap(c), cur_pos=cur_pos, ctx=ctx)
        return h, c_new

    new_cache: dict = {"prefix": [], "scan": None, "tail": []}
    for i, p, c in zip(lay.prefix, stage_params["prefix"], st_cache["prefix"]):
        h, c_new = one(i, p, c, h)
        new_cache["prefix"].append(c_new)

    if lay.n_scan:
        sigs = lay.scan_sigs

        def scan_body(h, pc):
            period_params, period_cache = pc
            cs = []
            for sig, p, c in zip(sigs, period_params, period_cache):
                h, c_new, _ = apply_layer(cfg, sig, p, h, mode="step",
                                          cache=wrap(c), cur_pos=cur_pos,
                                          ctx=ctx)
                cs.append(c_new)
            return h, tuple(cs)

        h, scan_cache = jax.lax.scan(
            scan_body, h, (stage_params["scan"], st_cache["scan"]))
        new_cache["scan"] = scan_cache

    for i, p, c in zip(lay.tail, stage_params["tail"], st_cache["tail"]):
        h, c_new = one(i, p, c, h)
        new_cache["tail"].append(c_new)
    return h, new_cache


def _write_layer(cache, slot):
    if "write_idx" not in slot:       # a recurrent layer: its new state
        return slot
    rows = jnp.arange(slot["write_idx"].shape[0])
    return {k: x.at[rows, slot["write_idx"]].set(slot[k].astype(x.dtype))
            for k, x in cache.items()}


def write_decode_slots(st_caches, st_slots):
    """Stage caches with the slots that steps on their
    :class:`CommittedCache` returned written in: ``st_caches`` and
    ``st_slots`` pair stage by stage.  Jitted with the caches donated,
    the write is in place."""
    out = []
    for c, s in zip(st_caches, st_slots):
        st = {"prefix": [_write_layer(a, b)
                         for a, b in zip(c["prefix"], s["prefix"])],
              "scan": None,
              "tail": [_write_layer(a, b)
                       for a, b in zip(c["tail"], s["tail"])]}
        if c["scan"] is not None:
            st["scan"] = jax.vmap(lambda cs, ss: tuple(map(
                _write_layer, cs, ss)))(c["scan"], s["scan"])
        out.append(st)
    return out


# ---------------------------------------------------------------------------
# stage-granular API (the scheduler's dispatch unit)
# ---------------------------------------------------------------------------

def stage_trunk(cfg, params, stage_idx: int, h_or_inputs, *, ctx=None,
                q_chunk=1024, mode="prefill"):
    """ONE stage's trunk (embed + blocks), *without* the exit head.

    stage 0 takes raw inputs (embeds them); later stages take hidden
    state.  Returns the stage-out hidden state (B, S, d).  This is the
    seam the kernel-backed stage fns build on: run the trunk here, then a
    fused exit epilogue (repro.models.exits.exit_stats_fused) instead of
    materializing the full logits tensor.
    """
    layouts = stage_layouts(cfg)
    lay = layouts[stage_idx]
    if stage_idx == 0:
        h, positions = apply_embed(cfg, params["embed"], h_or_inputs, ctx)
    else:
        h = h_or_inputs
        positions = jnp.arange(h.shape[1], dtype=jnp.int32)
    h, _aux, _ = _stage_apply_full(cfg, params["stages"][stage_idx], lay, h,
                                   mode=mode, positions=positions, ctx=ctx,
                                   collect_cache=False, q_chunk=q_chunk)
    return h


def stage_forward(cfg, params, stage_idx: int, h_or_inputs, *, ctx=None,
                  q_chunk=1024, conf_temperature=1.0, mode="prefill"):
    """Run ONE stage (paper's non-preemptive unit) and its exit head.

    stage 0 takes raw inputs (embeds them); later stages take hidden state.
    Returns (h, logits, confidence).
    """
    h = stage_trunk(cfg, params, stage_idx, h_or_inputs, ctx=ctx,
                    q_chunk=q_chunk, mode=mode)
    lg = exits.apply_exit(
        cfg, {**params["exits"][stage_idx], **params["exit_shared"]}, h,
        ctx=ctx)
    conf = exits.confidence_from_logits(lg, conf_temperature)
    while conf.ndim > 1:
        conf = conf.mean(-1)
    return h, lg, conf


def stage_decode_step(cfg, params, stage_idx: int, st_cache, h, cur_pos, *,
                      ctx=None):
    """ONE stage of a decode step over its per-stage cache (the decode-mode
    dispatch unit: the serving engine holds per-request caches device-side
    and batches co-runners at the same stage through this function).

    stage 0 takes the raw token(s) (embeds them); later stages take hidden
    state.  ``st_cache`` is ``init_decode_cache(...)[stage_idx]``.  Routing
    ``ctx.decode_attn == "kernel"`` runs attention through the Pallas
    decode kernel, whose per-row slot_pos masking keeps ragged co-batched
    requests exact.  Returns (h, new_st_cache).
    """
    lay = stage_layouts(cfg)[stage_idx]
    if stage_idx == 0:
        h = embed_one(cfg, params["embed"], h, cur_pos)      # (B, d)
        if ctx is not None:
            h = shard(h, ctx, ctx.dp, None)
    return _stage_decode(cfg, params["stages"][stage_idx], lay, st_cache, h,
                         cur_pos, ctx)


# ---------------------------------------------------------------------------
# analytic parameter counts (roofline MODEL_FLOPS)
# ---------------------------------------------------------------------------

def count_params_analytic(cfg, active_only: bool = False) -> int:
    shapes = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    total = sum(int(math.prod(l.shape)) for l in jax.tree.leaves(shapes))
    if active_only and cfg.moe is not None:
        m = cfg.moe
        n_moe = sum(cfg.is_moe_layer(i) for i in range(cfg.num_layers))
        per_expert = 3 * cfg.d_model * m.d_ff_expert
        if cfg.ffn_type == "relu2":
            per_expert = 2 * cfg.d_model * m.d_ff_expert
        total -= n_moe * (m.num_experts - m.top_k) * per_expert
    return total
