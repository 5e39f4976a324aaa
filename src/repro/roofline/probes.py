"""Scan-aware cost probes.

`cost_analysis()` counts a `lax.scan` body once (verified empirically), so
full-model compiles undercount scanned layers, chunked recurrences, and
q-chunked attention.  Instead of trusting one number, we compile *per
layer-kind probes* at scan-free sizes and extrapolate with the kind's known
scaling law, then compose:

  total(S) = Σ_kind count_kind × cost_kind(S) + head(S)

  attn / attn+moe        cost(S) = a·S + b·S²   (fit from two scan-free
                                                 probe points; the chunked
                                                 production path computes the
                                                 same masked S² work)
  attn_local (window w)  cost(S) = a + b·S      (block-local path, probed at
                                                 2w and 4w)
  mamba / mlstm          cost(S) ∝ S            (single-chunk probe × S/chunk
                                                 — chunked recurrences do
                                                 fixed work per chunk)
  slstm                  cost(S) ∝ S            (python-loop probe over 32
                                                 steps × S/32)
  decode (any kind)      exact single compile   (no scans; real cache size)
  head (embed+exits+loss) exact single compile  (no scans)

Each probe lowers with the production shardings on the production mesh, so
collective bytes parsed from its optimized HLO scale identically.
"""
from __future__ import annotations

from collections import Counter

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.roofline.collectives import collective_bytes_from_hlo

METRICS = ("flops", "bytes", "coll")


def _compile_cost(fn, args, shardings=None):
    jitted = jax.jit(fn, in_shardings=shardings)
    compiled = jitted.lower(*args).compile()
    ca = compiled.cost_analysis() or {}
    coll = collective_bytes_from_hlo(compiled.as_text())
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll": float(coll["total"])}


def _fit_linear(c1, s1, c2, s2):
    """cost = a + b*S from two points."""
    out = {}
    for m in METRICS:
        b = (c2[m] - c1[m]) / (s2 - s1)
        a = c1[m] - b * s1
        out[m] = (a, b)
    return out


def _fit_quad(c1, s1, c2, s2):
    """cost = a*S + b*S^2 from two points."""
    out = {}
    for m in METRICS:
        # solve a*s1 + b*s1^2 = c1 ; a*s2 + b*s2^2 = c2
        det = s1 * s2 * s2 - s2 * s1 * s1
        b = (c2[m] * s1 - c1[m] * s2) / det
        a = (c1[m] - b * s1 * s1) / s1
        out[m] = (a, b)
    return out


def _eval_linear(fit, S):
    return {m: max(0.0, fit[m][0] + fit[m][1] * S) for m in METRICS}


def _eval_quad(fit, S):
    return {m: max(0.0, fit[m][0] * S + fit[m][1] * S * S) for m in METRICS}


def _layer_fn(cfg, sig, ctx, mode, q_chunk, cur_slots=None):
    from repro.models.model import apply_layer

    def fwd(layer_params, h, *extra):
        # NOTE: reduce in the model dtype so backward cotangents are bf16,
        # matching the real CE-loss backward (an f32 probe loss doubles the
        # measured collective/memory traffic — §Perf iteration 3 finding)
        if mode == "step":
            cache, cur_pos = extra
            h2, _, aux = apply_layer(cfg, sig, layer_params, h, mode="step",
                                     cache=cache, cur_pos=cur_pos, ctx=ctx)
            return jnp.sum(h2).astype(jnp.float32) + aux
        positions = jnp.arange(h.shape[1], dtype=jnp.int32)
        h2, _, aux = apply_layer(cfg, sig, layer_params, h, mode="full",
                                 positions=positions, ctx=ctx,
                                 q_chunk=q_chunk)
        return jnp.sum(h2).astype(jnp.float32) + aux

    return fwd


def _probe_layer(cfg, sig, ctx, mesh, *, batch, seq, mode, train,
                 cache_slots=None):
    """Compile one layer (+grad when train) at (batch, seq)."""
    from repro.launch.shardings import cache_shardings, param_shardings
    from repro.models.model import _layer_cache_struct, init_layer

    params = jax.eval_shape(
        lambda k: init_layer(cfg, sig, k),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    from repro.launch.shardings import (decode_weight_layout,
                                        expert_templates_for)
    etpl = expert_templates_for(cfg, mesh, ctx.dp, ctx.moe_impl)
    layout = decode_weight_layout(cfg, mesh) if mode == "step" else "2d"
    p_sh = param_shardings(mesh, params, etpl, layout=layout)
    dt = jnp.dtype(cfg.dtype)
    if mode == "step":
        h = jax.ShapeDtypeStruct((batch, cfg.d_model), dt)
        cache = jax.eval_shape(lambda: _layer_cache_struct(
            cfg, sig, batch, cache_slots, dt))
        c_sh = cache_shardings(mesh, cache, ctx.dp, ctx.seq_axes)
        bdp = tuple(a for a in ctx.dp if a not in ctx.seq_axes) or None
        h_sh = NamedSharding(mesh, P(bdp, None))
        pos_sh = NamedSharding(mesh, P(bdp))
        fn = _layer_fn(cfg, sig, ctx, "step", 0)
        args = (params, h, cache,
                jax.ShapeDtypeStruct((batch,), jnp.int32))
        shardings = (p_sh, h_sh, c_sh, pos_sh)
    else:
        h = jax.ShapeDtypeStruct((batch, seq, cfg.d_model), dt)
        h_sh = NamedSharding(mesh, P(ctx.dp, None, None))
        fn = _layer_fn(cfg, sig, ctx, "full", q_chunk=seq)
        args = (params, h)
        shardings = (p_sh, h_sh)
    if train:
        base = fn
        fn = lambda *a: jax.value_and_grad(base)(*a)  # noqa: E731
    with jax.set_mesh(mesh):
        return _compile_cost(fn, args, shardings)


def _probe_slstm(cfg, ctx, mesh, *, batch, seq_probe, train):
    """Python-loop sLSTM probe (scan-free) over seq_probe steps."""
    from repro.launch.shardings import param_shardings
    from repro.models import xlstm as xl

    params = jax.eval_shape(
        lambda k: xl.init_slstm(cfg, k),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    p_sh = param_shardings(mesh, params)
    dt = jnp.dtype(cfg.dtype)

    def fwd(p, x):
        from repro.models.common import rms_norm
        h_in = rms_norm(x, p["ln"], cfg.norm_eps)
        wx = h_in @ p["W"]
        state = xl.init_slstm_state(cfg, x.shape[0])
        hs = []
        for t in range(seq_probe):
            state = xl.slstm_step_core(cfg, p, wx[:, t], state)
            hs.append(state[0])
        h = jnp.stack(hs, 1)
        h = xl._group_norm(h, p["gn"], cfg.num_heads)
        y = x + h
        y = xl._slstm_mlp(cfg, p, y)
        return jnp.sum(y).astype(jnp.float32)

    if train:
        base = fwd
        fwd = lambda *a: jax.value_and_grad(base)(*a)  # noqa: E731
    x = jax.ShapeDtypeStruct((batch, seq_probe, cfg.d_model), dt)
    h_sh = NamedSharding(mesh, P(ctx.dp, None, None))
    with jax.set_mesh(mesh):
        return _compile_cost(fwd, (params, x), (p_sh, h_sh))


def probe_head(cfg, ctx, mesh, *, batch, seq, train):
    from repro.launch.shardings import batch_shardings, param_shardings
    from repro.launch.steps import label_spec, model_inputs_spec
    from repro.models import exits as ex
    from repro.models.model import apply_embed, init_embed
    from repro.training.loop import _exit_loss

    def init_sub(k):
        from repro.models.common import KeyGen
        kg = KeyGen(k)
        return {"embed": init_embed(cfg, kg()),
                "exits": [ex.init_exit(cfg, kg())
                          for _ in range(cfg.num_stages)],
                "exit_shared": ex.init_exit(cfg, kg(), shared=True)}

    params = jax.eval_shape(init_sub, jax.ShapeDtypeStruct((2,), jnp.uint32))
    p_sh = param_shardings(mesh, params)
    inputs = model_inputs_spec(cfg, batch, seq)
    in_sh = batch_shardings(mesh, inputs, ctx.dp)

    stride = 4 if (train and cfg.vocab_size >= 32768) else 1

    def fwd(p, inputs, labels=None):
        h, _ = apply_embed(cfg, p["embed"], inputs, ctx)
        total = jnp.zeros((), jnp.float32)
        for s in range(cfg.num_stages):
            hs = h
            lb = labels
            if (stride > 1 and s < cfg.num_stages - 1 and h.ndim == 3
                    and cfg.modality in ("text", "vision_stub")
                    and h.shape[1] % stride == 0):
                hs = h[:, ::stride]
                lb = labels[:, ::stride] if labels is not None else None
            lg = ex.apply_exit(cfg, {**p["exits"][s], **p["exit_shared"]},
                               hs, ctx=ctx)
            if lb is not None:
                total += _exit_loss(cfg, lg, lb)
            else:
                total += jnp.sum(
                    ex.confidence_from_logits(lg).astype(jnp.float32))
        return total

    if train:
        labels = label_spec(cfg, batch, seq)
        l_sh = batch_shardings(mesh, {"l": labels}, ctx.dp)["l"]
        fn = lambda p, i, l: jax.value_and_grad(fwd)(p, i, l)  # noqa: E731
        args = (params, inputs, labels)
        shardings = (p_sh, in_sh, l_sh)
    else:
        fn = fwd
        args = (params, inputs)
        shardings = (p_sh, in_sh)
    with jax.set_mesh(mesh):
        return _compile_cost(fn, args, shardings)


def probe_combo(cfg, shape, mesh, ctx, *, q_chunk=1024):
    """Composed cost estimate for one (arch × shape × mesh)."""
    from repro.launch.steps import decode_cache_slots, uses_swa_variant
    from repro.models import ssm as ssm_mod
    from repro.models import xlstm as xl_mod
    from repro.models.model import layer_sig

    B, S = shape.global_batch, shape.seq_len
    train = shape.kind == "train"
    mode = "step" if shape.kind == "decode" else "full"
    counts = Counter(layer_sig(cfg, i) for i in range(cfg.num_layers))

    per_kind = {}
    totals = {m: 0.0 for m in METRICS}
    for sig, n in counts.items():
        key = f"{sig.kind}{'+moe' if sig.is_moe else ''}"
        if mode == "step":
            slots = decode_cache_slots(cfg, shape)
            cost = _probe_layer(cfg, sig, ctx, mesh, batch=B, seq=1,
                                mode="step", train=False, cache_slots=slots)
        elif sig.kind in ("attn", "attn_local") and not (
                sig.kind == "attn_local" and cfg.sliding_window
                and S > 2 * cfg.sliding_window):
            # quadratic fit from two scan-free points; keep extrapolation
            # <= 4x (far extrapolation amplifies fit noise ~ (S/s2)^2)
            s1 = min(S, max(1024, S // 4))
            s2 = min(S, max(2048, S // 2)) if S > 1024 else S
            if s1 == s2:
                cost = _probe_layer(cfg, sig, ctx, mesh, batch=B, seq=S,
                                    mode="full", train=train)
            else:
                c1 = _probe_layer(cfg, sig, ctx, mesh, batch=B, seq=s1,
                                  mode="full", train=train)
                c2 = _probe_layer(cfg, sig, ctx, mesh, batch=B, seq=s2,
                                  mode="full", train=train)
                cost = _eval_quad(_fit_quad(c1, s1, c2, s2), S)
        elif sig.kind == "attn_local":
            w = cfg.sliding_window
            c1 = _probe_layer(cfg, sig, ctx, mesh, batch=B, seq=2 * w,
                              mode="full", train=train)
            c2 = _probe_layer(cfg, sig, ctx, mesh, batch=B, seq=4 * w,
                              mode="full", train=train)
            cost = _eval_linear(_fit_linear(c1, 2 * w, c2, 4 * w), S)
        elif sig.kind == "mamba":
            sp = min(S, ssm_mod.CHUNK)
            c = _probe_layer(cfg, sig, ctx, mesh, batch=B, seq=sp,
                             mode="full", train=train)
            cost = {m: c[m] * S / sp for m in METRICS}
        elif sig.kind == "mlstm":
            sp = min(S, xl_mod.MLSTM_CHUNK)
            c = _probe_layer(cfg, sig, ctx, mesh, batch=B, seq=sp,
                             mode="full", train=train)
            cost = {m: c[m] * S / sp for m in METRICS}
        elif sig.kind == "slstm":
            sp = min(S, 32)
            c = _probe_slstm(cfg, ctx, mesh, batch=B, seq_probe=sp,
                             train=train)
            cost = {m: c[m] * S / sp for m in METRICS}
        else:
            raise ValueError(sig.kind)
        per_kind[key] = {"count": n, **{m: cost[m] for m in METRICS}}
        for m in METRICS:
            totals[m] += n * cost[m]

    head = probe_head(cfg, ctx, mesh, batch=B,
                      seq=1 if mode == "step" else S, train=train)
    for m in METRICS:
        totals[m] += head[m]
    return {"per_kind": per_kind, "head": head, "totals": totals,
            "swa_variant": uses_swa_variant(cfg, shape)}
