"""Mixture-of-Experts sublayer with expert parallelism.

Baseline impl ("gather"): capacity-bounded sort-based dispatch under GSPMD —
tokens are ranked within their expert via an argsort (no T×E×C one-hot
einsums), gathered into an (E, C, d) buffer, pushed through the stacked expert
FFNs (experts sharded over the 'model' axis = expert parallelism), and
scatter-added back weighted by their gates.

Optimized impl ("alltoall"): shard_map version where each data shard routes
locally and exchanges expert buffers with an explicit all_to_all over the
expert-parallel axis (see EXPERIMENTS.md §Perf).

Routing: softmax router, top-k, renormalized gates, Switch-style load-balance
auxiliary loss.  Over-capacity tokens are dropped (capacity_factor bounds the
buffer, as in GShard/Switch).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.models.common import shard_residual, KeyGen, dense_init, param_dtype, rms_norm, shard
from repro.models.ffn import ffn_core, init_ffn


def init_moe(cfg, key, dtype=None):
    kg = KeyGen(key)
    dt = dtype or param_dtype(cfg)
    m = cfg.moe
    d, fe, E = cfg.d_model, m.d_ff_expert, m.num_experts
    down_scale = 0.02 / max(1, cfg.num_layers) ** 0.5
    p = {
        "ln": jnp.zeros((d,), dt),
        "router": dense_init(kg(), (d, E), jnp.float32),
        "we_gate": dense_init(kg(), (E, d, fe), dt),
        "we_up": dense_init(kg(), (E, d, fe), dt),
        "we_down": dense_init(kg(), (E, fe, d), dt, scale=down_scale),
    }
    if m.num_shared_experts:
        p["shared"] = init_ffn(cfg, kg(), d_ff=fe * m.num_shared_experts,
                               dtype=dt)
        p["shared"].pop("ln")  # shares the MoE layernorm
    return p


def _route(cfg, logits):
    """top-k routing. logits: (T, E) fp32 -> gates (T,k), idx (T,k), aux."""
    m = cfg.moe
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, m.top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch load-balance loss: E * sum_e f_e * p_e
    T = logits.shape[0]
    f = jnp.zeros((m.num_experts,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    f = f / (T * m.top_k)
    pbar = probs.mean(0)
    aux = m.num_experts * jnp.sum(f * pbar)
    return gates, idx, aux


def _capacity(cfg, n_tokens: int) -> int:
    m = cfg.moe
    c = -(-int(n_tokens * m.top_k * m.capacity_factor) // m.num_experts)
    c = max(1, c)
    if c > 8:
        c = -(-c // 4) * 4             # align larger buffers
    # never more slots than assignments exist
    return min(c, n_tokens * m.top_k)


def _dispatch_tables(cfg, idx, n_tokens: int, capacity: int):
    """Sort-based rank-in-expert; returns (dispatch_idx (E,C), slot_gatepos).

    dispatch_idx[e, c] = flat token index filling slot c of expert e (or
    n_tokens = sentinel padding row).  slot_assign[e, c] = index into the
    flattened (T*k) assignment list (or -1) used to fetch gates.
    """
    m = cfg.moe
    E, k = m.num_experts, m.top_k
    TK = n_tokens * k
    a = idx.reshape(TK)                                   # expert of each assignment
    order = jnp.argsort(a)                                # stable
    a_sorted = a[order]
    start = jnp.searchsorted(a_sorted, jnp.arange(E))     # first pos of each expert
    rank_sorted = jnp.arange(TK) - start[a_sorted]        # rank within expert
    keep = rank_sorted < capacity
    # scatter into (E, C) tables
    flat_slot = a_sorted * capacity + rank_sorted
    flat_slot = jnp.where(keep, flat_slot, E * capacity)  # dropped -> overflow row
    token_of_assign = order // k
    dispatch = jnp.full((E * capacity + 1,), n_tokens, jnp.int32)
    dispatch = dispatch.at[flat_slot].set(token_of_assign.astype(jnp.int32),
                                          mode="drop")
    assign_of_slot = jnp.full((E * capacity + 1,), -1, jnp.int32)
    assign_of_slot = assign_of_slot.at[flat_slot].set(order.astype(jnp.int32),
                                                      mode="drop")
    return (dispatch[:-1].reshape(E, capacity),
            assign_of_slot[:-1].reshape(E, capacity))


def _expert_ffn(cfg, params, xd):
    """xd: (E, C, d) -> (E, C, d) through stacked expert FFNs."""
    if cfg.ffn_type == "relu2":
        h = jnp.square(jax.nn.relu(jnp.einsum("ecd,edf->ecf", xd, params["we_up"])))
    else:
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xd, params["we_gate"]))
        h = h * jnp.einsum("ecd,edf->ecf", xd, params["we_up"])
    return jnp.einsum("ecf,efd->ecd", h, params["we_down"])


def moe_gather(cfg, params, h2, ctx):
    """GSPMD-auto dispatch. h2: (T, d) -> (y (T, d), aux)."""
    T, d = h2.shape
    cap = _capacity(cfg, T)
    logits = h2.astype(jnp.float32) @ params["router"]
    gates, idx, aux = _route(cfg, logits)
    dispatch, assign_of_slot = _dispatch_tables(cfg, idx, T, cap)

    h_pad = jnp.concatenate([h2, jnp.zeros((1, d), h2.dtype)], 0)
    xd = h_pad[dispatch]                                  # (E, C, d)
    if ctx is not None:
        xd = shard(xd, ctx, ctx.tp, None, None)
    yd = _expert_ffn(cfg, params, xd)                     # (E, C, d)

    gate_flat = gates.reshape(-1)
    slot_gate = jnp.where(assign_of_slot >= 0,
                          gate_flat[jnp.clip(assign_of_slot, 0)], 0.0)
    y = jnp.zeros((T + 1, d), jnp.float32)
    y = y.at[dispatch.reshape(-1)].add(
        (yd * slot_gate[..., None].astype(yd.dtype)).reshape(-1, d)
        .astype(jnp.float32))
    return y[:-1].astype(h2.dtype), aux


def alltoall_ep_axes(cfg, mesh, dp):
    """Data axes carrying expert parallelism for the all_to_all MoE: the
    largest suffix of dp whose product divides num_experts."""
    E = cfg.moe.num_experts
    for start in range(len(dp)):
        axes = dp[start:]
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        if size > 1 and E % size == 0:
            return axes
    return ()


def moe_alltoall(cfg, params, h2, ctx):
    """shard_map expert-parallel MoE: EP over the data axes, TP over the
    model axis, explicit all_to_all dispatch/combine (DeepSpeed-MoE-style
    EP x TP hybrid — the production layout).

    Tokens are sharded over dp (replicated over tp).  Experts live E-major
    on the EP axes with their FFN width sharded over tp.  Each data shard
    routes its local tokens, all_to_all's the (E, C_loc, d) dispatch buffer
    over the EP axes so every shard receives exactly its own experts' slots,
    runs the row/column-parallel expert FFN (psum over tp), and reverses the
    exchange.  Per-device collective volume is O(T_loc * k * cf * d) —
    independent of the global token count — versus the GSPMD gather
    baseline's full-token-buffer rematerializations (see EXPERIMENTS.md
    §Perf).
    """
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    mesh = ctx.mesh
    tp, dp = ctx.tp, ctx.dp
    E = m.num_experts
    ep = alltoall_ep_axes(cfg, mesh, dp)
    if not ep:                                # no divisible EP axis: fall back
        return moe_gather(cfg, params, h2, ctx)
    ep_size = 1
    for a in ep:
        ep_size *= mesh.shape[a]
    E_loc = E // ep_size
    T, d = h2.shape
    fe = m.d_ff_expert
    tp_size = mesh.shape[tp]
    fe_tp = tp if fe % tp_size == 0 else None

    router = params["router"]
    we = {k_: params[k_] for k_ in ("we_gate", "we_up", "we_down")
          if k_ in params}

    def body(h_loc, router_, we_loc):
        Tl = h_loc.shape[0]
        cap = _capacity(cfg, Tl)
        logits = h_loc.astype(jnp.float32) @ router_
        gates, idx, aux = _route(cfg, logits)
        dispatch, assign_of_slot = _dispatch_tables(cfg, idx, Tl, cap)
        h_pad = jnp.concatenate([h_loc, jnp.zeros((1, d), h_loc.dtype)], 0)
        xd = h_pad[dispatch]                      # (E, cap, d), E-major by EP
        # dispatch: shard i keeps experts [i*E_loc, (i+1)*E_loc); receives
        # the matching slice from every peer along its slot axis
        xd = xd.astype(h_loc.dtype)               # keep exchanges in bf16
        xr = jax.lax.all_to_all(xd, ep, split_axis=0, concat_axis=1,
                                tiled=True)       # (E_loc, ep*cap, d)
        yr = _expert_ffn(cfg, we_loc, xr).astype(h_loc.dtype)
        if fe_tp is not None:
            yr = jax.lax.psum(yr, tp)             # row-parallel down-proj
        yd = jax.lax.all_to_all(yr, ep, split_axis=1, concat_axis=0,
                                tiled=True)       # (E, cap, d)
        gate_flat = gates.reshape(-1)
        slot_gate = jnp.where(assign_of_slot >= 0,
                              gate_flat[jnp.clip(assign_of_slot, 0)], 0.0)
        y = jnp.zeros((Tl + 1, d), jnp.float32)
        y = y.at[dispatch.reshape(-1)].add(
            (yd * slot_gate[..., None].astype(yd.dtype))
            .reshape(-1, d).astype(jnp.float32))
        if dp:
            aux = jax.lax.pmean(aux, dp)
        return y[:-1].astype(h_loc.dtype), aux

    gate_spec = P(ep, None, fe_tp)                # we_gate/we_up (E, d, fe)
    down_spec = P(ep, fe_tp, None)                # we_down (E, fe, d)
    we_specs = {k_: (down_spec if k_ == "we_down" else gate_spec)
                for k_ in we}
    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp if dp else None, None), P(None, None), we_specs),
        out_specs=(P(dp if dp else None, None), P()),
    )(h2, router, we)
    return y, aux


def apply_moe(cfg, params, x, *, ctx=None):
    """x: (B, S, d) or (T, d). Returns (y, aux_loss)."""
    m = cfg.moe
    orig_shape = x.shape
    h = rms_norm(x, params["ln"], cfg.norm_eps)
    h2 = h.reshape(-1, orig_shape[-1])
    if ctx is not None and ctx.moe_impl == "alltoall":
        y2, aux = moe_alltoall(cfg, params, h2, ctx)
    else:
        y2, aux = moe_gather(cfg, params, h2, ctx)
    if m.num_shared_experts:
        y2 = y2 + ffn_core(cfg, dict(params["shared"]), h2, ctx)
    y = y2.reshape(orig_shape)
    if y.ndim == 3:
        y = shard_residual(y, ctx)
    return x + y, aux * m.router_aux_weight
