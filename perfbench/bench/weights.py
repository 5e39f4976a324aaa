"""Random weights from the seed, made on the device in one jitted call.

The benchmark makes the weights itself, so that the reference takes
nothing the program made.  They come out in the program's parameter
layout, read from the configuration file's block keys: ``embed``, per
stage the layer groups as the program groups them (leading dense layers
in ``prefix``, a stacked ``scan`` group over two or more periods, then
``tail``; a stage with fewer periods is a ``prefix`` list), per-stage
exit norm scales, the shared vocabulary projection, and with
``num_nextn_predict_layers`` the multi-token-prediction block.
:func:`check_layout` compares that layout with what the program's own
``init_params`` would build, so a change of layout fails at set-up, not
as a wrong answer.

Leaves per mixer: GQA (``wq``, ``wk``, ``wv``, ``wo``, with ``qk_norm``
``q_norm`` and ``k_norm``) or MLA (``wq_a``, ``q_norm``, ``wq_b``,
``wkv_a``, ``kv_norm``, ``wkv_b``, ``wo``), each with its norm ``ln``.
Per feed-forward: dense SwiGLU (``w_gate``, ``w_up``, ``w_down``) or
experts (a float32 ``router`` over the ``n_routed_experts``, with
``topk_method`` ``"noaux_tc"`` its float32 selection bias
``router_bias``, one per routed expert; the ``we_gate``, ``we_up``,
``we_down`` of the experts this chip holds, :func:`held_experts`; and the
shared experts' SwiGLU under ``shared``).

Scales by leaf name: norm scales N(0, 0.1) (they act as ``1 + scale``),
output projections ``wo``, ``w_down`` and ``we_down`` N(0, 0.02 /
sqrt(layers)), ``router_bias`` N(0, 0.005), the rest N(0, 0.02).  At
that scale the bias changes the top-8 set of about half the rows at
DeepSeek-V3's router widths (7168 x 256, rows of unit variance), so a
program that ignores it computes other experts.  Each leaf draws from
the seed's key folded with its own tag (``s{stage}/{part}/{name}`` for a
scan group, ``l{layer}/...`` for a single layer), so a leaf's values do
not depend on which other leaves exist.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

NORMS = ("ln", "q_norm", "k_norm", "kv_norm")
OUT_PROJ = ("wo", "w_down", "we_down")
FLOAT32 = ("router", "router_bias")
BIAS_SCALE = 0.005

#: the fewest routed experts a chip may hold (model-configs guide, section 4)
MIN_HELD = 8


def stage_spans(m: dict):
    ends = list(m["stage_ends"])
    return list(zip([0] + ends[:-1], ends))


def is_moe_layer(m: dict, i: int) -> bool:
    """Layer ``i`` has routed experts: from ``first_k_dense_replace`` on,
    every ``moe_layer_freq``-th layer."""
    return "n_routed_experts" in m and i >= m["first_k_dense_replace"] \
        and i % m.get("moe_layer_freq", 1) == 0


def held_experts(m: dict) -> int:
    """The routed experts one chip holds, experts ``0 .. held - 1`` (rank
    0): ``n_routed_experts / ep_size``, all of them where ``ep_size`` is
    not stated.  Raises unless ``ep_size`` divides ``n_routed_experts``
    and, where it cuts, leaves :data:`MIN_HELD` or more."""
    E, ep = m["n_routed_experts"], m.get("ep_size", 1)
    if E % ep:
        raise ValueError(f"ep_size {ep} does not divide n_routed_experts {E}")
    if ep > 1 and E // ep < MIN_HELD:
        raise ValueError(f"ep_size {ep} leaves {E // ep} of {E} experts "
                         f"held, under {MIN_HELD}")
    return E // ep


def mixer_shapes(m: dict) -> dict:
    d, H = m["hidden_size"], m["num_attention_heads"]
    if m.get("attention", "gqa") == "mla":
        q, kv = m["q_lora_rank"], m["kv_lora_rank"]
        if q is None:
            raise ValueError("q_lora_rank null: the direct query "
                             "projection is not built")
        nope, rope, v = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                         m["v_head_dim"])
        return {"ln": (d,), "wq_a": (d, q), "q_norm": (q,),
                "wq_b": (q, H * (nope + rope)), "wkv_a": (d, kv + rope),
                "kv_norm": (kv,), "wkv_b": (kv, H * (nope + v)),
                "wo": (H * v, d)}
    KV, hd = m["num_key_value_heads"], m["head_dim"]
    mixer = {"ln": (d,), "wq": (d, H * hd), "wk": (d, KV * hd),
             "wv": (d, KV * hd), "wo": (H * hd, d)}
    if m["qk_norm"]:
        mixer.update(q_norm=(hd,), k_norm=(hd,))
    return mixer


def ffn_shapes(m: dict, moe: bool) -> dict:
    d = m["hidden_size"]
    if not moe:
        f = m["intermediate_size"]
        return {"ln": (d,), "w_up": (d, f), "w_down": (f, d),
                "w_gate": (d, f)}
    fe, E = m["moe_intermediate_size"], m["n_routed_experts"]
    h = held_experts(m)
    p = {"ln": (d,), "router": (d, E), "we_gate": (h, d, fe),
         "we_up": (h, d, fe), "we_down": (h, fe, d)}
    if m.get("topk_method") == "noaux_tc":
        p["router_bias"] = (E,)
    fs = fe * m["n_shared_experts"]
    if fs:
        p["shared"] = {"w_up": (d, fs), "w_down": (fs, d), "w_gate": (d, fs)}
    return p


def layer_shapes(m: dict, moe: bool = False) -> dict:
    return {"mixer": mixer_shapes(m), "ffn": ffn_shapes(m, moe)}


def stage_groups(m: dict):
    """Per stage ``(prefix, scan, tail)`` as the program's
    ``stage_layouts`` groups them: ``scan`` is ``None`` or ``(start,
    periods, period length)``; ``prefix`` and ``tail`` are layer indices."""
    moe = "n_routed_experts" in m
    fd = m["first_k_dense_replace"] if moe else 0
    E = m.get("moe_layer_freq", 1) if moe else 1
    out = []
    for a, b in stage_spans(m):
        g0 = max(a, fd)
        n = max(0, (b - g0) // E)
        if n < 2:
            out.append((list(range(a, b)), None, []))
        else:
            out.append((list(range(a, g0)), (g0, n, E),
                        list(range(g0 + n * E, b))))
    return out


def _scale(name: str, m: dict) -> float:
    if name in NORMS:
        return 0.1
    if name in OUT_PROJ:
        return 0.02 / float(m["num_hidden_layers"]) ** 0.5
    if name == "router_bias":
        return BIAS_SCALE
    return 0.02


def _draw(key, tag: str, shape, dtype, scale):
    k = jax.random.fold_in(key, zlib.crc32(tag.encode()) & 0x7FFFFFFF)
    return jax.random.normal(k, shape, dtype) * jnp.asarray(scale, dtype)


def _leaves(key, m, dt, tag, shapes, lead=()):
    out = {}
    for name, shp in shapes.items():
        t = f"{tag}/{name}"
        if isinstance(shp, dict):
            out[name] = _leaves(key, m, dt, t, shp, lead)
        else:
            ldt = jnp.float32 if name in FLOAT32 else dt
            out[name] = _draw(key, t, lead + shp, ldt, _scale(name, m))
    return out


def _build(key, m: dict):
    dt = jnp.dtype(m["torch_dtype"])
    d, V = m["hidden_size"], m["vocab_size"]

    def layer(i, tag=None, n=0):
        lead = (n,) if n else ()
        return _leaves(key, m, dt, tag or f"l{i}",
                       layer_shapes(m, is_moe_layer(m, i)), lead)

    stages = []
    for s, (prefix, scan, tail) in enumerate(stage_groups(m)):
        st = {"prefix": [layer(i) for i in prefix]}
        if scan:
            g0, n, E = scan
            st["scan"] = tuple(layer(g0 + j, f"s{s}" + (f".{j}" if j else ""),
                                     n) for j in range(E))
        st["tail"] = [layer(i) for i in tail]
        stages.append(st)
    params = {"embed": {"tok": _draw(key, "embed/tok", (V, d), dt, 0.02)},
              "stages": stages,
              "exits": [{"ln": _draw(key, f"exit{s}/ln", (d,), dt, 0.1)}
                        for s in range(len(stages))],
              "exit_shared": {"w_out": _draw(key, "exit/w_out", (d, V), dt,
                                             0.02)}}
    if m.get("num_nextn_predict_layers", 0):
        params["mtp"] = {
            "proj": _draw(key, "mtp/proj", (2 * d, d), dt, 0.02),
            "block": _leaves(key, m, dt, "mtp/block", layer_shapes(m)),
            "exit": {"ln": _draw(key, "mtp/exit/ln", (d,), dt, 0.1)}}
    return params


def seed_key(seed: int):
    """A threefry key from any non-negative integer seed."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def make_weights(m: dict, seed: int, device=None):
    """The whole weight pytree, made on ``device`` in one jitted call."""
    fn = jax.jit(lambda k: _build(k, m))
    key = seed_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    out = fn(key)
    jax.block_until_ready(out)
    return out


def _sig(tree):
    leaves, treedef = jax.tree.flatten(tree)
    return treedef, [(tuple(x.shape), jnp.dtype(x.dtype)) for x in leaves]


def check_layout(m: dict, program_init, program_cfg) -> None:
    """Raise unless the benchmark's layout equals the program's."""
    ours = jax.eval_shape(lambda k: _build(k, m), seed_key(0))
    theirs = jax.eval_shape(lambda k: program_init(program_cfg, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    a, b = _sig(ours), _sig(theirs)
    if a != b:
        raise RuntimeError(
            "the program's parameter layout differs from the benchmark's "
            f"weights: {a[0]} {a[1][:4]} vs {b[0]} {b[1][:4]}")
