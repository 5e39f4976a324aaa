"""Random weights from the seed, made on the device in one jitted call.

The benchmark makes the weights itself, so that the reference takes
nothing the program made.  They come out in the program's parameter
layout: ``embed``, per-stage layer groups (a stacked ``scan`` group for a
stage of two or more layers, else a ``prefix`` list), per-stage exit norm
scales, and the shared vocabulary projection.  :func:`check_layout`
compares that layout with what the program's own ``init_params`` would
build, so a change of layout fails at set-up, not as a wrong answer.

Scales: projections N(0, 0.02), output projections ``wo`` and ``w_down``
N(0, 0.02 / sqrt(layers)), norm scales N(0, 0.1) (they act as
``1 + scale``).
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np


def stage_spans(m: dict):
    ends = list(m["stage_ends"])
    return list(zip([0] + ends[:-1], ends))


def layer_shapes(m: dict) -> dict:
    d, H, KV, hd = (m["hidden_size"], m["num_attention_heads"],
                    m["num_key_value_heads"], m["head_dim"])
    f = m["intermediate_size"]
    mixer = {"ln": (d,), "wq": (d, H * hd), "wk": (d, KV * hd),
             "wv": (d, KV * hd), "wo": (H * hd, d)}
    if m["qk_norm"]:
        mixer.update(q_norm=(hd,), k_norm=(hd,))
    ffn = {"ln": (d,), "w_up": (d, f), "w_down": (f, d), "w_gate": (d, f)}
    return {"mixer": mixer, "ffn": ffn}


def _scale(name: str, m: dict) -> float:
    if name in ("ln", "q_norm", "k_norm"):
        return 0.1
    if name in ("wo", "w_down"):
        return 0.02 / float(m["num_hidden_layers"]) ** 0.5
    return 0.02


def _draw(key, tag: str, shape, dtype, scale):
    k = jax.random.fold_in(key, zlib.crc32(tag.encode()) & 0x7FFFFFFF)
    return jax.random.normal(k, shape, dtype) * jnp.asarray(scale, dtype)


def _build(key, m: dict):
    dt = jnp.dtype(m["torch_dtype"])
    d, V = m["hidden_size"], m["vocab_size"]
    shapes = layer_shapes(m)
    stages = []
    for s, (a, b) in enumerate(stage_spans(m)):
        def group(n, tag):
            lead = (n,) if n else ()
            return {part: {name: _draw(key, f"{tag}/{part}/{name}",
                                       lead + shp, dt, _scale(name, m))
                           for name, shp in leaves.items()}
                    for part, leaves in shapes.items()}
        if b - a >= 2:
            stages.append({"prefix": [], "scan": (group(b - a, f"s{s}"),),
                           "tail": []})
        else:
            stages.append({"prefix": [group(0, f"l{i}") for i in range(a, b)],
                           "tail": []})
    return {"embed": {"tok": _draw(key, "embed/tok", (V, d), dt, 0.02)}, "stages": stages,
            "exits": [{"ln": _draw(key, f"exit{s}/ln", (d,), dt, 0.1)}
                      for s in range(len(stages))],
            "exit_shared": {"w_out": _draw(key, "exit/w_out", (d, V), dt,
                                           0.02)}}


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
