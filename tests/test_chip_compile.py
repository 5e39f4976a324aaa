"""Ahead-of-time compiles of the serving path's Pallas kernels for a TPU v5e.

The chip is described, not attached: the TPU compiler installed with jaxlib
lowers and compiles each kernel at the widths the serving path runs, and
refuses what Mosaic cannot tile — which interpret-mode tests cannot see.
Each test asserts the compiled program holds the kernel
(``tpu_custom_call``), i.e. it was compiled and not interpreted.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention.kernel import decode_attention
from repro.kernels.exit_confidence.kernel import exit_confidence
from repro.models import init_params


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("n,d,vocab,dtype", [
    (8, 128, 10, jnp.float32),           # anytime-classifier exit head
    (4, 2560, 151936, jnp.bfloat16),     # qwen3-4b exit head, decode batch 4
])
def test_exit_confidence_compiles_for_v5e(one_chip, n, d, vocab, dtype):
    txt = _compiled_text(
        lambda h, s, w: exit_confidence(h, s, w, interpret=False),
        _shape(one_chip, (n, d), dtype), _shape(one_chip, (d,), dtype),
        _shape(one_chip, (d, vocab), dtype))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("batch,slots", [
    (4, 256),    # qwen3-4b decode: batch 4, 256 KV slots
    (3, 16),     # ragged co-batch of three requests, one length bucket
])
def test_decode_attention_compiles_for_v5e(one_chip, batch, slots):
    H, KV, dh = 32, 8, 128               # qwen3-4b heads
    txt = _compiled_text(
        lambda q, k, v, sp, cp: decode_attention(q, k, v, sp, cp,
                                                 interpret=False),
        _shape(one_chip, (batch, H, dh), jnp.bfloat16),
        _shape(one_chip, (batch, KV, slots, dh), jnp.bfloat16),
        _shape(one_chip, (batch, KV, slots, dh), jnp.bfloat16),
        _shape(one_chip, (batch, slots), jnp.int32),
        _shape(one_chip, (batch,), jnp.int32))
    assert "tpu_custom_call" in txt


def test_device_kernel_classifier_stage_compiles_for_v5e(one_chip):
    """The ``device-kernel`` executor's stage fn (trunk + fused exit) at
    the registered anytime-classifier width and the largest batch
    bucket."""
    from repro.launch.kernel import KernelStageFns
    from repro.models.model import FEATURE_DIM
    cfg = get_config("anytime-classifier")
    params = jax.tree.map(
        lambda x: _shape(one_chip, x.shape, x.dtype),
        jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0)))
    fns = KernelStageFns(cfg, (1, 2, 4, 8), interpret=False)
    h = {"features": _shape(one_chip, (8, 16, FEATURE_DIM), jnp.float32)}
    txt = fns.fn(0).lower(params, h).compile().as_text()
    assert "tpu_custom_call" in txt
