"""Ahead-of-time compiles of the main-path Pallas kernels for a v5e.

Interpret mode (how every other kernel test runs on CPU) never checks
Mosaic's tiling rules, so a kernel can pass all of its parity tests and
still be refused by the TPU compiler. These tests compile the kernels for
a *described* v5e (``jax.experimental.topologies``) at the registered sim
widths, with no chip attached: the TPU compiler ships with libtpu and
raises here exactly what it would raise on the chip.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load libtpu, and every test worker imports
this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

B = 8                      # > 1: a (1, block) tile of a (B, S) array is refused
HEADS = 8
LAYERS = 6
TOKENS = 336               # sim-se2-fourier scene: 48 map + 12 agents x 24 steps
SLAB = 384                 # the server's slab: TOKENS rounded up to 128
AGENTS = 12
#: cached k/v row width: se2_fourier expands 24-dim heads to 200
#: ((24/6) * (4*12 + 2)); sim-absolute caches the plain 24-dim head
ROW_WIDTHS = {"sim-se2-fourier": 200, "sim-absolute": 24}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_for_chip(fn, *structs, kernels=()):
    compiled = jax.jit(fn).lower(*structs).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # each kernel's custom call is named after the kernel, so a device
    # trace shows which kernel ran
    for name in kernels:
        assert re.search(rf"%{name}(\.\d+)? = .*custom-call\(", text), name
    return compiled


@pytest.mark.parametrize("cache_dtype", ["float32", "int8"])
@pytest.mark.parametrize("arch", sorted(ROW_WIDTHS))
def test_flash_decode_layer_stacked_cache_compiles(one_chip, arch,
                                                   cache_dtype):
    c = ROW_WIDTHS[arch]
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    int8 = cache_dtype == "int8"
    kv_dt = jnp.int8 if int8 else jnp.float32
    args = [sds((B, HEADS, AGENTS, c), jnp.float32),
            sds((LAYERS, B, HEADS, SLAB, c), kv_dt),
            sds((LAYERS, B, HEADS, SLAB, c), kv_dt),
            sds((B,), jnp.int32),
            sds((B, AGENTS), jnp.int32), sds((B, SLAB), jnp.int32),
            sds((B, AGENTS), jnp.int32), sds((B, SLAB), jnp.int32)]
    if int8:
        args += [sds((LAYERS, B, HEADS, SLAB), jnp.float32)] * 2

    def decode(q, k, v, kvl, qt, kt, qs, ks, k_scale=None, v_scale=None):
        return ops.decode_attention(
            q, k, v, kv_length=kvl, impl="flash_decode", interpret=False,
            layer=LAYERS // 2, q_times=qt, k_times=kt, q_segment_ids=qs,
            k_segment_ids=ks, k_scale=k_scale, v_scale=v_scale)

    _compile_for_chip(decode, *args, kernels=("flash_decode",))


def _flash_structs(one_chip, c=ROW_WIDTHS["sim-se2-fourier"]):
    qkv = jax.ShapeDtypeStruct((B, HEADS, TOKENS, c), jnp.float32,
                               sharding=one_chip)
    row = jax.ShapeDtypeStruct((B, TOKENS), jnp.int32, sharding=one_chip)
    return qkv, row


def _flash(q, k, v, times, seg):
    # block-causal over simulation times with padded-token segment ids:
    # exactly how SimAttention calls the kernel in training
    return ops.flash_attention(q, k, v, causal=True, q_times=times,
                               k_times=times, q_segment_ids=seg,
                               k_segment_ids=seg, interpret=False,
                               bwd_impl="pallas")


def test_flash_forward_compiles(one_chip):
    qkv, row = _flash_structs(one_chip)
    _compile_for_chip(_flash, qkv, qkv, qkv, row, row,
                      kernels=("flash_attention",))


def test_flash_forward_backward_compiles(one_chip):
    qkv, row = _flash_structs(one_chip)

    def loss(q, k, v, times, seg):
        return jnp.sum(_flash(q, k, v, times, seg) ** 2)

    compiled = _compile_for_chip(jax.grad(loss, argnums=(0, 1, 2)),
                                 qkv, qkv, qkv, row, row)
    # forward + the dq and dk/dv kernels
    assert compiled.as_text().count("tpu_custom_call") >= 3
