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
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_sim_arch
from repro.kernels import ops
from repro.nn import module as nnm
from repro.nn.agent_sim import AgentSimModel

B = 8                      # > 1: a (1, block) tile of a (B, S) array is refused
HEADS = 8
LAYERS = 6
TOKENS = 336               # sim-se2-fourier scene: 48 map + 12 agents x 24 steps
SLAB = 384                 # the server's slab: TOKENS rounded up to 128
#: a longer slab for the compiled tick: one k array then outweighs the
#: tick's own temporaries (3.5 MB of int8 rows at SLAB do not)
TICK_SLAB = 4 * SLAB
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


def _compile_for_chip(fn, *structs, kernels=(), donate=()):
    compiled = jax.jit(fn, donate_argnums=donate).lower(*structs).compile()
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
    # the model's stacked cache is feature-major: (L, B, H, c, S)
    args = [sds((B, HEADS, AGENTS, c), jnp.float32),
            sds((LAYERS, B, HEADS, c, SLAB), kv_dt),
            sds((LAYERS, B, HEADS, c, SLAB), kv_dt),
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


@pytest.mark.parametrize("cache_dtype", ["float32", "int8"])
@pytest.mark.parametrize("arch", sorted(ROW_WIDTHS))
def test_sim_step_keeps_stacked_cache_in_place(one_chip, monkeypatch, arch,
                                                cache_dtype):
    """The compiled tick writes and reads the donated stacked cache where
    it lies: no op copies or rematerializes an array of its k/v shape, and
    the temporaries stay below one k array. With rows last, (L, B, H, S,
    c), c sits in the padded lanes, the runtime stores the buffer S-minor,
    and the tick copied the whole cache in and out of the padded layout
    (plus remat compress/restore copies around each layer)."""
    # the Pallas kernel, compiled for the chip rather than interpreted
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    cfg = dataclasses.replace(get_sim_arch(arch).agent_sim_config(),
                              decode_impl="flash_decode")
    model = AgentSimModel(cfg)
    on_chip = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)
    params = on_chip(jax.eval_shape(
        lambda: nnm.init_params(model.specs(), jax.random.key(0))))
    cache = on_chip(jax.eval_shape(
        lambda: model.init_cache(B, TICK_SLAB, cache_dtype)))
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    step_args = (sds((B, AGENTS, cfg.agent_feat_dim), jnp.float32),
                 sds((B, AGENTS, 3), jnp.float32),
                 sds((B, AGENTS), jnp.bool_), sds((B,), jnp.int32))
    compiled = _compile_for_chip(model.step, params, cache, *step_args,
                                 kernels=("flash_decode",), donate=(1,))
    cache_dims = {",".join(map(str, cache[key].shape)) for key in ("k", "v")}
    for line in compiled.as_text().splitlines():
        # "%name = f32[6,8,8,200,1536]{...} op(...)"
        name, _, rhs = line.strip().partition(" = ")
        made = re.match(r"\w+\[([\d,]*)\]\{", rhs)
        if made and made.group(1) in cache_dims:
            assert " copy(" not in rhs and "remat" not in name, line
    k = cache["k"]
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < k.size * k.dtype.itemsize, temp


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
