"""Pipeline parallelism: GPipe-style microbatched schedule on a "pipe" mesh
axis via ``shard_map`` + ``ppermute``.

For depth-dominated configs (granite-20b's 52 layers) pipeline stages are an
alternative to pure TP when the model axis is exhausted. The schedule here
is the classic fill-drain loop:

  * layers are split into ``P`` contiguous stages; stage parameters live on
    their pipe slice (leading "layers" dim sharded over "pipe");
  * the batch is split into ``M`` microbatches; each loop tick every stage
    processes one resident microbatch, then activations rotate one hop with
    ``lax.ppermute`` (neighbor-only traffic — the property that makes PP the
    cross-pod-friendly axis at 1000+ nodes);
  * total ticks = M + P - 1; bubble fraction = (P-1)/(M+P-1).

The implementation is deliberately layer-homogeneous (stage = equal slice of
a scanned block stack), matching how the uniform-depth architectures here
are built. Losses/logits are computed on the last stage and psum'd back.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    num_stages: int
    num_microbatches: int

    @property
    def bubble_fraction(self) -> float:
        p, m = self.num_stages, self.num_microbatches
        return (p - 1) / (m + p - 1)


def pipeline_apply(block_fn: Callable, stage_params, x, cfg: PipelineConfig,
                   axis_name: str = "pipe"):
    """Run inside shard_map: every pipe rank holds ``stage_params`` (its
    layers, stacked) and the full microbatched input ``x`` of shape
    ``(M, mb, ...)``; rank 0 feeds, rank P-1 collects.

    block_fn(stage_params, x_mb) -> x_mb applies this rank's layers.
    Returns (M, mb, ...) outputs (valid on the last stage; psum'd out).
    """
    p = cfg.num_stages
    m = cfg.num_microbatches
    rank = jax.lax.axis_index(axis_name)
    ticks = m + p - 1

    mb_shape = x.shape[1:]
    state = jnp.zeros(mb_shape, x.dtype)          # resident microbatch
    outputs = jnp.zeros((m,) + mb_shape, x.dtype)

    def tick(carry, t):
        state, outputs = carry
        # stage 0 ingests microbatch t (if still in range)
        feed = jnp.where(t < m, t, m - 1)
        state = jnp.where(rank == 0, x[feed], state)
        state = block_fn(stage_params, state)
        # last stage emits the microbatch that entered at t - (p - 1)
        out_idx = t - (p - 1)
        emit = jnp.logical_and(rank == p - 1, out_idx >= 0)
        outputs = jax.lax.cond(
            emit,
            lambda o: o.at[jnp.maximum(out_idx, 0)].set(state),
            lambda o: o,
            outputs)
        # rotate activations one hop down the pipe
        state = jax.lax.ppermute(
            state, axis_name, [(i, (i + 1) % p) for i in range(p)])
        return (state, outputs), None

    (state, outputs), _ = jax.lax.scan(tick, (state, outputs),
                                       jnp.arange(ticks))
    # broadcast the last stage's outputs to all ranks (for loss replication)
    # ppermute rotated one extra time; undo is unnecessary because outputs
    # were captured pre-rotation.
    mask = (rank == p - 1).astype(outputs.dtype)
    return jax.lax.psum(outputs * mask, axis_name)


def make_pipelined_fn(block_fn: Callable, mesh: Mesh, cfg: PipelineConfig,
                      axis_name: str = "pipe"):
    """Wrap a per-stage block fn into a full-model fn over the pipe axis.

    stage_params: any pytree whose leaves have a leading dim divisible by
    the pipe axis (layer-stacked); x: (batch, ...) with batch divisible by
    num_microbatches.
    """
    def full(params, x):
        m = cfg.num_microbatches
        xm = x.reshape((m, x.shape[0] // m) + x.shape[1:])
        inner = functools.partial(pipeline_apply, block_fn, cfg=cfg,
                                  axis_name=axis_name)
        out = jax.shard_map(
            lambda sp, xi: inner(sp, xi),
            mesh=mesh,
            in_specs=(P(axis_name), P()),
            out_specs=P(),
            check_vma=False,
        )(params, xm)
        return out.reshape(x.shape[:1] + out.shape[2:])

    return full
