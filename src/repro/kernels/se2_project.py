"""Pallas TPU kernel: fused SE(2) Fourier query/key projection.

The linear-memory algorithm (paper Alg. 2) pre-transforms every token:

  key/value side: quadrature-sample ``cos/sin(u_m(z_j))`` at 2F nodes,
    project onto the Fourier basis (two small matmuls per spatial axis), and
    assemble the expanded ``(4F + 2)``-wide feature block;
  query side: evaluate the basis ``b_n = [g_i(theta_n)]`` and rotate by
    ``v_n^{(x/y)}`` / ``theta_n``.

Unfused, XLA materializes several ``(tokens, nb, 2F)`` intermediates in HBM
(quadrature samples, their cos/sin, and four coefficient tensors) — an
~8x blow-up of the token stream before attention even starts. This kernel
keeps the whole pipeline for a tile of tokens resident in VMEM: one read of
``(x, pose)``, one write of the expanded features.

TPU adaptation: tokens ride the sublane dimension (tiles of ``block_t``
rows); the per-block loop over the ``nb`` feature blocks is unrolled
(nb is small, ~2-8); quadrature projection is a ``(block_t, 2F) @ (2F, F)``
MXU matmul. The quadrature constants are tiny and passed as replicated
inputs so Mosaic keeps them pinned in VMEM across the grid.

Validated against the pure-jnp oracle ``repro.core.encodings.SE2Fourier``
(which doubles as ``ref`` for this kernel) in interpret mode.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import fourier
from repro.core.encodings import SE2Fourier, _log_spaced


def _k_kernel(pose_ref, x_ref, nodes_ref, proj_ref, out_ref, *,
              num_terms: int, num_blocks: int, scales: tuple):
    """Key/value-side projection for one tile of tokens."""
    F = num_terms
    xp = pose_ref[:, 0:1]                       # (bt, 1)
    yp = pose_ref[:, 1:2]
    theta = pose_ref[:, 2:3]
    cz = nodes_ref[0:1, :]                      # (1, 2F) cos(z_j)
    sz = nodes_ref[1:2, :]                      # (1, 2F) sin(z_j)
    proj = proj_ref[...]                        # (2F, F)
    ct, st = jnp.cos(theta), jnp.sin(theta)     # (bt, 1)
    width = 4 * F + 2
    for b in range(num_blocks):
        a = scales[b]
        ux = (a * xp) * cz + (a * yp) * sz      # (bt, 2F)
        uy = -(a * xp) * sz + (a * yp) * cz
        gx = jnp.dot(jnp.cos(ux), proj, preferred_element_type=jnp.float32)
        lx = jnp.dot(jnp.sin(ux), proj, preferred_element_type=jnp.float32)
        gy = jnp.dot(jnp.cos(uy), proj, preferred_element_type=jnp.float32)
        ly = jnp.dot(jnp.sin(uy), proj, preferred_element_type=jnp.float32)
        k0 = x_ref[:, 6 * b + 0:6 * b + 1].astype(jnp.float32)
        k1 = x_ref[:, 6 * b + 1:6 * b + 2].astype(jnp.float32)
        k2 = x_ref[:, 6 * b + 2:6 * b + 3].astype(jnp.float32)
        k3 = x_ref[:, 6 * b + 3:6 * b + 4].astype(jnp.float32)
        k4 = x_ref[:, 6 * b + 4:6 * b + 5].astype(jnp.float32)
        k5 = x_ref[:, 6 * b + 5:6 * b + 6].astype(jnp.float32)
        off = b * width
        seg = jnp.concatenate(
            [gx * k0 - lx * k1, lx * k0 + gx * k1,
             gy * k2 - ly * k3, ly * k2 + gy * k3,
             ct * k4 - st * k5, st * k4 + ct * k5], axis=1)
        out_ref[:, off:off + width] = seg.astype(out_ref.dtype)


def _q_kernel(pose_ref, x_ref, basis_ref, out_ref, *,
              num_terms: int, num_blocks: int, scales: tuple):
    """Query-side projection for one tile of tokens."""
    F = num_terms
    xp = pose_ref[:, 0:1]
    yp = pose_ref[:, 1:2]
    theta = pose_ref[:, 2:3]
    ct, st = jnp.cos(theta), jnp.sin(theta)
    freqs = basis_ref[0:1, :]                   # (1, F) integer frequencies
    odd = basis_ref[1:2, :]                     # (1, F) 1.0 where g_i = sin
    zf = theta * freqs
    bvec = odd * jnp.sin(zf) + (1.0 - odd) * jnp.cos(zf)   # (bt, F)
    width = 4 * F + 2
    for b in range(num_blocks):
        a = scales[b]
        vx = -(a * xp) * ct - (a * yp) * st     # (bt, 1)
        vy = (a * xp) * st - (a * yp) * ct
        q0 = x_ref[:, 6 * b + 0:6 * b + 1].astype(jnp.float32)
        q1 = x_ref[:, 6 * b + 1:6 * b + 2].astype(jnp.float32)
        q2 = x_ref[:, 6 * b + 2:6 * b + 3].astype(jnp.float32)
        q3 = x_ref[:, 6 * b + 3:6 * b + 4].astype(jnp.float32)
        q4 = x_ref[:, 6 * b + 4:6 * b + 5].astype(jnp.float32)
        q5 = x_ref[:, 6 * b + 5:6 * b + 6].astype(jnp.float32)
        cvx, svx = jnp.cos(vx), jnp.sin(vx)
        cvy, svy = jnp.cos(vy), jnp.sin(vy)
        rx0 = q0 * cvx + q1 * svx               # rho(-v) [q0; q1]
        rx1 = -q0 * svx + q1 * cvx
        ry0 = q2 * cvy + q3 * svy
        ry1 = -q2 * svy + q3 * cvy
        t0 = q4 * ct - q5 * st                  # rho(theta) [q4; q5]
        t1 = q4 * st + q5 * ct
        off = b * width
        seg = jnp.concatenate(
            [rx0 * bvec, rx1 * bvec, ry0 * bvec, ry1 * bvec, t0, t1], axis=1)
        out_ref[:, off:off + width] = seg.astype(out_ref.dtype)


def se2_fourier_project(x, pose, enc: SE2Fourier, mode: str, *,
                        block_t: int = 256,
                        interpret: Optional[bool] = None):
    """Fused SE(2) Fourier projection.

    Args:
      x: ``(tokens, head_dim)`` query or key/value features.
      pose: ``(tokens, 3)`` SE(2) poses.
      enc: the encoding config (num_terms, scales, head_dim).
      mode: "q" for the query-side transform, "k" for key/value-side.

    Returns ``(tokens, enc.expanded_dim)``; bit-compatible (to fp32 rounding)
    with ``enc.transform_q`` / ``enc.transform_k``.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    t, d = x.shape
    assert d == enc.head_dim, (d, enc.head_dim)
    F, nb = enc.num_terms, enc.num_blocks
    scales = tuple(float(s) for s in
                   _log_spaced(nb, enc.min_scale, enc.max_scale))
    c = enc.expanded_dim

    pad = (-t) % block_t
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        pose = jnp.pad(pose, ((0, pad), (0, 0)))
    tp = x.shape[0]
    grid = (tp // block_t,)
    pose32 = pose.astype(jnp.float32)

    if mode == "k":
        nodes, _ = fourier._quadrature_constants(F)  # float64 numpy
        const_nodes = jnp.asarray(
            np.stack([np.cos(nodes), np.sin(nodes)]), dtype=jnp.float32)
        proj = fourier.quadrature_projection(F, jnp.float32)
        kernel = functools.partial(_k_kernel, num_terms=F, num_blocks=nb,
                                   scales=scales)
        out = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_t, 3), lambda i: (i, 0)),
                pl.BlockSpec((block_t, d), lambda i: (i, 0)),
                pl.BlockSpec((2, 2 * F), lambda i: (0, 0)),
                pl.BlockSpec((2 * F, F), lambda i: (0, 0)),
            ],
            out_specs=pl.BlockSpec((block_t, c), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((tp, c), x.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)),
            interpret=interpret,
            name="se2_project_k",
        )(pose32, x, const_nodes, proj)
    elif mode == "q":
        freqs = fourier.basis_frequencies(F).astype(np.float32)
        odd = (np.arange(F) % 2 == 1).astype(np.float32)
        basis_const = jnp.asarray(np.stack([freqs, odd]), dtype=jnp.float32)
        kernel = functools.partial(_q_kernel, num_terms=F, num_blocks=nb,
                                   scales=scales)
        out = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_t, 3), lambda i: (i, 0)),
                pl.BlockSpec((block_t, d), lambda i: (i, 0)),
                pl.BlockSpec((2, F), lambda i: (0, 0)),
            ],
            out_specs=pl.BlockSpec((block_t, c), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((tp, c), x.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)),
            interpret=interpret,
            name="se2_project_q",
        )(pose32, x, basis_const)
    else:
        raise ValueError(f"mode must be 'q' or 'k', got {mode!r}")
    return out[:t]
