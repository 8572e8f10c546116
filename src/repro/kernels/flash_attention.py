"""Pallas TPU flash-attention kernel (forward).

TPU-native design notes (vs. the CUDA FlashAttention the paper reuses):

  * Tiling targets VMEM: each grid step holds one ``(block_q, d)`` query
    tile plus one ``(block_k, d)`` key/value tile in VMEM; the online-softmax
    running state (m, l, acc) lives in VMEM scratch that persists across the
    innermost (key) grid dimension.
  * Block shapes default to 128 so the MXU (128x128 systolic array) runs at
    full tile occupancy; head dims are padded to a multiple of 128 by the
    ``ops.flash_attention`` wrapper.
  * The grid is (batch, q_heads, q_blocks, k_blocks) with
    ``dimension_semantics = (parallel, parallel, parallel, arbitrary)`` —
    the k dimension is sequential so the scratch accumulators carry.
  * Causal / sliding-window masking skips fully-masked key blocks with
    ``pl.when`` (block-level early out), and applies an element mask built
    from ``broadcasted_iota`` inside partially-masked blocks.
  * Grouped-query attention is folded into the index maps: the key/value
    BlockSpecs map q-head ``h`` to kv-head ``h // group``.

Supported features (superset of what the architectures need): causal masking,
sliding windows (gemma2 local layers, hymba), logit soft-capping (gemma2),
segment ids (agent-simulation scene packing + padding), GQA/MQA, distinct
qk/v head dims (SE(2) Fourier expanded features and MLA).

The pure-jnp oracle lives in ``repro.kernels.ref``; the public padded/
autodiff-capable wrapper lives in ``repro.kernels.ops``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def row_operands(q_vec, k_vec):
    """Lay out a pair of per-token (B, S) vectors for Mosaic's tiling.

    A block's last two dims must either equal the array's or be divisible
    by (8, 128), so a ``(1, block)`` tile of a ``(B, S)`` array is refused
    whenever B > 1. Query-side vectors become ``(B, Sq, 1)`` columns and
    key-side vectors ``(B, 1, Sk)`` rows: their ``(block_q, 1)`` /
    ``(1, block_k)`` tiles are legal, and inside the kernel they arrive
    already oriented for the ``(block_q, block_k)`` mask broadcast.
    """
    return q_vec[:, :, None], k_vec[:, None, :]


def _fwd_kernel(q_seg_ref, k_seg_ref, q_time_ref, k_time_ref,
                q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *,
                scale: float, causal: bool, window: Optional[int],
                softcap: Optional[float], block_q: int, block_k: int,
                num_k_blocks: int, use_segments: bool, use_times: bool):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * block_q
    k_start = ik * block_k

    # Block-level early out: skip key blocks entirely masked by the causal /
    # sliding-window structure (saves both MXU work and VPU mask work).
    # With explicit per-token times the structure is data-dependent, so no
    # static skipping is possible.
    run = jnp.bool_(True)
    if not use_times:
        if causal:
            run = jnp.logical_and(run, k_start <= q_start + block_q - 1)
        if window is not None:
            run = jnp.logical_and(run,
                                  k_start + block_k - 1 > q_start - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)          # (bk, dv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if softcap is not None and softcap > 0:
            s = jnp.tanh(s / softcap) * softcap

        if use_times:
            rows = q_time_ref[0]                     # (bq, 1)
            cols = k_time_ref[0]                     # (1, bk)
        else:
            rows = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0) + q_start
            cols = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1) + k_start
        mask = jnp.ones((block_q, block_k), dtype=jnp.bool_)
        if causal:
            mask = jnp.logical_and(mask, cols <= rows)
        if window is not None:
            mask = jnp.logical_and(mask, cols > rows - window)
        if use_segments:
            qs = q_seg_ref[0]                         # (bq, 1)
            ks = k_seg_ref[0]                         # (1, bk)
            seg = jnp.logical_and(qs == ks, ks >= 0)
            mask = jnp.logical_and(mask, seg)
        s = jnp.where(mask, s, _NEG_INF)

        # m/l scratch are stored broadcast across the 128-lane minor dim so
        # the VMEM layout is native to the VPU (same trick as the reference
        # TPU flash kernel).
        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        # Fully-masked rows would otherwise contribute exp(-inf + inf) noise.
        p = jnp.where(mask, p, 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == num_k_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-30)          # (bq, 1)
        o_ref[0, 0, :, :] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[:, :1] + jnp.log(l)


def flash_attention_fwd(q, k, v, *,
                        causal: bool = False,
                        window: Optional[int] = None,
                        softcap: Optional[float] = None,
                        scale: Optional[float] = None,
                        q_segment_ids=None, k_segment_ids=None,
                        q_times=None, k_times=None,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool = False,
                        return_lse: bool = False):
    """Raw kernel invocation. Requires block-aligned sequence lengths.

    q: (B, Hq, Sq, D); k: (B, Hkv, Sk, D); v: (B, Hkv, Sk, Dv);
    segment ids / times: (B, S) int32 or None. Sq % block_q == 0 etc.
    Returns (B, Hq, Sq, Dv) in v.dtype; with ``return_lse`` also the
    float32 (B, Hq, Sq) log-sum-exp rows consumed by the backward kernels.
    """
    b, hq, sq, d = q.shape
    _, hkv, sk, dv = v.shape
    assert k.shape == (b, hkv, sk, d), (q.shape, k.shape, v.shape)
    assert hq % hkv == 0, (hq, hkv)
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk, block_q, block_k)
    group = hq // hkv
    if scale is None:
        scale = 1.0 / float(d) ** 0.5
    nq, nk = sq // block_q, sk // block_k
    use_segments = q_segment_ids is not None
    if not use_segments:
        q_segment_ids = jnp.zeros((b, sq), jnp.int32)
        k_segment_ids = jnp.zeros((b, sk), jnp.int32)
    use_times = q_times is not None
    if not use_times:
        q_times = jnp.zeros((b, sq), jnp.int32)
        k_times = jnp.zeros((b, sk), jnp.int32)

    q_segment_ids, k_segment_ids = row_operands(q_segment_ids, k_segment_ids)
    q_times, k_times = row_operands(q_times, k_times)

    kernel = functools.partial(
        _fwd_kernel, scale=float(scale), causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_k=block_k, num_k_blocks=nk,
        use_segments=use_segments, use_times=use_times)

    q_row = pl.BlockSpec((1, block_q, 1), lambda b_, h, iq, ik: (b_, iq, 0))
    k_row = pl.BlockSpec((1, 1, block_k), lambda b_, h, iq, ik: (b_, 0, ik))
    out, lse = pl.pallas_call(
        kernel,
        grid=(b, hq, nq, nk),
        in_specs=[
            q_row, k_row, q_row, k_row,
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h, iq, ik: (b_, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h, iq, ik: (b_, h // group, ik, 0)),
            pl.BlockSpec((1, 1, block_k, dv),
                         lambda b_, h, iq, ik: (b_, h // group, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, dv),
                         lambda b_, h, iq, ik: (b_, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b_, h, iq, ik: (b_, h, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sq, dv), v.dtype),
            jax.ShapeDtypeStruct((b, hq, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),    # acc
            pltpu.VMEM((block_q, 128), jnp.float32),   # m (running max)
            pltpu.VMEM((block_q, 128), jnp.float32),   # l (running denom)
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(q_segment_ids, k_segment_ids, q_times, k_times, q, k, v)
    return (out, lse[..., 0]) if return_lse else out
