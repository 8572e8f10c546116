"""Pallas TPU flash-attention kernels (backward).

FlashAttention-style backward pass: never materializes the (Sq, Sk)
probability matrix. Each kernel recomputes the block logits from the saved
per-row log-sum-exp (``lse``) emitted by the forward kernel, so the whole
train step stays linear-memory on both sides of the autodiff boundary.

Two kernels mirror the forward's tiling:

  * ``dq`` kernel — grid ``(batch, q_heads, q_blocks, k_blocks)``; the key
    dimension is sequential and a ``(block_q, d)`` float32 accumulator lives
    in VMEM scratch across it. Identical iteration structure to the forward,
    so the same causal/window block-skip predicate applies.
  * ``dk/dv`` kernel — grid ``(batch, kv_heads, k_blocks, group * q_blocks)``;
    the innermost dimension walks every (q-head-in-group, q-block) pair
    sequentially while ``(block_k, d)`` / ``(block_k, dv)`` accumulators sit
    in VMEM scratch. Folding the GQA group into the sequential dimension
    gives each kv head exactly one writer, so dk/dv accumulation needs no
    cross-core reduction.

Both kernels recompute P = exp(S - lse) from q/k rather than loading it:
at block sizes 128x128 the recompute is one extra MXU matmul, far cheaper
than streaming an (Sq, Sk) tensor through HBM (the quadratic-memory cost
the paper exists to avoid).

The preprocessing row term ``delta = sum(dO * O, axis=-1)`` is computed in
plain XLA by the caller (an elementwise multiply-reduce, O(Sq) memory),
matching FlashAttention-2's separate preprocess step.

Feature parity with the forward kernel: causal masking, sliding windows,
segment ids, explicit per-token times (block-causal agent scenes), logit
soft-capping, GQA/MQA, and distinct qk/v head dims.

The public autodiff wrapper (padding + ``jax.custom_vjp`` + backend
selection) lives in ``repro.kernels.ops``; the pure-XLA fallback backward is
``ops._bwd_chunked``, kept as the parity oracle and the non-TPU path.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import row_operands

_NEG_INF = -1e30


def _block_probs_and_ds(q, k, v, do, lse, delta, *, scale, softcap,
                        rows, cols, causal, window, use_segments,
                        q_seg, k_seg, block_q, block_k):
    """Shared recompute: P from saved LSE, then dS (pre-softmax grad).

    All operands are float32 tiles: q (bq, d), k (bk, d), v (bk, dv),
    do (bq, dv), lse/delta (bq, 1) columns; the segment ids arrive as a
    (bq, 1) column and a (1, bk) row. Returns (p, ds) both (bq, bk), with dS
    already including the softcap chain rule and the score scale.
    """
    s_pre = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if softcap is not None and softcap > 0:
        t = jnp.tanh(s_pre / softcap)
        s = t * softcap
        dcap = 1.0 - t * t
    else:
        s = s_pre
        dcap = None

    mask = jnp.ones((block_q, block_k), dtype=jnp.bool_)
    if causal:
        mask = jnp.logical_and(mask, cols <= rows)
    if window is not None:
        mask = jnp.logical_and(mask, cols > rows - window)
    if use_segments:
        seg = jnp.logical_and(q_seg == k_seg, k_seg >= 0)
        mask = jnp.logical_and(mask, seg)

    # P = exp(S - lse) is exactly softmax(S) restricted to this block; rows
    # that were fully masked in the forward carry lse = log(1e-30) and are
    # masked to zero here anyway.
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    if dcap is not None:
        ds = ds * dcap
    ds = ds * scale
    return p, ds


def _mask_geometry(q_time_ref, k_time_ref, q_start, k_start, *,
                   block_q, block_k, use_times):
    if use_times:
        rows = q_time_ref[0]                             # (bq, 1)
        cols = k_time_ref[0]                             # (1, bk)
    else:
        rows = jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0) + q_start
        cols = jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1) + k_start
    return rows, cols


def _run_predicate(q_start, k_start, *, causal, window, block_q, block_k,
                   use_times):
    """Static block-skip: False iff the (q_block, k_block) tile is entirely
    masked by the causal / sliding-window structure. Identical condition for
    the forward, dq, and dk/dv kernels: the tile either contributes or not.
    With explicit per-token times the structure is data-dependent, so no
    static skipping is possible.
    """
    run = jnp.bool_(True)
    if not use_times:
        if causal:
            run = jnp.logical_and(run, k_start <= q_start + block_q - 1)
        if window is not None:
            run = jnp.logical_and(run,
                                  k_start + block_k - 1 > q_start - window)
    return run


# ---------------------------------------------------------------------------
# dq kernel: grid (b, hq, q_blocks, k_blocks), sequential over k blocks.
# ---------------------------------------------------------------------------

def _dq_kernel(q_seg_ref, k_seg_ref, q_time_ref, k_time_ref,
               q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc_ref, *,
               scale: float, causal: bool, window: Optional[int],
               softcap: Optional[float], block_q: int, block_k: int,
               num_k_blocks: int, use_segments: bool, use_times: bool):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    q_start = iq * block_q
    k_start = ik * block_k
    run = _run_predicate(q_start, k_start, causal=causal, window=window,
                         block_q=block_q, block_k=block_k,
                         use_times=use_times)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        rows, cols = _mask_geometry(q_time_ref, k_time_ref, q_start, k_start,
                                    block_q=block_q, block_k=block_k,
                                    use_times=use_times)
        _, ds = _block_probs_and_ds(
            q, k, v, do, lse, delta, scale=scale, softcap=softcap,
            rows=rows, cols=cols, causal=causal, window=window,
            use_segments=use_segments, q_seg=q_seg_ref[0], k_seg=k_seg_ref[0],
            block_q=block_q, block_k=block_k)
        dq_acc_ref[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == num_k_blocks - 1)
    def _finalize():
        dq_ref[0, 0, :, :] = dq_acc_ref[...].astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# dk/dv kernel: grid (b, hkv, k_blocks, group * q_blocks), sequential over
# the fused (q-head-in-group, q_block) dimension.
# ---------------------------------------------------------------------------

def _dkv_kernel(q_seg_ref, k_seg_ref, q_time_ref, k_time_ref,
                q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                dk_ref, dv_ref,
                dk_acc_ref, dv_acc_ref, *,
                scale: float, causal: bool, window: Optional[int],
                softcap: Optional[float], block_q: int, block_k: int,
                num_q_blocks: int, num_inner: int, use_segments: bool,
                use_times: bool):
    ik = pl.program_id(2)
    iqg = pl.program_id(3)
    iq = jax.lax.rem(iqg, num_q_blocks)

    @pl.when(iqg == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    q_start = iq * block_q
    k_start = ik * block_k
    run = _run_predicate(q_start, k_start, causal=causal, window=window,
                         block_q=block_q, block_k=block_k,
                         use_times=use_times)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        rows, cols = _mask_geometry(q_time_ref, k_time_ref, q_start, k_start,
                                    block_q=block_q, block_k=block_k,
                                    use_times=use_times)
        p, ds = _block_probs_and_ds(
            q, k, v, do, lse, delta, scale=scale, softcap=softcap,
            rows=rows, cols=cols, causal=causal, window=window,
            use_segments=use_segments, q_seg=q_seg_ref[0], k_seg=k_seg_ref[0],
            block_q=block_q, block_k=block_k)
        dv_acc_ref[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc_ref[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(iqg == num_inner - 1)
    def _finalize():
        dk_ref[0, 0, :, :] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_acc_ref[...].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, o, lse, do, *,
                        causal: bool = False,
                        window: Optional[int] = None,
                        softcap: Optional[float] = None,
                        scale: Optional[float] = None,
                        q_segment_ids=None, k_segment_ids=None,
                        q_times=None, k_times=None,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool = False):
    """Raw backward kernel invocation. Requires block-aligned inputs.

    q: (B, Hq, Sq, D); k: (B, Hkv, Sk, D); v: (B, Hkv, Sk, Dv);
    o/do: (B, Hq, Sq, Dv); lse: (B, Hq, Sq) float32 (from
    ``flash_attention_fwd(..., return_lse=True)``). Returns
    (dq, dk, dv) in the dtypes of (q, k, v).

    Padded query rows must carry ``do == 0`` (the ``ops`` wrapper pads the
    cotangent with zeros), which zeroes their dk/dv contributions without
    needing a row-validity mask; padded key columns are excluded via
    segment id -1, exactly as in the forward.
    """
    b, hq, sq, d = q.shape
    _, hkv, sk, dv = v.shape
    assert k.shape == (b, hkv, sk, d), (q.shape, k.shape, v.shape)
    assert do.shape == o.shape == (b, hq, sq, dv), (do.shape, o.shape)
    assert lse.shape == (b, hq, sq), lse.shape
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

    # FlashAttention-2 preprocess: delta_i = sum_j dO_ij O_ij, an O(Sq)
    # elementwise reduce that XLA fuses well; not worth a kernel. Both row
    # terms travel as (B, Hq, Sq, 1) so their (block_q, 1) tiles are legal.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                    keepdims=True)
    lse = lse.astype(jnp.float32)[..., None]
    q_segment_ids, k_segment_ids = row_operands(q_segment_ids, k_segment_ids)
    q_times, k_times = row_operands(q_times, k_times)

    common = dict(scale=float(scale), causal=causal, window=window,
                  softcap=softcap, block_q=block_q, block_k=block_k,
                  use_segments=use_segments, use_times=use_times)

    q_row = pl.BlockSpec((1, block_q, 1), lambda b_, h, iq, ik: (b_, iq, 0))
    k_row = pl.BlockSpec((1, 1, block_k), lambda b_, h, iq, ik: (b_, 0, ik))
    q_col = pl.BlockSpec((1, 1, block_q, 1),
                         lambda b_, h, iq, ik: (b_, h, iq, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, num_k_blocks=nk, **common),
        grid=(b, hq, nq, nk),
        in_specs=[
            q_row, k_row, q_row, k_row,
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h, iq, ik: (b_, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h, iq, ik: (b_, h // group, ik, 0)),
            pl.BlockSpec((1, 1, block_k, dv),
                         lambda b_, h, iq, ik: (b_, h // group, ik, 0)),
            pl.BlockSpec((1, 1, block_q, dv),
                         lambda b_, h, iq, ik: (b_, h, iq, 0)),
            q_col, q_col,
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b_, h, iq, ik: (b_, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention_dq",
    )(q_segment_ids, k_segment_ids, q_times, k_times,
      q, k, v, do, lse, delta)

    # The inner dimension fuses (head-in-group, q_block): head index
    # h*group + iqg // nq, q block iqg % nq.
    num_inner = group * nq

    def _qh(h, iqg):
        return h * group + iqg // nq

    q_row = pl.BlockSpec((1, block_q, 1),
                         lambda b_, h, ik, iqg: (b_, iqg % nq, 0))
    k_row = pl.BlockSpec((1, 1, block_k), lambda b_, h, ik, iqg: (b_, 0, ik))
    q_col = pl.BlockSpec((1, 1, block_q, 1),
                         lambda b_, h, ik, iqg: (b_, _qh(h, iqg), iqg % nq, 0))

    dk, dv_out = pl.pallas_call(
        functools.partial(_dkv_kernel, num_q_blocks=nq, num_inner=num_inner,
                          **common),
        grid=(b, hkv, nk, num_inner),
        in_specs=[
            q_row, k_row, q_row, k_row,
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h, ik, iqg: (b_, _qh(h, iqg),
                                                 iqg % nq, 0)),
            pl.BlockSpec((1, 1, block_q, dv),
                         lambda b_, h, ik, iqg: (b_, _qh(h, iqg),
                                                 iqg % nq, 0)),
            q_col, q_col,
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h, ik, iqg: (b_, h, ik, 0)),
            pl.BlockSpec((1, 1, block_k, dv),
                         lambda b_, h, ik, iqg: (b_, h, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h, ik, iqg: (b_, h, ik, 0)),
            pl.BlockSpec((1, 1, block_k, dv),
                         lambda b_, h, ik, iqg: (b_, h, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b, hkv, sk, dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),     # dk accumulator
            pltpu.VMEM((block_k, dv), jnp.float32),    # dv accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention_dkv",
    )(q_segment_ids, k_segment_ids, q_times, k_times,
      q, do, lse, delta, k, v)

    return dq, dk, dv_out
