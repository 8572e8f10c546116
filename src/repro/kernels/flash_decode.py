"""Pallas TPU split-K flash-decode kernel for the rollout hot path.

The generic flash kernel (``flash_attention.py``) is shaped for training:
big query blocks, a sequential walk over *every* key block, and masking
folded into segment ids. A closed-loop rollout tick inverts all of those
assumptions — q_len is the handful of agent tokens appended this step,
the keys are a preallocated ``max_len`` cache that is mostly *unwritten*
(a per-slot ``kv_length`` cursor marks the live prefix), and there is no
backward pass. Routing that shape through the generic kernel wastes the
machine twice:

  1. **No parallelism.** One tiny query block means the whole (batch,
     head) program is a single sequential scan over key blocks; the MXU
     sits behind a serial dependency chain of online-softmax updates.
  2. **O(max_len) work per tick.** ``ops._fold_kv_length`` hides dead
     cache rows behind segment id -1, which masks them *after* their
     blocks are fetched from HBM and pushed through the MXU. Every tick
     pays for the whole preallocated cache, live or not.

This kernel is specialized for the decode shape:

* **Split-K parallelism** — the grid is ``(B, Hq, num_splits,
  blocks_per_split)`` with the split dimension parallel and only the
  within-split walk sequential. Each split reduces its key range to a
  partial ``(m, l, acc)`` triple (the associative online-softmax state);
  a cheap XLA combine rescales and sums the partials. Work that the
  single small-q program serialized now spreads across ``num_splits``
  programs per (batch, head).
* **Cursor-bounded ragged scanning** — ``kv_length`` rides in as a
  scalar-prefetch operand, so it is available to the BlockSpec index
  maps *before* the pipeline issues any copy: key blocks at or beyond a
  row's cursor are clamped back to the last live block (the pipeline
  elides the re-fetch of an already-resident block — no HBM traffic)
  and their compute is skipped entirely with ``pl.when`` (no MXU/VPU
  work). Each tick therefore costs O(live prefix), not O(max_len).
* **Quantized KV cache** — the cache may store the SE(2)-transformed
  K/V rows as int8 with per-(head, token) float32 scales (or as bf16);
  dequantization happens in VMEM on the tile just loaded, so the HBM
  working set of a tick shrinks 4x (2x for bf16) while all arithmetic
  stays float32.

Masking supports the decode feature set the model actually uses:
block-causal attention over explicit per-token times, segment ids, GQA
(via ``h // group`` index maps), and the ragged ``kv_length`` bound.
Softcap / sliding windows are deliberately out of scope — no decode
path uses them; fall back to the generic kernel if that changes.

``decode_ragged_xla`` is the same algorithm in pure XLA (a
``fori_loop`` whose trip count is the *batch-max* live block count — so
it is also O(live), unlike ``ref.mha_chunked`` which scans the padded
cache). It is the CPU/fallback production path and, together with
``ref.mha_reference`` over a dequantized cache, the parity oracle
(``tests/test_decode.py``).
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


# ---------------------------------------------------------------------------
# int8 KV-cache quantization helpers (shared by the cache writers, the
# kernels, and the oracle fallbacks).
# ---------------------------------------------------------------------------

#: cache storage dtypes accepted (as strings) by the model/engine
#: ``init_cache(dtype=...)`` / ``cache_dtype=`` options
CACHE_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
                "int8": jnp.int8}


def canonical_cache_dtype(dtype, default=None):
    """Resolve a cache-dtype option (string / jnp dtype / None)."""
    if dtype is None:
        return default
    if isinstance(dtype, str):
        return CACHE_DTYPES[dtype]
    return dtype


def quantize_kv(x, eps: float = 1e-8):
    """Symmetric int8 quantization over the feature axis.

    ``x`` (..., d) -> (int8 values (..., d), float32 scales (...,)). One
    scale per (batch, head, token) row: K/V rows are written to the cache
    once and never revised, so per-row absmax is exact, and a row's scale
    travels beside it in the cache.
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax, eps) / 127.0
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127.0, 127.0)
    return q.astype(jnp.int8), scale


def dequantize_kv(q, scale, dtype=jnp.float32):
    """Inverse of :func:`quantize_kv` (used by the XLA oracle paths; the
    Pallas kernel dequantizes per-tile in VMEM instead)."""
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


# ---------------------------------------------------------------------------
# The split-K kernel.
# ---------------------------------------------------------------------------

def _column(row):
    """(1, n) lane row -> (n, 1) sublane column, bit-exact: an 8-sublane
    broadcast and a 2D transpose, both native to Mosaic."""
    return jnp.transpose(jnp.broadcast_to(row, (8, row.shape[1])))[:, :1]


def _decode_kernel(kvl_ref, *refs, scale: float, block_k: int,
                   blocks_per_split: int, num_k_blocks: int, group: int,
                   use_segments: bool, use_times: bool,
                   quant_k: bool, quant_v: bool, layered: bool):
    """One grid step: fold one key block into this split's (m, l, acc).

    Grid: (B, Hq, num_splits, blocks_per_split); the last dimension is
    sequential so the online-softmax scratch carries across it; the split
    dimension is parallel. Outputs are per-split partials, combined by
    :func:`_combine_splits`.
    """
    (q_seg_ref, k_seg_ref, q_time_ref, k_time_ref,
     q_ref, k_ref, v_ref) = refs[:7]
    i = 7
    k_scale_ref = v_scale_ref = None
    if quant_k:
        k_scale_ref = refs[i]
        i += 1
    if quant_v:
        v_scale_ref = refs[i]
        i += 1
    o_ref, m_ref, l_ref = refs[i:i + 3]
    acc_s, m_s, l_s = refs[i + 3:]

    b = pl.program_id(0)
    hk = pl.ds(pl.program_id(1) // group, 1)     # this program's kv head
    split = pl.program_id(2)
    ik = pl.program_id(3)
    jk = split * blocks_per_split + ik          # global key-block index
    k_start = jk * block_k
    kvl = kvl_ref[b]

    @pl.when(ik == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    # Ragged early-out: a block entirely at/beyond the row's cursor (or
    # past the padded key range) does no loads (its index map clamped the
    # fetch to an already-resident block) and no compute.
    live = jnp.logical_and(jk < num_k_blocks, k_start < kvl)

    # A layer-stacked cache tile is feature-major, (d, bk) / (dv, bk) with
    # the tokens in the lanes; an unstacked one is (bk, d) / (bk, dv).
    kv_idx = (0, 0, 0) if layered else (0, 0)
    tok_ax = 1 if layered else 0                 # token axis of a k/v tile

    def row_scale(ref):
        # the scale tile spans every kv head (a one-head (1, block_k) tile
        # is not a legal block); pick this program's head: a (1, bk) lane
        # row for a feature-major tile, a (bk, 1) column otherwise
        if layered:
            return ref[0, 0, hk, :]
        return _column(ref[0, hk, :])

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, d)
        k = k_ref[kv_idx].astype(jnp.float32)
        v = v_ref[kv_idx].astype(jnp.float32)
        if quant_k:
            k = k * row_scale(k_scale_ref)
        if quant_v:
            v = v * row_scale(v_scale_ref)
        s = jax.lax.dot_general(
            q, k, (((1,), (1 - tok_ax,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

        cols = jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1) + k_start
        mask = cols < kvl                            # ragged cursor bound
        if use_times:
            rows_t = q_time_ref[0]                   # (bq, 1)
            cols_t = k_time_ref[0]                   # (1, bk)
            mask = jnp.logical_and(mask, cols_t <= rows_t)
        if use_segments:
            qs = q_seg_ref[0]                        # (bq, 1)
            ks = k_seg_ref[0]                        # (1, bk)
            seg = jnp.logical_and(qs == ks, ks >= 0)
            mask = jnp.logical_and(mask, seg)
        s = jnp.where(mask, s, _NEG_INF)
        # zero unreachable rows' VALUES too, not just their weights:
        # 0 * NaN is NaN, and rows beyond the cursor may carry any bit
        # pattern (a quarantined predecessor's NaN rows included). For
        # finite stale rows this is an exact no-op (0 * finite == 0).
        reachable = jnp.any(mask, axis=0, keepdims=True)      # (1, bk)
        if not layered:
            reachable = _column(reachable.astype(jnp.float32)) > 0
        v = jnp.where(reachable, v, 0.0)

        m_prev = m_s[:, 0]
        l_prev = l_s[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)                  # dead rows stay zero
        l_s[...] = jnp.broadcast_to(
            (l_prev * alpha + jnp.sum(p, axis=-1))[:, None], l_s.shape)
        m_s[...] = jnp.broadcast_to(m_new[:, None], m_s.shape)
        acc_s[...] = acc_s[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (tok_ax,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == blocks_per_split - 1)
    def _finalize():
        o_ref[0, 0, 0] = acc_s[...]
        m_ref[0, 0, 0] = m_s[:, :1]
        l_ref[0, 0, 0] = l_s[:, :1]


def _combine_splits(o_p, m_p, l_p, out_dtype):
    """Merge per-split partial softmax states (the standard split-K
    reduction): rescale every split to the global row max, sum the
    denominators and accumulators, normalize once.

    o_p (B, H, S, bq, dv); m_p / l_p (B, H, S, bq, 1), all float32. A split
    that saw only dead blocks contributes m = -1e30 (finite sentinel, so
    exp stays NaN-free), l = 0, acc = 0 — an exact no-op in the sums.
    Rows with no live key anywhere end with l == 0 and are forced to
    zero, matching ``ref.mha_reference``'s fully-masked-row convention.
    """
    m_g = jnp.max(m_p, axis=2)                           # (B, H, bq, 1)
    alpha = jnp.exp(m_p - m_g[:, :, None])               # (B, H, S, bq, 1)
    l_g = jnp.sum(l_p * alpha, axis=2)
    o = jnp.sum(o_p * alpha, axis=2)
    out = o / jnp.maximum(l_g, 1e-30)
    return out.astype(out_dtype)


def flash_decode_fwd(q, k, v, kv_length, *,
                     k_scale=None, v_scale=None,
                     q_segment_ids=None, k_segment_ids=None,
                     q_times=None, k_times=None,
                     scale: Optional[float] = None,
                     block_k: int = 128,
                     num_splits: Optional[int] = None,
                     interpret: bool = False,
                     layer: Optional[int] = None):
    """Raw kernel invocation. Requires aligned shapes.

    q (B, Hq, Sq, D) with Sq the (small, padded) decode query block;
    k (B, Hkv, Sk, D); v (B, Hkv, Sk, Dv); Sk % block_k == 0.
    ``kv_length`` (B,) int32 live-prefix cursors. ``k_scale``/``v_scale``
    (B, Hkv, Sk) float32 mark the cache as int8-quantized. Returns
    (B, Hq, Sq, Dv) in q.dtype.

    With ``layer=i`` (static int) the cache operands are the model's
    layer-stacked, feature-major buffers — k (L, B, Hkv, D, Sk), v (L, B,
    Hkv, Dv, Sk), scales (L, B, Hkv, Sk) — and the BlockSpec index maps
    address layer ``i`` directly, so no per-layer slice of the stacked
    cache is ever materialized (see :func:`decode_ragged_xla`). Their
    tiles are (D, block_k) / (Dv, block_k): ``q @ k`` is a plain matmul
    and ``p @ v^T`` contracts both operands' token axes.
    """
    b, hq, sq, d = q.shape
    if layer is None:
        _, hkv, sk, dv = v.shape
        assert k.shape == (b, hkv, sk, d), (q.shape, k.shape, v.shape)
    else:
        nl, _, hkv, dv, sk = v.shape
        assert k.shape == (nl, b, hkv, d, sk), (q.shape, k.shape, v.shape)
        assert 0 <= layer < nl, (layer, nl)
    assert hq % hkv == 0, (hq, hkv)
    assert sk % block_k == 0, (sk, block_k)
    group = hq // hkv
    if scale is None:
        scale = 1.0 / float(d) ** 0.5
    nk = sk // block_k
    if num_splits is None:
        num_splits = min(nk, 8)
    num_splits = max(1, min(num_splits, nk))
    bps = -(-nk // num_splits)                   # blocks per split
    kvl = jnp.asarray(kv_length, jnp.int32)
    if kvl.ndim == 0:
        kvl = jnp.broadcast_to(kvl[None], (b,))

    use_segments = q_segment_ids is not None
    if not use_segments:
        q_segment_ids = jnp.zeros((b, sq), jnp.int32)
        k_segment_ids = jnp.zeros((b, sk), jnp.int32)
    use_times = q_times is not None
    if not use_times:
        q_times = jnp.zeros((b, sq), jnp.int32)
        k_times = jnp.zeros((b, sk), jnp.int32)
    quant_k = k_scale is not None
    quant_v = v_scale is not None

    def _clamped(jk, kvl_b):
        # Last live block for this row; dead grid steps re-map to it so
        # the pipeline never fetches beyond the cursor (a repeated block
        # index is not re-copied), and in-kernel predication skips their
        # compute anyway.
        nlive = (kvl_b + block_k - 1) // block_k
        hi = jnp.maximum(jnp.minimum(nlive, nk) - 1, 0)
        return jnp.minimum(jk, hi)

    # Scale tiles span all kv heads: (1, block_k) of one head is not a
    # legal block unless Hkv == 1, (Hkv, block_k) always is.
    if layer is None:
        def kv_map(b_, h, s, ik, kvl_ref):
            return (b_, h // group, _clamped(s * bps + ik, kvl_ref[b_]), 0)

        def kvec_map(b_, h, s, ik, kvl_ref):
            return (b_, 0, _clamped(s * bps + ik, kvl_ref[b_]))

        kv_block = (1, hkv, block_k)
        kd_block = (1, 1, block_k, d)
        kdv_block = (1, 1, block_k, dv)
    else:
        def kv_map(b_, h, s, ik, kvl_ref):
            return (layer, b_, h // group, 0,
                    _clamped(s * bps + ik, kvl_ref[b_]))

        def kvec_map(b_, h, s, ik, kvl_ref):
            return (layer, b_, 0, _clamped(s * bps + ik, kvl_ref[b_]))

        kv_block = (1, 1, hkv, block_k)
        kd_block = (1, 1, 1, d, block_k)
        kdv_block = (1, 1, 1, dv, block_k)

    def krow_map(b_, h, s, ik, kvl_ref):
        return (b_, 0, _clamped(s * bps + ik, kvl_ref[b_]))

    q_segment_ids, k_segment_ids = row_operands(q_segment_ids, k_segment_ids)
    q_times, k_times = row_operands(q_times, k_times)
    q_row = pl.BlockSpec((1, sq, 1), lambda b_, h, s, ik, kvl_ref: (b_, 0, 0))
    k_row = pl.BlockSpec((1, 1, block_k), krow_map)
    in_specs = [
        q_row, k_row, q_row, k_row,
        pl.BlockSpec((1, 1, sq, d),
                     lambda b_, h, s, ik, kvl_ref: (b_, h, 0, 0)),
        pl.BlockSpec(kd_block, kv_map),
        pl.BlockSpec(kdv_block, kv_map),
    ]
    inputs = [q_segment_ids, k_segment_ids, q_times, k_times, q, k, v]
    if quant_k:
        in_specs.append(pl.BlockSpec(kv_block, kvec_map))
        inputs.append(k_scale.astype(jnp.float32))
    if quant_v:
        in_specs.append(pl.BlockSpec(kv_block, kvec_map))
        inputs.append(v_scale.astype(jnp.float32))

    kernel = functools.partial(
        _decode_kernel, scale=float(scale), block_k=block_k,
        blocks_per_split=bps, num_k_blocks=nk, group=group,
        use_segments=use_segments, use_times=use_times,
        quant_k=quant_k, quant_v=quant_v, layered=layer is not None)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hq, num_splits, bps),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, 1, sq, dv),
                         lambda b_, h, s, ik, kvl_ref: (b_, h, s, 0, 0)),
            pl.BlockSpec((1, 1, 1, sq, 1),
                         lambda b_, h, s, ik, kvl_ref: (b_, h, s, 0, 0)),
            pl.BlockSpec((1, 1, 1, sq, 1),
                         lambda b_, h, s, ik, kvl_ref: (b_, h, s, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((sq, dv), jnp.float32),     # acc
            pltpu.VMEM((sq, 128), jnp.float32),    # m (running max)
            pltpu.VMEM((sq, 128), jnp.float32),    # l (running denom)
        ],
    )
    o_p, m_p, l_p = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, num_splits, sq, dv), jnp.float32),
            jax.ShapeDtypeStruct((b, hq, num_splits, sq, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hq, num_splits, sq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_decode",
    )(kvl, *inputs)
    return _combine_splits(o_p, m_p, l_p, q.dtype)


# ---------------------------------------------------------------------------
# Padded public wrapper.
# ---------------------------------------------------------------------------

def pad_to_multiple(x, multiple, axis, value=0):
    """Pad ``axis`` up to a multiple; returns (padded, pad_amount).

    The single padding implementation for the kernels package —
    ``ops._pad_to`` aliases it (ops imports this module, not vice versa).
    """
    size = x.shape[axis]
    rem = size % multiple
    if rem == 0:
        return x, 0
    pad = multiple - rem
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value), pad


def _pad_axis(x, multiple, axis, value=0):
    return pad_to_multiple(x, multiple, axis, value)[0]


def flash_decode(q, k, v, kv_length, *,
                 k_scale=None, v_scale=None,
                 q_segment_ids=None, k_segment_ids=None,
                 q_times=None, k_times=None,
                 scale: Optional[float] = None,
                 block_k: int = 128,
                 num_splits: Optional[int] = None,
                 interpret: bool = False,
                 layer: Optional[int] = None):
    """Split-K ragged flash decode over arbitrary (unaligned) shapes.

    Pads head dims to 128 lanes, the query length to a 16-sublane tile,
    and the key length to ``block_k``; slices the padding back off. Key
    rows introduced by padding sit at positions >= ``kv_length`` and are
    already unreachable through the ragged bound — no extra masking
    operand is needed. Inference-only (no custom_vjp): the decode path
    never differentiates.

    With ``layer`` set (layer-stacked, feature-major (L, B, H, c, S)
    cache operands), the cache is consumed **in place** and must already
    be token-aligned: ``S % block_k == 0`` (or ``S <= block_k``, which
    shrinks the block) — padding it here would copy the whole
    preallocated buffer every call. ``RolloutEngine`` and ``SimServer``
    round ``max_len`` up to a 128 multiple for exactly this reason.
    """
    b, hq, sq, d = q.shape
    if layer is None:
        sk, dv = v.shape[-2], v.shape[-1]
    else:
        dv, sk = v.shape[-2], v.shape[-1]
    if scale is None:
        scale = 1.0 / float(d) ** 0.5
    q = _pad_axis(q, 16, 2)
    if layer is None:
        q = _pad_axis(q, 128, 3)
        k = _pad_axis(_pad_axis(k, 128, 3), block_k, 2)
        v = _pad_axis(_pad_axis(v, 128, 3), block_k, 2)
        if k_scale is not None:
            k_scale = _pad_axis(k_scale, block_k, 2)   # (B, Hkv, Sk)
        if v_scale is not None:
            v_scale = _pad_axis(v_scale, block_k, 2)
    else:
        block_k = min(block_k, sk)
        if sk % block_k != 0:
            raise ValueError(
                f"layer-stacked decode caches must be block-aligned "
                f"(S={sk}, block_k={block_k}): padding in the hot path "
                f"would copy the whole cache every tick — allocate "
                f"max_len rounded up to a multiple of {block_k}")
        # Feature dims are consumed as allocated (padding would copy the
        # cache). They sit on the tiles' sublane axis, with the
        # 128-aligned tokens in the lanes, so a width that is a multiple
        # of 8 (200, 24) tiles with no padding at all.
    if q_segment_ids is not None:
        q_segment_ids = _pad_axis(q_segment_ids, 16, 1, value=0)
        k_segment_ids = _pad_axis(k_segment_ids, block_k, 1, value=-1)
    if q_times is not None:
        q_times = _pad_axis(q_times, 16, 1, value=0)
        k_times = _pad_axis(k_times, block_k, 1, value=0)
    out = flash_decode_fwd(
        q, k, v, kv_length, k_scale=k_scale, v_scale=v_scale,
        q_segment_ids=q_segment_ids, k_segment_ids=k_segment_ids,
        q_times=q_times, k_times=k_times, scale=scale, block_k=block_k,
        num_splits=num_splits, interpret=interpret, layer=layer)
    return out[:, :, :sq, :dv]


# ---------------------------------------------------------------------------
# XLA ragged decode: the same O(live-prefix) algorithm without Pallas.
# ---------------------------------------------------------------------------

def decode_ragged_xla(q, k, v, kv_length, *,
                      k_scale=None, v_scale=None,
                      q_segment_ids=None, k_segment_ids=None,
                      q_times=None, k_times=None,
                      scale: Optional[float] = None,
                      block_k: int = 128,
                      layer: Optional[int] = None):
    """Cursor-bounded online-softmax decode in pure XLA.

    A ``fori_loop`` whose trip count is the **batch-max** live block
    count (``ceil(max(kv_length) / block_k)``) — a dynamic bound, lowered
    to a while loop, so each tick's work scales with the live cache
    prefix rather than the preallocated ``max_len``. This is the
    production decode path on CPU (where interpret-mode Pallas is slow)
    and the differentiation-free XLA twin of :func:`flash_decode`.

    Two details keep it truly O(live prefix) per call:

    * **No padding, ever.** Instead of padding the cache to a block
      multiple (which would copy the whole preallocated buffer every
      tick), the final partial block clamps its slice start to
      ``S - block_k`` and masks the re-read rows out (``cols >= start``)
      so every row is folded exactly once.
    * **Layer-stacked caches are sliced in place.** With ``layer=i``
      (a static int), ``k``/``v`` are the model's full stacked,
      feature-major ``(L, B, Hkv, c, S)`` cache buffers and every block
      read is a single ``dynamic_slice`` at ``(i, 0, 0, 0, start)`` —
      the per-layer ``(B, Hkv, c, S)`` view is never materialized.
      (Slicing the layer out first — e.g. threading the cache through
      ``lax.scan`` xs/ys — copies O(max_len) per layer per tick and
      silently erases the ragged win; that is exactly the regression
      ``benchmarks/rollout_bench.py`` pins.)

    Quantized caches are dequantized one block at a time inside the
    loop, so the float32 working set stays O(block), mirroring the
    kernel's per-tile VMEM dequant.
    """
    b, hq, sq, d = q.shape
    feat_major = layer is not None       # stacked cache: tokens last
    if feat_major:
        _, _, hkv, dv, sk = v.shape
    else:
        _, hkv, sk, dv = v.shape
    group = hq // hkv
    if scale is None:
        scale = 1.0 / float(d) ** 0.5
    block_k = min(block_k, sk)
    kvl = jnp.asarray(kv_length, jnp.int32)
    if kvl.ndim == 0:
        kvl = jnp.broadcast_to(kvl[None], (b,))
    qf = q.astype(jnp.float32)
    n_live = (jnp.minimum(jnp.max(kvl), sk) + block_k - 1) // block_k

    def block_slice(arr, start, width, token_axis_from_end):
        """dynamic_slice of one key block straight out of ``arr`` (which
        may carry the leading layer axis), never materializing more than
        the block."""
        nd = arr.ndim
        tok_ax = nd - token_axis_from_end
        starts = [0] * nd
        sizes = list(arr.shape)
        if layer is not None:
            starts[0] = layer
            sizes[0] = 1
        starts[tok_ax] = start
        sizes[tok_ax] = width
        out = jax.lax.dynamic_slice(arr, starts, sizes)
        return out[0] if layer is not None else out

    # a k/v block is (B, H, c, bk) feature-major, else (B, H, bk, c); a
    # per-token (B, H, bk) row broadcasts along its token axis
    tok = 1 if feat_major else 2
    kv_eq = "bhdm" if feat_major else "bhmd"

    def row(x):
        return x[..., None, :] if feat_major else x[..., None]

    def body(i, carry):
        m, l, acc = carry
        start_u = i * block_k                       # nominal block start
        start = jnp.minimum(start_u, sk - block_k)  # clamped (last block)
        kc = block_slice(k, start, block_k, tok).astype(jnp.float32)
        vc = block_slice(v, start, block_k, tok).astype(jnp.float32)
        if k_scale is not None:
            kc = kc * row(block_slice(k_scale, start, block_k, 1))
        if v_scale is not None:
            vc = vc * row(block_slice(v_scale, start, block_k, 1))
        if group > 1:
            kc = jnp.repeat(kc, group, axis=1)
            vc = jnp.repeat(vc, group, axis=1)
        s = jnp.einsum(f"bhnd,{kv_eq}->bhnm", qf, kc) * scale
        cols = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, block_k), 3) \
            + start
        # rows before the nominal start were folded by an earlier block
        # (clamping only moves the final partial block backwards)
        mask = (cols < kvl[:, None, None, None]) & (cols >= start_u)
        if q_times is not None:
            ct = jax.lax.dynamic_slice_in_dim(k_times, start, block_k, 1)
            mask = mask & (ct[:, None, None, :] <= q_times[:, None, :, None])
        if q_segment_ids is not None:
            cs = jax.lax.dynamic_slice_in_dim(k_segment_ids, start,
                                              block_k, 1)
            seg = (q_segment_ids[:, None, :, None] == cs[:, None, None, :]) \
                & (cs[:, None, None, :] >= 0)
            mask = mask & seg
        s = jnp.where(mask, s, _NEG_INF)
        # zero unreachable rows' values too, not just their weights:
        # 0 * NaN is NaN, and rows beyond the cursor may carry any bit
        # pattern (a quarantined predecessor's NaN rows included). For
        # finite stale rows this is an exact no-op (0 * finite == 0).
        vc = jnp.where(row(mask.any(axis=(1, 2))[:, None]), vc, 0.0)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            f"bhnm,{kv_eq}->bhnd", p, vc)
        return m_new, l_new, acc_new

    m0 = jnp.full((b, hq, sq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hq, sq), jnp.float32)
    acc0 = jnp.zeros((b, hq, sq, dv), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_live, body, (m0, l0, acc0))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)
