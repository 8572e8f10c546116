"""Public attention ops: padded, autodiff-capable wrappers over the kernels.

``attention(...)`` is the single entry point the model stack uses; ``impl``
selects between:

  * ``"flash"``   — the Pallas TPU kernels, forward AND backward. On CPU the
    kernels run in interpret mode (used by tests).
  * ``"chunked"`` — pure-XLA linear-memory online-softmax attention
    (``ref.mha_chunked``); the implementation lowered in the multi-pod
    dry-run, and the default on CPU where interpret-mode Pallas is slow.
  * ``"ref"``     — O(S^2) reference (small inputs / oracle).

The flash path is wired with ``jax.custom_vjp``: the forward runs the Pallas
kernel and saves its log-sum-exp rows; the backward dispatches on
``bwd_impl``:

  * ``"pallas"`` (default) — the FlashAttention-style Pallas backward kernels
    (``repro.kernels.flash_attention_bwd``): a dq kernel and a dk/dv kernel,
    both recomputing block probabilities from the saved LSE in VMEM.
  * ``"xla"``    — the blocked-XLA recurrence (``_bwd_chunked``), kept as a
    selectable fallback and as the gradient parity oracle.

Either way training stays linear-memory end to end. The process-wide default
can be overridden with the ``REPRO_FLASH_BWD`` environment variable.
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as fa
from repro.kernels import flash_attention_bwd as fab
from repro.kernels import flash_decode as fd
from repro.kernels import ref

#: Default backend for the flash-attention backward pass. ``"pallas"`` runs
#: the Pallas kernels (interpret mode off-TPU); ``"xla"`` runs the blocked
#: recurrence. Overridable per call via ``flash_attention(bwd_impl=...)``.
DEFAULT_BWD_IMPL = os.environ.get("REPRO_FLASH_BWD", "pallas")


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


# the single padding implementation for the kernels package lives next to
# the decode kernel (this module imports it; the reverse would be a cycle)
_pad_to = fd.pad_to_multiple


def _decode_block_q(sq: int, block_q: int) -> int:
    """Shrink the query block for small-q (decode) calls.

    A decode step has q_len in the single digits; padding it to the default
    128-row block wastes ~99% of the MXU work. 16 sublanes is the minimum
    tile for every supported dtype (f32 needs 8, bf16 needs 16), so round
    the query length up to a multiple of 16 and never exceed the caller's
    block_q.
    """
    if sq >= block_q:
        return block_q
    return max(16, -(-sq // 16) * 16)


def _fold_kv_length(kv_length, q_seg, k_seg, b, sq, sk):
    """Fold decode-cursor masking into the segment-id machinery.

    Key positions at or beyond ``kv_length`` (scalar or per-row ``(B,)``
    cursors) get segment id -1, which the kernel's segment mask always
    rejects — the same mechanism that hides padded key rows. This reuses
    the existing kernel feature set instead of threading another operand
    through the Pallas call (and through the custom_vjp residuals).

    **Cost caveat**: the fold only changes the *mask*, not the iteration
    space. The generic kernel (and ``ref.mha_chunked``) still fetches and
    multiplies every KV block of the preallocated cache — dead rows are
    rejected after their HBM load and MXU work are already paid, so a
    decode tick costs O(max_len) regardless of the cursor. That is fine
    for training-shaped calls (the cache IS the sequence) but wrong for
    the rollout hot path; :func:`decode_attention` dispatches decode
    shapes to the split-K ragged kernel (``flash_decode.py``), which
    bounds both loads and FLOPs by the live prefix and keeps this path
    only as the parity oracle / fallback.
    """
    kvl = jnp.asarray(kv_length, jnp.int32)
    if kvl.ndim == 0:
        kvl = jnp.broadcast_to(kvl[None], (b,))
    live = jnp.arange(sk, dtype=jnp.int32)[None, :] < kvl[:, None]  # (B, Sk)
    # Materialize BOTH sides: the kernel enables its segment mask off
    # q_segment_ids alone, and a caller may legitimately pass either side
    # without the other (e.g. cache-side ids with all-valid queries).
    if q_seg is None:
        q_seg = jnp.zeros((b, sq), jnp.int32)
    if k_seg is None:
        k_seg = jnp.zeros((b, sk), jnp.int32)
    k_seg = jnp.where(live, k_seg, -1)
    return q_seg, k_seg


# ---------------------------------------------------------------------------
# Flash path: Pallas forward + Pallas (or blocked-XLA) backward, custom_vjp.
# ---------------------------------------------------------------------------

def _pad_all(q, k, v, q_seg, k_seg, q_times, k_times, *, block_q, block_k):
    """Pad sequences to block multiples and head dims to lane multiples.

    Zero-padding the qk contraction dim leaves scores unchanged; zero-padded
    dv columns are sliced off by the caller. Padded key rows get segment id
    -1 (always masked); padded query rows produce garbage rows that the
    caller slices off (forward) or that contribute zero because the padded
    cotangent is zero (backward).
    """
    b, hq, sq, d = q.shape
    _, hkv, sk, dv = v.shape
    q, _ = _pad_to(q, 128, 3)
    k, _ = _pad_to(k, 128, 3)
    v, dv_pad = _pad_to(v, 128, 3)
    need_seg = (sq % block_q != 0) or (sk % block_k != 0)
    if q_seg is None and need_seg:
        q_seg = jnp.zeros((b, sq), jnp.int32)
        k_seg = jnp.zeros((b, sk), jnp.int32)
    if q_seg is not None:
        q_seg, _ = _pad_to(q_seg, block_q, 1, value=0)
        k_seg, _ = _pad_to(k_seg, block_k, 1, value=-1)
    if q_times is not None:
        q_times, _ = _pad_to(q_times, block_q, 1, value=0)
        k_times, _ = _pad_to(k_times, block_k, 1, value=0)
    q, q_pad = _pad_to(q, block_q, 2)
    k, _ = _pad_to(k, block_k, 2)
    v, _ = _pad_to(v, block_k, 2)
    return q, k, v, q_seg, k_seg, q_times, k_times, q_pad, dv_pad


def _flash_fwd_padded(q, k, v, q_seg, k_seg, q_times, k_times, *, causal,
                      window, softcap, scale, block_q, block_k, interpret):
    """Run the forward kernel on padded operands; returns (out, lse)."""
    b, hq, sq, d = q.shape
    _, hkv, sk, dv = v.shape
    if scale is None:
        scale = 1.0 / float(d) ** 0.5
    q, k, v, q_seg, k_seg, q_times, k_times, q_pad, dv_pad = _pad_all(
        q, k, v, q_seg, k_seg, q_times, k_times,
        block_q=block_q, block_k=block_k)
    out, lse = fa.flash_attention_fwd(
        q, k, v, causal=causal, window=window, softcap=softcap, scale=scale,
        q_segment_ids=q_seg, k_segment_ids=k_seg,
        q_times=q_times, k_times=k_times,
        block_q=block_q, block_k=block_k, interpret=interpret,
        return_lse=True)
    if q_pad:
        out = out[:, :, :sq, :]
        lse = lse[:, :, :sq]
    if dv_pad:
        out = out[..., :dv]
    return out, lse


def _bwd_pallas(saved, g, *, causal, window, softcap, scale, block_q,
                block_k, interpret):
    """Pallas backward: pad exactly like the forward, run the dq and dk/dv
    kernels, slice the padding back off.

    The cotangent (and hence ``delta``) is zero on padded query rows, which
    zeroes their dk/dv contributions; padded key rows carry segment id -1 and
    are masked out of dq.
    """
    q, k, v, o, lse, q_seg, k_seg, q_times, k_times = saved
    b, hq, sq, d = q.shape
    _, hkv, sk, dv = v.shape
    if scale is None:
        scale = 1.0 / float(d) ** 0.5
    qp, kp, vp, q_seg, k_seg, q_times, k_times, _, _ = _pad_all(
        q, k, v, q_seg, k_seg, q_times, k_times,
        block_q=block_q, block_k=block_k)
    gp, _ = _pad_to(g, 128, 3)
    gp, _ = _pad_to(gp, block_q, 2)
    op, _ = _pad_to(o, 128, 3)
    op, _ = _pad_to(op, block_q, 2)
    lsep, _ = _pad_to(lse, block_q, 2)
    dq, dk, dv_grad = fab.flash_attention_bwd(
        qp, kp, vp, op, lsep, gp, causal=causal, window=window,
        softcap=softcap, scale=scale,
        q_segment_ids=q_seg, k_segment_ids=k_seg,
        q_times=q_times, k_times=k_times,
        block_q=block_q, block_k=block_k, interpret=interpret)
    return (dq[:, :, :sq, :d], dk[:, :, :sk, :d], dv_grad[:, :, :sk, :dv])


def _bwd_chunked(saved, g, *, causal, window, softcap, scale, chunk_size=512):
    """Linear-memory attention backward (FlashAttention recurrence in XLA).

    Recomputes block logits from (q, k) chunk by chunk; never materializes
    an (Sq, Sk) tensor. Handles GQA by accumulating dk/dv over head groups.
    """
    q, k, v, o, lse, q_seg, k_seg, q_times, k_times = saved
    b, hq, sq, d = q.shape
    _, hkv, sk, dv = v.shape
    group = hq // hkv
    if scale is None:
        scale = 1.0 / float(d) ** 0.5
    qf = q.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    of = o.astype(jnp.float32)
    delta = jnp.sum(gf * of, axis=-1)                    # (b, hq, sq)

    if sk % chunk_size != 0:
        pad = chunk_size - sk % chunk_size
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        if k_seg is None:
            k_seg = jnp.zeros((b, sk), jnp.int32)
            q_seg = jnp.zeros((b, sq), jnp.int32)
        k_seg = jnp.pad(k_seg, ((0, 0), (0, pad)), constant_values=-1)
        if k_times is not None:
            k_times = jnp.pad(k_times, ((0, 0), (0, pad)))
    sk_p = k.shape[2]
    n_chunks = sk_p // chunk_size

    def body(dq, idx):
        start = idx * chunk_size
        kc = jax.lax.dynamic_slice_in_dim(k, start, chunk_size, 2)
        vc = jax.lax.dynamic_slice_in_dim(v, start, chunk_size, 2)
        kcr = jnp.repeat(kc, group, axis=1).astype(jnp.float32)
        vcr = jnp.repeat(vc, group, axis=1).astype(jnp.float32)
        s_pre = jnp.einsum("bhnd,bhmd->bhnm", qf, kcr) * scale
        if softcap is not None and softcap > 0:
            t = jnp.tanh(s_pre / softcap)
            s = t * softcap
            dcap = 1.0 - t * t
        else:
            s = s_pre
            dcap = None
        if q_times is not None:
            rows = q_times[:, :, None]
            cols = jax.lax.dynamic_slice_in_dim(
                k_times, start, chunk_size, 1)[:, None, :]
            mask = jnp.ones((b, sq, chunk_size), bool)
        else:
            rows = jax.lax.broadcasted_iota(
                jnp.int32, (sq, chunk_size), 0)[None]
            cols = (jax.lax.broadcasted_iota(
                jnp.int32, (sq, chunk_size), 1) + start)[None]
            mask = jnp.ones((1, sq, chunk_size), bool)
        if causal:
            mask = mask & (cols <= rows)
        if window is not None:
            mask = mask & (cols > rows - window)
        mask = mask[:, None]
        if q_seg is not None:
            ks = jax.lax.dynamic_slice_in_dim(k_seg, start, chunk_size, 1)
            seg = (q_seg[:, :, None] == ks[:, None, :]) & (ks[:, None, :] >= 0)
            mask = mask & seg[:, None]
        p = jnp.where(mask, jnp.exp(s - lse[..., None]), 0.0)
        dp = jnp.einsum("bhnd,bhmd->bhnm", gf, vcr)
        ds = p * (dp - delta[..., None])
        if dcap is not None:
            ds = ds * dcap
        ds = ds * scale
        dq = dq + jnp.einsum("bhnm,bhmd->bhnd", ds, kcr)
        dkc = jnp.einsum("bhnm,bhnd->bhmd", ds, qf)
        dvc = jnp.einsum("bhnm,bhnd->bhmd", p, gf)
        if group > 1:
            dkc = dkc.reshape(b, hkv, group, chunk_size, d).sum(axis=2)
            dvc = dvc.reshape(b, hkv, group, chunk_size, dv).sum(axis=2)
        return dq, (dkc, dvc)

    dq0 = jnp.zeros_like(qf)
    dq, (dks, dvs) = jax.lax.scan(body, dq0, jnp.arange(n_chunks))
    dk = jnp.moveaxis(dks, 0, 2).reshape(b, hkv, sk_p, d)[:, :, :sk]
    dv = jnp.moveaxis(dvs, 0, 2).reshape(b, hkv, sk_p, dv)[:, :, :sk]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(7, 8, 9, 10, 11, 12, 13, 14))
def _flash(q, k, v, q_seg, k_seg, q_times, k_times, causal, window, softcap,
           scale, block_q, block_k, interpret, bwd_impl):
    out, _ = _flash_fwd_padded(q, k, v, q_seg, k_seg, q_times, k_times,
                               causal=causal, window=window, softcap=softcap,
                               scale=scale, block_q=block_q, block_k=block_k,
                               interpret=interpret)
    return out


def _flash_fwd_rule(q, k, v, q_seg, k_seg, q_times, k_times, causal, window,
                    softcap, scale, block_q, block_k, interpret, bwd_impl):
    # The forward kernel emits its log-sum-exp rows as a second output; the
    # backward recomputes block probabilities from them, so the residuals are
    # all O(S): no (Sq, Sk) tensor is ever saved.
    out, lse = _flash_fwd_padded(q, k, v, q_seg, k_seg, q_times, k_times,
                                 causal=causal, window=window,
                                 softcap=softcap, scale=scale,
                                 block_q=block_q, block_k=block_k,
                                 interpret=interpret)
    return out, (q, k, v, out, lse, q_seg, k_seg, q_times, k_times)


def _flash_bwd_rule(causal, window, softcap, scale, block_q, block_k,
                    interpret, bwd_impl, saved, g):
    if bwd_impl == "pallas":
        dq, dk, dv = _bwd_pallas(saved, g, causal=causal, window=window,
                                 softcap=softcap, scale=scale,
                                 block_q=block_q, block_k=block_k,
                                 interpret=interpret)
    elif bwd_impl == "xla":
        dq, dk, dv = _bwd_chunked(saved, g, causal=causal, window=window,
                                  softcap=softcap, scale=scale)
    else:
        raise ValueError(f"unknown bwd_impl {bwd_impl!r} "
                         "(expected 'pallas' or 'xla')")
    return dq, dk, dv, None, None, None, None


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q, k, v, *, causal: bool = False,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    scale: Optional[float] = None,
                    q_segment_ids=None, k_segment_ids=None,
                    q_times=None, k_times=None,
                    kv_length=None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None,
                    bwd_impl: Optional[str] = None):
    """Differentiable flash attention (Pallas forward and backward).

    ``bwd_impl`` selects the backward backend: ``"pallas"`` (default; the
    FlashAttention-style dq and dk/dv kernels) or ``"xla"`` (the blocked
    recurrence — the fallback and parity oracle). The default comes from
    ``DEFAULT_BWD_IMPL`` / the ``REPRO_FLASH_BWD`` environment variable.

    ``kv_length`` (scalar or per-row ``(B,)`` decode cursors) masks key
    positions at or beyond it — the incremental-decode path where ``k``/``v``
    are preallocated caches only partially written. It is folded into the
    segment-id mask, so it composes with every other feature. Small-q calls
    (``q_len < block_q``, the decode shape) automatically shrink the query
    block to the minimum legal tile instead of padding to 128 rows.
    """
    if interpret is None:
        interpret = _default_interpret()
    if bwd_impl is None:
        bwd_impl = DEFAULT_BWD_IMPL
    block_q = _decode_block_q(q.shape[2], block_q)
    if kv_length is not None:
        q_segment_ids, k_segment_ids = _fold_kv_length(
            kv_length, q_segment_ids, k_segment_ids,
            q.shape[0], q.shape[2], k.shape[2])
    return _flash(q, k, v, q_segment_ids, k_segment_ids, q_times, k_times,
                  causal, window, softcap, scale, block_q, block_k, interpret,
                  bwd_impl)


# ---------------------------------------------------------------------------
# Decode dispatcher: small-q attention over a partially-written KV cache.
# ---------------------------------------------------------------------------

def decode_attention(q, k, v, *, kv_length, impl: str = "auto",
                     scale: Optional[float] = None,
                     q_segment_ids=None, k_segment_ids=None,
                     q_times=None, k_times=None,
                     k_scale=None, v_scale=None,
                     block_k: int = 128,
                     num_splits: Optional[int] = None,
                     interpret: Optional[bool] = None,
                     layer: Optional[int] = None,
                     v_dim: Optional[int] = None):
    """Attention for the incremental-decode shape: a handful of query
    tokens against a preallocated (and possibly quantized) KV cache whose
    live prefix is bounded by per-row ``kv_length`` cursors.

    ``impl`` selects:

      * ``"auto"``         — ``"flash_decode"`` on TPU, ``"xla"`` elsewhere.
      * ``"flash_decode"`` — the Pallas split-K ragged kernel
        (``repro.kernels.flash_decode``): O(live-prefix) loads and FLOPs,
        in-kernel dequantization of int8 caches.
      * ``"xla"``          — the same cursor-bounded algorithm as a pure-XLA
        ``fori_loop`` over live key blocks (dynamic trip count); the
        production path on CPU.
      * ``"ref"`` / ``"chunked"`` / ``"flash"`` — the *generic* kernels with
        ``kv_length`` folded into the mask. These scan the whole
        preallocated cache every call (see :func:`_fold_kv_length`) and are
        kept as the parity oracle for every decode flag combination —
        quantized caches are dequantized up front with
        :func:`flash_decode.dequantize_kv` before the generic call.

    ``k_scale``/``v_scale`` (B, Hkv, Sk) float32 mark ``k``/``v`` as int8
    caches with per-(head, token) scales. ``layer`` (static int) marks
    ``k``/``v`` as the model's layer-stacked, feature-major
    ``(L, B, Hkv, c, Sk)`` cache buffers (scales ``(L, B, Hkv, Sk)``),
    which the ragged paths index in place — the per-layer slice is never
    materialized (the generic fallbacks *do* materialize it, and swap it
    back to ``(B, Hkv, Sk, c)``; they are O(max_len) oracles either
    way). ``v=None`` with ``v_dim`` marks a latent cache whose values are
    the first ``v_dim`` features of the key rows (MLA's absorbed decode).
    Masking semantics (block-causal ``q_times``/``k_times``, segment ids,
    grouped query heads) match :func:`attention` with ``causal=True``;
    decode is inference-only, so none of these paths define a VJP.
    """
    if impl == "auto":
        impl = "flash_decode" if jax.default_backend() == "tpu" else "xla"
    if impl == "flash_decode":
        if interpret is None:
            interpret = _default_interpret()
        return fd.flash_decode(
            q, k, v, kv_length, k_scale=k_scale, v_scale=v_scale,
            q_segment_ids=q_segment_ids, k_segment_ids=k_segment_ids,
            q_times=q_times, k_times=k_times, scale=scale,
            block_k=block_k, num_splits=num_splits, interpret=interpret,
            layer=layer, v_dim=v_dim)
    if impl == "xla":
        return fd.decode_ragged_xla(
            q, k, v, kv_length, k_scale=k_scale, v_scale=v_scale,
            q_segment_ids=q_segment_ids, k_segment_ids=k_segment_ids,
            q_times=q_times, k_times=k_times, scale=scale, block_k=block_k,
            layer=layer, v_dim=v_dim)
    if impl in ("ref", "chunked", "flash"):
        if v is None:
            v = k[..., :v_dim, :] if layer is not None else k[..., :v_dim]
        if layer is not None:
            k = jnp.swapaxes(k[layer], -1, -2)
            v = jnp.swapaxes(v[layer], -1, -2)
            k_scale = None if k_scale is None else k_scale[layer]
            v_scale = None if v_scale is None else v_scale[layer]
        if k_scale is not None:
            k = fd.dequantize_kv(k, k_scale, dtype=q.dtype)
        if v_scale is not None:
            v = fd.dequantize_kv(v, v_scale, dtype=q.dtype)
        # Causality in decode is expressed through explicit times (the
        # query rows are *appended* tokens — their positional indices
        # 0..Sq-1 say nothing about where they sit in the cache). With no
        # times, the structural mask is the cursor bound (+ segments).
        return attention(q, k, v, impl=impl, causal=q_times is not None,
                         scale=scale,
                         q_segment_ids=q_segment_ids,
                         k_segment_ids=k_segment_ids,
                         q_times=q_times, k_times=k_times,
                         kv_length=kv_length, block_k=block_k)
    raise ValueError(f"unknown decode_attention impl {impl!r}")


# ---------------------------------------------------------------------------
# Dispatcher used by the model stack.
# ---------------------------------------------------------------------------

def attention(q, k, v, *, impl: str = "auto", causal: bool = False,
              window: Optional[int] = None, softcap: Optional[float] = None,
              scale: Optional[float] = None,
              q_segment_ids=None, k_segment_ids=None,
              q_times=None, k_times=None,
              q_offset: int = 0,
              kv_length=None,
              block_q: int = 128, block_k: int = 128,
              chunk_size: Optional[int] = None,
              bwd_impl: Optional[str] = None):
    """Multi-head attention with selectable implementation.

    ``impl="auto"`` picks flash on TPU and the chunked XLA path elsewhere.
    ``q_offset`` (chunked/ref only) offsets query positions for decode.
    ``q_times/k_times``: block-causal over explicit per-token times
    (agent-simulation scenes). ``kv_length`` (all impls; scalar or per-row
    ``(B,)`` cursors) masks cache rows at or beyond the decode cursor —
    the incremental-decode path over preallocated K/V caches. ``bwd_impl``
    (flash only) selects the backward backend, see :func:`flash_attention`.
    """
    if impl == "auto":
        impl = "flash" if jax.default_backend() == "tpu" else "chunked"
    unroll = False
    if impl == "chunked_unrolled":   # dry-run mode: expand the chunk loop so
        impl, unroll = "chunked", True  # cost_analysis sees every chunk
    if impl == "flash":
        if q_offset:
            raise NotImplementedError("q_offset requires impl='chunked'/'ref'")
        return flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, scale=scale,
                               q_segment_ids=q_segment_ids,
                               k_segment_ids=k_segment_ids,
                               q_times=q_times, k_times=k_times,
                               kv_length=kv_length,
                               block_q=block_q, block_k=block_k,
                               bwd_impl=bwd_impl)
    if impl == "chunked":
        return ref.mha_chunked(q, k, v, causal=causal, window=window,
                               softcap=softcap, scale=scale,
                               q_segment_ids=q_segment_ids,
                               k_segment_ids=k_segment_ids,
                               q_times=q_times, k_times=k_times,
                               q_offset=q_offset, kv_length=kv_length,
                               chunk_size=chunk_size, unroll=unroll)
    if impl == "ref":
        return ref.mha_reference(q, k, v, causal=causal, window=window,
                                 softcap=softcap, scale=scale,
                                 q_segment_ids=q_segment_ids,
                                 k_segment_ids=k_segment_ids,
                                 q_times=q_times, k_times=k_times,
                                 q_offset=q_offset, kv_length=kv_length)
    raise ValueError(f"unknown attention impl {impl!r}")
