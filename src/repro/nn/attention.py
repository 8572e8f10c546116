"""Attention layers: GQA/MQA/MHA with group-relative encodings, MLA, caches.

Two families:

  * :class:`Attention` — standard multi-head attention with grouped KV heads.
    Position information goes through a pluggable ``GroupEncoding`` (the
    paper's abstraction): ``rope1d`` for LMs, ``rope2d`` / ``se2_repr`` /
    ``se2_fourier`` for spatial models, ``absolute``/None for models that add
    position embeddings upstream (granite, whisper). Supports causal masks,
    sliding windows (gemma2 local layers, hymba), logit softcap (gemma2),
    partial-rotary (stablelm), and a decode KV cache.

  * :class:`MLAttention` — DeepSeek-style Multi-head Latent Attention:
    compressed KV latent + decoupled RoPE key, so the cache holds one
    ``kv_lora + rope_dim`` row per token (576 for deepseek-v2). Its cache
    is the model's layer-stacked, feature-major latent buffer ``(L, B, 1,
    kv_lora + rope_dim, Smax)``, written in place; single-token decode uses
    the *absorbed* formulation (queries projected into latent space, the
    ragged ``flash_decode`` over the latent rows, values their first
    ``kv_lora`` features), a multi-token chunk the *expanded* one (the
    slot's latent rows projected to per-head keys and values).

Shapes: activations ``(B, S, d_model)``; :class:`Attention` caches ``(B,
Hkv, Smax, D)`` plus an integer cursor handled by the caller (all cache
slots are preallocated so serve steps are shape-stable under jit).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.encodings import GroupEncoding, Rope1D
from repro.distributed.sharding import logical_constraint
from repro.kernels import ops as kops
from repro.kernels.flash_decode import (canonical_cache_dtype, dequantize_kv,
                                        quantize_kv)
from repro.nn.layers import Dense


def _split_heads(x, num_heads, head_dim):
    if x.ndim == 4:            # DenseGeneral already produced (B, S, H, D)
        return x.transpose(0, 2, 1, 3)
    b, s, _ = x.shape
    return x.reshape(b, s, num_heads, head_dim).transpose(0, 2, 1, 3)


def _merge_heads(x):
    """(B, H, S, D) -> (B, S, H, D); the output projection is a
    DenseGeneral contracting both head axes."""
    return x.transpose(0, 2, 1, 3)


def _apply_encoding(enc, transform, x, pose):
    """Apply an encoding transform to (B, H, S, D) given pose (B, S, P)."""
    return transform(x, pose[:, None, :, :])


def _cache_update(cache, new, index):
    """Write ``new`` (B, H, S, D) into ``cache`` at position ``index`` along
    the length axis. ``index`` may be a scalar (synchronized decode) or a
    per-row (B,) vector (continuous batching: per-slot cursors)."""
    new = new.astype(cache.dtype)
    if getattr(index, "ndim", 0) == 1:
        assert new.shape[2] == 1, "vector cursors require single-token steps"
        b = cache.shape[0]
        return cache.at[jnp.arange(b), :, index, :].set(new[:, :, 0, :])
    return jax.lax.dynamic_update_slice_in_dim(cache, new, index, axis=2)


@dataclasses.dataclass(frozen=True)
class Attention:
    d_model: int
    num_q_heads: int
    num_kv_heads: int
    head_dim: int
    encoding: Optional[GroupEncoding] = None
    rope_fraction: float = 1.0          # stablelm partial rotary
    causal: bool = True
    window: Optional[int] = None
    softcap: Optional[float] = None
    query_scale: Optional[float] = None  # gemma2 query_pre_attn_scalar
    use_bias: bool = False
    out_dim: Optional[int] = None
    impl: str = "chunked"

    def __post_init__(self):
        assert self.num_q_heads % self.num_kv_heads == 0

    @property
    def _odim(self):
        return self.out_dim or self.d_model

    def _projs(self):
        h, hk, hd, d = (self.num_q_heads, self.num_kv_heads, self.head_dim,
                        self.d_model)
        return {
            "q": Dense((d,), (h, hd), ("embed",), ("heads", "head_dim"),
                       use_bias=self.use_bias),
            "k": Dense((d,), (hk, hd), ("embed",), ("kv_heads", "head_dim"),
                       use_bias=self.use_bias),
            "v": Dense((d,), (hk, hd), ("embed",), ("kv_heads", "head_dim"),
                       use_bias=self.use_bias),
            "o": Dense((h, hd), (self._odim,), ("heads", "head_dim"),
                       ("embed",), use_bias=self.use_bias),
        }

    def specs(self):
        return {k: l.specs() for k, l in self._projs().items()}

    @property
    def _rot_dim(self):
        if self.encoding is None:
            return 0
        rd = int(self.head_dim * self.rope_fraction)
        return rd - rd % 2

    def _encode(self, q, k, pose):
        """Apply the group encoding to (possibly a fraction of) q/k."""
        enc = self.encoding
        if enc is None or pose is None:
            return q, k
        rd = self._rot_dim
        if rd == self.head_dim:
            q = _apply_encoding(enc, enc.transform_q, q, pose)
            k = _apply_encoding(enc, enc.transform_k, k, pose)
            return q, k
        qr = _apply_encoding(enc, enc.transform_q, q[..., :rd], pose)
        kr = _apply_encoding(enc, enc.transform_k, k[..., :rd], pose)
        return (jnp.concatenate([qr, q[..., rd:]], -1),
                jnp.concatenate([kr, k[..., rd:]], -1))

    def _scale(self):
        if self.query_scale is not None:
            return self.query_scale ** -0.5
        return 1.0 / float(self.head_dim) ** 0.5

    def __call__(self, params, x, pose=None, *, kv=None, segment_ids=None,
                 cache=None, cache_index=None, impl=None):
        """Returns (out, new_cache). ``pose``: (B, S, pose_dim) or (B, S)
        integer positions for rope1d. ``kv``: cross-attention source (keys/
        values projected from it instead of x). With a cache, x is the
        current chunk (usually S=1 decode) written at ``cache_index``."""
        impl = impl or self.impl
        projs = self._projs()
        kv_src = x if kv is None else kv
        if pose is not None and pose.ndim == 2:
            pose = pose[..., None].astype(jnp.float32)
        q = _split_heads(projs["q"](params["q"], x), self.num_q_heads,
                         self.head_dim)
        k = _split_heads(projs["k"](params["k"], kv_src), self.num_kv_heads,
                         self.head_dim)
        v = _split_heads(projs["v"](params["v"], kv_src), self.num_kv_heads,
                         self.head_dim)
        q = logical_constraint(q, "act_batch", "act_heads", "act_seq", None)
        k = logical_constraint(k, "act_batch", "act_kv", "act_seq", None)
        q, k = self._encode(q, k, pose)
        if (self.encoding is not None and self.encoding.transforms_values
                and pose is not None):
            v = _apply_encoding(self.encoding, self.encoding.transform_v, v,
                                pose)
        scale = self._scale()

        new_cache = None
        if cache is not None:
            if "k_scale" in cache:
                # int8 cache: quantize the new rows on write (per-row
                # scales beside the values), dequantize for the XLA
                # fallback attention below. The cache's HBM footprint is
                # what shrinks; the rollout-path Pallas decode kernel
                # (repro.kernels.flash_decode) dequantizes per-tile in
                # VMEM instead of materializing the cache in f32.
                kq, ks = quantize_kv(k)
                vq, vs = quantize_kv(v)
                ck = _cache_update(cache["k"], kq, cache_index)
                cv = _cache_update(cache["v"], vq, cache_index)
                cks = _cache_update(cache["k_scale"][..., None],
                                    ks[..., None], cache_index)[..., 0]
                cvs = _cache_update(cache["v_scale"][..., None],
                                    vs[..., None], cache_index)[..., 0]
                new_cache = {"k": ck, "v": cv, "k_scale": cks,
                             "v_scale": cvs}
                ck = dequantize_kv(ck, cks, dtype=q.dtype)
                cv = dequantize_kv(cv, cvs, dtype=q.dtype)
            else:
                ck = _cache_update(cache["k"], k, cache_index)
                cv = _cache_update(cache["v"], v, cache_index)
                new_cache = {"k": ck, "v": cv}
            out = kops.attention(
                q, ck, cv, impl="chunked" if impl == "flash" else impl,
                causal=self.causal, window=self.window, softcap=self.softcap,
                scale=scale, q_offset=cache_index)
        else:
            out = kops.attention(
                q, k, v, impl=impl, causal=self.causal, window=self.window,
                softcap=self.softcap, scale=scale,
                q_segment_ids=segment_ids, k_segment_ids=segment_ids)
        if (self.encoding is not None and self.encoding.transforms_values
                and pose is not None):
            out = _apply_encoding(self.encoding, self.encoding.untransform_out,
                                  out, pose)
        out = logical_constraint(out, "act_batch", "act_heads", "act_seq", None)
        y = projs["o"](params["o"], _merge_heads(out))
        return logical_constraint(y, "act_batch", "act_seq", "act_embed"), new_cache

    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        """``dtype``: jnp dtype or "float32"/"bfloat16"/"int8". int8
        caches store per-(head, token) float32 scales beside K/V
        (quantize-on-write; see ``repro.kernels.flash_decode``)."""
        dtype = canonical_cache_dtype(dtype, default=jnp.bfloat16)
        hd = self.head_dim
        # cache stores encoded keys; for dim-preserving encodings hd is right
        if self.encoding is not None and self.encoding.transforms_values:
            raise NotImplementedError(
                "KV cache with value-transforming encodings")
        cache = {
            "k": jnp.zeros((batch, self.num_kv_heads, max_len, hd), dtype),
            "v": jnp.zeros((batch, self.num_kv_heads, max_len, hd), dtype),
        }
        if dtype == jnp.int8:
            cache["k_scale"] = jnp.zeros(
                (batch, self.num_kv_heads, max_len), jnp.float32)
            cache["v_scale"] = jnp.zeros(
                (batch, self.num_kv_heads, max_len), jnp.float32)
        return cache


#: key block of the absorbed latent decode: tokens one grid step reads (a
#: latent cache's length is a multiple of it, or shorter)
LATENT_DECODE_BLOCK = 512


def write_latent_rows(buf, layer, rows, slots, cursor, kv_length):
    """Write new latent rows into the stacked cache in place.

    buf (L, B, 1, c, Smax), tokens last; rows (B', n, c); ``slots`` (B',)
    the slot of each row of ``rows`` (None: row b is slot b); cursor (B',)
    where the n rows land; layer a static int. A row whose ``kv_length``
    does not pass its cursor (an inactive slot) writes back what was
    there. One ``dynamic_update_slice`` a row touches only its n rows of
    (layer, slot), so under jit with a donated cache the update is
    O(B' n), never O(Smax) (the pattern of ``nn.agent_sim``).
    """
    n, c = rows.shape[1], rows.shape[2]
    new = jnp.swapaxes(rows, 1, 2).astype(buf.dtype)          # (B', c, n)
    with jax.named_scope("lm.cache_write"):
        for bi in range(rows.shape[0]):
            si = bi if slots is None else slots[bi]
            at = (layer, si, 0, 0, cursor[bi])
            old = jax.lax.dynamic_slice(buf, at, (1, 1, 1, c, n))
            upd = jnp.where(kv_length[bi] > cursor[bi],
                            new[bi][None, None, None], old)
            buf = jax.lax.dynamic_update_slice(buf, upd, at)
    return buf


@dataclasses.dataclass(frozen=True)
class MLAttention:
    """Multi-head Latent Attention (deepseek-v2 family).

    ``scale_mult`` multiplies the softmax scale ``qk_dim ** -0.5`` (YaRN's
    ``mscale ** 2``); ``yarn`` is the rope's YaRN scaling (``Rope1D``).
    The rope channels of q and k_rope pair split-half (feature i with
    i + qk_rope_dim / 2); Hugging Face's DeepSeek-V2 de-interleaves them
    first, a fixed permutation of those weight columns.
    """

    d_model: int
    num_heads: int
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    q_lora_rank: Optional[int] = None
    rope_base: float = 10000.0
    causal: bool = True
    impl: str = "chunked"
    scale_mult: float = 1.0
    yarn: Optional[tuple] = None

    @property
    def qk_dim(self):
        return self.qk_nope_dim + self.qk_rope_dim

    @property
    def latent_dim(self):
        """Width of one cached row: the latent, then the rope key."""
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def scale(self):
        return self.scale_mult / float(self.qk_dim) ** 0.5

    def _rope(self):
        return Rope1D(head_dim=self.qk_rope_dim, base=self.rope_base,
                      yarn=self.yarn)
    def _projs(self):
        d, h = self.d_model, self.num_heads
        dn, dr, dv, r = (self.qk_nope_dim, self.qk_rope_dim, self.v_head_dim,
                         self.kv_lora_rank)
        p = {}
        if self.q_lora_rank:
            p["q_down"] = Dense((d,), (self.q_lora_rank,), ("embed",),
                                ("kv_lora",))
            p["q_up"] = Dense((self.q_lora_rank,), (h, dn + dr), ("kv_lora",),
                              ("heads", "head_dim"))
        else:
            p["q"] = Dense((d,), (h, dn + dr), ("embed",),
                           ("heads", "head_dim"))
        p["kv_down"] = Dense((d,), (r,), ("embed",), ("kv_lora",))
        p["k_rope"] = Dense((d,), (dr,), ("embed",), ("head_dim",))
        p["k_up"] = Dense((r,), (h, dn), ("kv_lora",), ("heads", "head_dim"))
        p["v_up"] = Dense((r,), (h, dv), ("kv_lora",), ("heads", "head_dim"))
        p["o"] = Dense((h, dv), (d,), ("heads", "head_dim"), ("embed",))
        return p

    def specs(self):
        s = {k: l.specs() for k, l in self._projs().items()}
        from repro.nn.layers import RMSNorm
        s["kv_norm"] = RMSNorm(self.kv_lora_rank).specs()
        return s

    def _queries(self, params, projs, x):
        b, s, _ = x.shape
        if self.q_lora_rank:
            ql = projs["q_down"](params["q_down"], x)
            q = projs["q_up"](params["q_up"], ql)
        else:
            q = projs["q"](params["q"], x)
        return q.transpose(0, 2, 1, 3)  # (B, H, S, dn+dr)

    def _latent(self, params, projs, x):
        from repro.nn.layers import RMSNorm
        ckv = projs["kv_down"](params["kv_down"], x)          # (B, S, r)
        ckv = RMSNorm(self.kv_lora_rank)(params["kv_norm"], ckv)
        kr = projs["k_rope"](params["k_rope"], x)             # (B, S, dr)
        return ckv, kr

    def _project(self, params, projs, x, pose):
        """(q_nope (B,H,S,dn), roped q (B,H,S,dr), latent rows (B,S,r+dr)
        with the roped key beside the normed latent)."""
        rope = self._rope()
        q = self._queries(params, projs, x)
        qn, qr = q[..., :self.qk_nope_dim], q[..., self.qk_nope_dim:]
        qr = rope.transform_q(qr, pose[:, None, :, :])
        ckv, kr = self._latent(params, projs, x)
        kr = rope.transform_k(kr[:, None], pose[:, None, :, :])[:, 0]
        return qn, qr, jnp.concatenate([ckv, kr.astype(ckv.dtype)], -1)

    def _expand(self, params, lat):
        """Latent rows (B, S, r+dr) -> per-head keys (B, H, S, dn+dr) and
        values (B, H, S, dv)."""
        r = self.kv_lora_rank
        ckv, kr = lat[..., :r], lat[..., r:]
        wk = params["k_up"]["kernel"].astype(lat.dtype)     # (r, H, dn)
        wv = params["v_up"]["kernel"].astype(lat.dtype)     # (r, H, dv)
        kn = jnp.einsum("bsr,rhd->bhsd", ckv, wk)
        v = jnp.einsum("bsr,rhd->bhsd", ckv, wv)
        k = jnp.concatenate(
            [kn, jnp.broadcast_to(kr[:, None], kn.shape[:3]
                                  + (self.qk_rope_dim,))], -1)
        return k, v

    def __call__(self, params, x, pose=None, *, segment_ids=None, cache=None,
                 cache_index=None, impl=None):
        """Full-sequence attention (training, prefill without a cache).
        Cached decoding goes through :meth:`cached` on the model's
        stacked latent cache."""
        assert cache is None, "MLA caches are the model's stacked buffer"
        impl = impl or self.impl
        projs = self._projs()
        b, s, _ = x.shape
        if pose is None:
            pose = jnp.arange(s, dtype=jnp.float32)[None, :].repeat(b, 0)
        if pose.ndim == 2:
            pose = pose[..., None].astype(jnp.float32)
        qn, qr, lat = self._project(params, projs, x, pose)
        k, v = self._expand(params, lat)
        qf = jnp.concatenate([qn, qr], -1)
        qf = logical_constraint(qf, "act_batch", "act_heads", "act_seq", None)
        out = kops.attention(qf, k, v, impl=impl, causal=self.causal,
                             scale=self.scale, q_segment_ids=segment_ids,
                             k_segment_ids=segment_ids)
        y = projs["o"](params["o"], _merge_heads(out))
        return logical_constraint(y, "act_batch", "act_seq", "act_embed"), None

    def cached(self, params, x, pose, buf, layer: int, *, cursor, kv_length,
               slots=None, impl=None):
        """Attend ``n`` new tokens a row through the stacked latent cache.

        x (B', n, d_model); pose (B', n, 1) positions; buf (L, B, 1, r+dr,
        Smax); layer static; cursor (B',) where the new rows land;
        kv_length (B',) live rows after them (at most cursor + n; rows
        past it, such as a chunk's padding, stay unreachable; 0 leaves an
        inactive slot untouched); slots (B',) the rows' slots (None: row b
        is slot b). Returns (out (B', n, d_model), buf).

        n == 1 is the absorbed decode: q_lat = q_nope . W_uk, scored with
        the rope query against the 576-wide latent rows and averaging
        their first 512 features, by ``kops.decode_attention`` on the
        cache in place (O(live prefix), no per-layer copy). n > 1 (a
        prefill chunk) is expanded: the slot's rows are projected to
        per-head keys and values and attended causally by position.
        """
        projs = self._projs()
        n = x.shape[1]
        qn, qr, lat = self._project(params, projs, x, pose)
        buf = write_latent_rows(buf, layer, lat, slots, cursor, kv_length)
        if n == 1:
            assert slots is None, "decode rows are the cache's slots"
            with jax.named_scope("lm.mla_decode"):
                wk = params["k_up"]["kernel"].astype(x.dtype)
                q_lat = jnp.einsum("bhsd,rhd->bhsr", qn, wk)
                q_full = jnp.concatenate([q_lat, qr.astype(q_lat.dtype)], -1)
                o_lat = kops.decode_attention(
                    q_full, buf, None, kv_length=kv_length, layer=layer,
                    v_dim=self.kv_lora_rank, scale=self.scale,
                    impl=impl or "auto", block_k=LATENT_DECODE_BLOCK,
                    num_splits=1)
                wv = params["v_up"]["kernel"].astype(x.dtype)
                out = jnp.einsum("bhsr,rhd->bhsd", o_lat.astype(x.dtype), wv)
        else:
            smax = buf.shape[-1]
            rows = []
            for bi in range(x.shape[0]):
                si = bi if slots is None else slots[bi]
                rows.append(jax.lax.dynamic_slice(
                    buf, (layer, si, 0, 0, 0),
                    (1, 1, 1, buf.shape[3], smax))[0, 0, 0])
            cached_lat = jnp.swapaxes(jnp.stack(rows), 1, 2).astype(x.dtype)
            k, v = self._expand(params, cached_lat)
            qf = jnp.concatenate([qn, qr.astype(qn.dtype)], -1)
            q_times = cursor[:, None] + jnp.arange(n, dtype=jnp.int32)
            k_times = jnp.broadcast_to(jnp.arange(smax, dtype=jnp.int32),
                                       (x.shape[0], smax))
            out = kops.attention(qf, k, v, impl=impl or self.impl,
                                 causal=True, scale=self.scale,
                                 q_times=q_times, k_times=k_times,
                                 kv_length=kv_length)
        y = projs["o"](params["o"], _merge_heads(out.astype(x.dtype)))
        return y, buf
