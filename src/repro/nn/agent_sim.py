"""Agent-simulation model (paper Sec. IV-B): next-action prediction over
tokenized traffic scenes with SE(2)-relative attention.

Scene tokenization (mirrors the paper's setup): each map element and each
(agent, timestep) pair is one token with an associated SE(2) pose. Tokens
are ordered [map..., agents@t0, agents@t1, ...]; attention is block-causal
over *times* (map tokens have time 0, agents at step t have time t+1, and
tokens of the same step attend to each other bidirectionally). The model
predicts a categorical distribution over a discrete (acceleration x yaw
rate) action grid for every agent token.

The relative attention mechanism is pluggable — the four rows of the paper's
Table I:

  * ``absolute``     — learned Fourier-feature pose embedding added to token
    features, standard SDPA.
  * ``rope2d``       — translation-invariant only (Sec. II-D).
  * ``se2_repr``     — homogeneous-matrix SE(2) representation (Sec. II-E).
  * ``se2_fourier``  — the paper's contribution (Sec. III).

Positions are downscaled by ``pos_scale`` so magnitudes stay within the
Fourier basis budget (paper: <= 4 with F = 18).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.encodings import GroupEncoding, make_encoding
from repro.distributed.sharding import per_batch_head_shard
from repro.kernels import ops as kops
from repro.kernels.flash_decode import canonical_cache_dtype, quantize_kv
from repro.nn.attention import _merge_heads, _split_heads
from repro.nn.layers import Dense, RMSNorm
from repro.nn.mlp import GatedMLP
from repro.nn.module import stack_specs


@dataclasses.dataclass(frozen=True)
class AgentSimConfig:
    d_model: int = 256
    num_layers: int = 4
    num_heads: int = 8
    head_dim: int = 24            # divisible by 6/4/3/2: works for every enc
    d_ff: int = 1024
    num_actions: int = 63         # 7 accel bins x 9 yaw-rate bins
    agent_feat_dim: int = 8
    map_feat_dim: int = 8
    encoding: str = "se2_fourier"
    fourier_terms: int = 12
    min_scale: float = 0.25
    max_scale: float = 1.0
    pos_scale: float = 0.05       # world meters -> encoder units (<= 4)
    #: full-sequence attention impl (``kops.attention`` names). "auto" runs
    #: the Pallas flash kernels on TPU and the chunked XLA path elsewhere;
    #: "ref" is the O(S^2) oracle that tests pin.
    attn_impl: str = "auto"
    #: attention impl for the cached decode path (``kops.decode_attention``
    #: names: "auto" / "flash_decode" / "xla" / "ref" / "chunked").
    #: None falls back to ``attn_impl``: "auto" resolves to the split-K
    #: ragged kernel on TPU, while a pinned "ref" scans the whole
    #: preallocated cache and is kept as the oracle.
    decode_impl: Optional[str] = None
    dtype: str = "float32"

    @property
    def compute_dtype(self):
        return jnp.bfloat16 if self.dtype == "bfloat16" else jnp.float32


def _scatter_rows(buf, new, cursor):
    """Write ``new`` rows into ``buf`` at per-row cursors along the length
    axis: buf (B, S, ...) or (B, H, S, ...), new the matching (B, n, ...) /
    (B, H, n, ...), cursor (B,) int32. The caller guarantees
    cursor + n <= S (dynamic_update_slice clamps, it does not wrap)."""
    axis = 1 if buf.ndim == 2 else buf.ndim - 2    # length axis of buf
    with jax.named_scope("agent_sim.cache_write"):
        return jax.vmap(
            lambda b_, u, i: jax.lax.dynamic_update_slice_in_dim(
                b_, u, i, axis=axis - 1))(buf, new, cursor)


def _scatter_layer_rows(buf, layer, new, cursor):
    """Write one layer's new rows into the *stacked* cache in place.

    buf (L, B, H, c, S) or (L, B, H, S), tokens last (see
    ``AgentSimModel.init_cache``); new (B, H, n, c) / (B, H, n), as the
    projections produce them — k/v rows are laid out (B, H, c, n) here
    and land at lane offset ``cursor``; layer a static int; cursor (B,).
    A chain of per-slot ``dynamic_update_slice`` ops, each touching only
    the n written rows of (layer, slot) — under jit with a donated cache
    the whole update is O(B * n), not O(max_len). The tempting
    alternatives both silently copy the entire preallocated buffer every
    tick and erase the ragged-decode win: threading the cache through
    ``lax.scan`` xs/ys (slice-in/stack-out copies), and ``vmap`` over
    the slot axis (in_axes=1 inserts full-buffer transposes). The
    engine-level regression guard is ``benchmarks/rollout_bench.py``'s
    flatness assertion; ``tests/test_tpu_compile.py`` checks that the
    compiled v5e tick holds no copy of the cache.
    """
    if new.ndim == 4:
        new = jnp.swapaxes(new, -1, -2)
    b = buf.shape[1]
    with jax.named_scope("agent_sim.cache_write"):
        for bi in range(b):
            starts = (layer, bi) + (0,) * (buf.ndim - 3) + (cursor[bi],)
            buf = jax.lax.dynamic_update_slice(
                buf, new[bi][None, None], starts)
    return buf


def install_slot_rows(cache, sub, si, n_rows: int):
    """Install the first ``n_rows`` rows of a freshly written 1-slot cache
    ``sub`` into slot ``si`` of a multi-slot cache (continuous-batching
    admission: a retiring scene's slot is reused by the next scene).

    ``si`` may be a traced scalar, so one compilation serves every slot.
    This deliberately rewrites ONLY rows ``[0, n_rows)`` plus the slot's
    cursor: rows at and beyond the (reset) cursor keep whatever the
    evicted scene left behind — including segment ids claiming validity.
    They are unreachable anyway, because every decode masks keys at
    positions >= ``kv_length = cursor + n`` and the cursor only ever
    advances over freshly written rows (the isolation contract pinned by
    ``tests/test_sim_server.py``). Scrubbing them would cost an
    O(max_len) write per admission just to hide from that contract.
    """
    out = dict(cache)
    with jax.named_scope("agent_sim.cache_write"):
        for key in AgentSimModel._LAYER_CACHE_KEYS:
            if key in cache:
                # every stacked array keeps its tokens on the last axis
                rows = jax.lax.slice_in_dim(sub[key], 0, n_rows, axis=-1)
                starts = (0, si) + (0,) * (rows.ndim - 2)
                out[key] = jax.lax.dynamic_update_slice(
                    cache[key], rows, starts)
        for key in ("times", "seg"):
            out[key] = jax.lax.dynamic_update_slice(
                cache[key], sub[key][:, :n_rows], (si, 0))
        out["cursor"] = jax.lax.dynamic_update_slice(
            cache["cursor"], sub["cursor"], (si,))
    return out


def build_sim_encoding(cfg: AgentSimConfig) -> Optional[GroupEncoding]:
    if cfg.encoding == "absolute":
        return None
    kwargs: Dict[str, Any] = {}
    if cfg.encoding == "se2_fourier":
        kwargs = dict(num_terms=cfg.fourier_terms, min_scale=cfg.min_scale,
                      max_scale=cfg.max_scale)
    elif cfg.encoding == "se2_repr":
        kwargs = dict(min_scale=cfg.min_scale, max_scale=cfg.max_scale)
    elif cfg.encoding == "rope2d":
        kwargs = dict(max_freq=cfg.max_scale, base=100.0)
    return make_encoding(cfg.encoding, cfg.head_dim, **kwargs)


class SimAttention:
    """Relative attention over scene tokens (Alg. 2 around the SDPA kernel).

    Attention is **block-causal over times** (``causal=True`` with explicit
    per-token times): a token at simulation step t attends tokens at steps
    <= t, and tokens sharing a step attend each other bidirectionally. This
    is not just the autoregressive training mask — it is what makes the
    incremental decode cache sound: a token's attention output can never
    change when later tokens arrive, so per-layer K/V rows written once
    stay valid for the rest of the rollout.

    The cached rows are the *encoding-transformed* keys/values
    ``k~ = phi_k(p_m) k`` / ``v~ = phi_k(p_m) v``: the paper's per-token
    factorization means they depend only on the token's own pose, never on
    the (growing) rest of the scene — see ``docs/rollout.md``.
    """

    def __init__(self, cfg: AgentSimConfig):
        self.cfg = cfg
        self.enc = build_sim_encoding(cfg)
        d, h, hd = cfg.d_model, cfg.num_heads, cfg.head_dim
        self.projs = {
            "q": Dense((d,), (h, hd), ("embed",), ("heads", "head_dim")),
            "k": Dense((d,), (h, hd), ("embed",), ("heads", "head_dim")),
            "v": Dense((d,), (h, hd), ("embed",), ("heads", "head_dim")),
            "o": Dense((h, hd), (d,), ("heads", "head_dim"), ("embed",)),
        }

    def specs(self):
        return {k: p.specs() for k, p in self.projs.items()}

    @property
    def cache_dims(self) -> Tuple[int, int]:
        """(key_dim, value_dim) of one cached row (post-transform)."""
        if self.enc is None:
            return self.cfg.head_dim, self.cfg.head_dim
        return self.enc.expanded_dim, self.enc.expanded_v_dim

    def _qkv(self, params, x, pose):
        """Project new tokens and apply the per-token encoding transforms.

        Returns (q~, k~, v~), each (B, H, n, ·) — exactly the rows a cache
        stores. Everything here depends only on each token's own features
        and pose: the factorization that legitimizes caching.
        """
        cfg = self.cfg
        h, hd = cfg.num_heads, cfg.head_dim
        q = _split_heads(self.projs["q"](params["q"], x), h, hd)
        k = _split_heads(self.projs["k"](params["k"], x), h, hd)
        v = _split_heads(self.projs["v"](params["v"], x), h, hd)
        if self.enc is not None:
            p4 = pose[:, None]                       # (B, 1, n, 3)
            if self.enc.pose_dim == 2:
                p4 = p4[..., :2]
            with jax.named_scope("agent_sim.se2_transform"):
                q = self.enc.transform_q(q, p4)
                k = self.enc.transform_k(k, p4)
                if self.enc.transforms_values:
                    v = self.enc.transform_v(v, p4)
        return q, k, v

    def _finish(self, params, out, pose):
        if self.enc is not None and self.enc.transforms_values:
            with jax.named_scope("agent_sim.se2_transform"):
                out = self.enc.untransform_out(out, pose[:, None])
        return self.projs["o"](params["o"], _merge_heads(out))

    def __call__(self, params, x, pose, times, segment_ids):
        cfg = self.cfg
        q, k, v = self._qkv(params, x, pose)
        scale = 1.0 / float(cfg.head_dim) ** 0.5

        def attend(q, k, v, times, segment_ids):
            return kops.attention(q, k, v, impl=cfg.attn_impl, scale=scale,
                                  causal=True,
                                  q_times=times, k_times=times,
                                  q_segment_ids=segment_ids,
                                  k_segment_ids=segment_ids)

        out = per_batch_head_shard(attend, q, k, v, times, segment_ids)
        return self._finish(params, out, pose)

    def decode_step(self, params, x, pose, times, segment_ids,
                    kv_cache, layer, cache_times, cache_seg, cursor,
                    impl=None):
        """Incremental decode: attend ``n`` new tokens over the cache.

        x (B, n, d_model); pose (B, n, 3) *encoder-scaled*; times (B, n);
        segment_ids (B, n); ``kv_cache`` is the model's layer-STACKED,
        feature-major cache: ``{"k": (L, B, H, c, S_max), "v": (L, B,
        H, cv, S_max)}`` plus, for int8 caches, per-(head, token)
        ``"k_scale"``/``"v_scale"`` (L, B, H, S_max) float32 living
        beside the rows they scale; ``layer`` is this layer's static
        index. The stacked buffers are written with O(n) in-place
        scatters and read by the ragged decode paths through in-place
        (layer, block) slices — a per-layer copy never exists.
        cache_times / cache_seg (B, S_max) are **already updated** with
        the new tokens' rows (they are layer-independent, so the model
        writes them once); cursor (B,) — rows written *before* this
        call. Returns
        (out (B, n, d_model), updated kv_cache).

        New rows are written at [cursor, cursor + n) — quantized on
        write for int8 caches (a row's absmax never changes after the
        write, so per-row scales are exact). The query attends the cache
        with the same block-causal times + segment mask as the full
        forward, plus cursor masking (``kv_length = cursor + n``) so
        never-written slots are unreachable even where ``cache_seg`` has
        been scribbled on by a retired scene. ``impl`` (or
        ``cfg.decode_impl``, or ``cfg.attn_impl``) picks the
        ``kops.decode_attention`` backend: the split-K ragged decode
        kernel / its XLA twin pay O(cursor) per call; the generic-kernel
        names scan all of S_max and remain the parity oracle.
        """
        cfg = self.cfg
        n = x.shape[1]
        q, k_new, v_new = self._qkv(params, x, pose)
        kv_cache = dict(kv_cache)
        if "k_scale" in kv_cache:
            k_q, k_s = quantize_kv(k_new)
            v_q, v_s = quantize_kv(v_new)
            kv_cache["k"] = _scatter_layer_rows(kv_cache["k"], layer, k_q,
                                                cursor)
            kv_cache["v"] = _scatter_layer_rows(kv_cache["v"], layer, v_q,
                                                cursor)
            kv_cache["k_scale"] = _scatter_layer_rows(
                kv_cache["k_scale"], layer, k_s, cursor)
            kv_cache["v_scale"] = _scatter_layer_rows(
                kv_cache["v_scale"], layer, v_s, cursor)
        else:
            kv_cache["k"] = _scatter_layer_rows(
                kv_cache["k"], layer,
                k_new.astype(kv_cache["k"].dtype), cursor)
            kv_cache["v"] = _scatter_layer_rows(
                kv_cache["v"], layer,
                v_new.astype(kv_cache["v"].dtype), cursor)
        scale = 1.0 / float(cfg.head_dim) ** 0.5
        with jax.named_scope("agent_sim.decode_attention"):
            out = kops.decode_attention(
                q, kv_cache["k"], kv_cache["v"],
                kv_length=cursor + n, layer=layer,
                impl=impl or cfg.decode_impl or cfg.attn_impl,
                scale=scale, q_times=times, k_times=cache_times,
                q_segment_ids=segment_ids, k_segment_ids=cache_seg,
                k_scale=kv_cache.get("k_scale"),
                v_scale=kv_cache.get("v_scale"))
        return self._finish(params, out, pose), kv_cache


class AgentSimModel:
    """Scene transformer -> per-(agent, t) action logits."""

    def __init__(self, cfg: AgentSimConfig):
        self.cfg = cfg
        d = cfg.d_model
        self.map_enc = Dense((cfg.map_feat_dim,), (d,), (None,), ("embed",))
        self.agent_enc = Dense((cfg.agent_feat_dim,), (d,), (None,), ("embed",))
        self.attn = SimAttention(cfg)
        self.mlp = GatedMLP(d, cfg.d_ff)
        self.norm1 = RMSNorm(d)
        self.norm2 = RMSNorm(d)
        self.final_norm = RMSNorm(d)
        self.head = Dense((d,), (cfg.num_actions,), ("embed",), (None,))
        # learned Fourier pose embedding for the "absolute" baseline
        self.pose_freqs = 16

    def specs(self):
        cfg = self.cfg
        block = {"attn": self.attn.specs(), "mlp": self.mlp.specs(),
                 "norm1": self.norm1.specs(), "norm2": self.norm2.specs()}
        s = {
            "map_enc": self.map_enc.specs(),
            "agent_enc": self.agent_enc.specs(),
            "blocks": stack_specs(block, cfg.num_layers),
            "final_norm": self.final_norm.specs(),
            "head": self.head.specs(),
        }
        if cfg.encoding == "absolute":
            s["pose_proj"] = Dense((3 * self.pose_freqs,), (cfg.d_model,),
                                   ("basis",), ("embed",)).specs()
        return s

    def _pose_embedding(self, params, pose):
        """Fourier features of (x, y, theta) -> d_model (absolute baseline)."""
        freqs = jnp.asarray(2.0 ** np.arange(self.pose_freqs // 2),
                            jnp.float32)
        scaled = jnp.concatenate(
            [pose[..., 0:1] * self.cfg.pos_scale,
             pose[..., 1:2] * self.cfg.pos_scale, pose[..., 2:3]], -1)
        ang = scaled[..., None] * freqs                  # (..., 3, PF/2)
        feats = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)
        feats = feats.reshape(*pose.shape[:-1], 3 * self.pose_freqs)
        return Dense((3 * self.pose_freqs,), (self.cfg.d_model,), ("basis",),
                     ("embed",))(params["pose_proj"], feats)

    def tokenize(self, batch):
        """Assemble scene tokens.

        batch: dict with
          map_feats (B, M, Fm), map_pose (B, M, 3), map_valid (B, M) bool
          agent_feats (B, T, A, Fa), agent_pose (B, T, A, 3),
          agent_valid (B, T, A) bool
        Returns (feats, pose, times, segment_ids) with S = M + T*A.
        """
        b, m, _ = batch["map_feats"].shape
        _, t, a, _ = batch["agent_feats"].shape
        pose = jnp.concatenate(
            [batch["map_pose"],
             batch["agent_pose"].reshape(b, t * a, 3)], axis=1)
        times = jnp.concatenate(
            [jnp.zeros((b, m), jnp.int32),
             jnp.broadcast_to(1 + jnp.arange(t, dtype=jnp.int32)[None, :, None],
                              (b, t, a)).reshape(b, t * a)], axis=1)
        valid = jnp.concatenate(
            [batch["map_valid"],
             batch["agent_valid"].reshape(b, t * a)], axis=1)
        segment_ids = jnp.where(valid, 0, -1).astype(jnp.int32)
        return pose, times, segment_ids

    def __call__(self, params, batch):
        """Returns logits (B, T, A, num_actions) and aux (zeros)."""
        cfg = self.cfg
        dt = cfg.compute_dtype
        b, m, _ = batch["map_feats"].shape
        _, t, a, _ = batch["agent_feats"].shape
        pose, times, segment_ids = self.tokenize(batch)
        mtok = self.map_enc(params["map_enc"], batch["map_feats"].astype(dt))
        atok = self.agent_enc(params["agent_enc"],
                              batch["agent_feats"].astype(dt))
        x = jnp.concatenate([mtok, atok.reshape(b, t * a, -1)], axis=1)
        if cfg.encoding == "absolute":
            x = x + self._pose_embedding(params, pose).astype(dt)
        enc_pose = pose.astype(jnp.float32) * jnp.asarray(
            [cfg.pos_scale, cfg.pos_scale, 1.0], jnp.float32)

        def body(x, lp):
            h = self.norm1(lp["norm1"], x)
            x = x + self.attn(lp["attn"], h, enc_pose, times, segment_ids)
            h = self.norm2(lp["norm2"], x)
            x = x + self.mlp(lp["mlp"], h)
            return x, 0

        x, _ = jax.lax.scan(body, x, params["blocks"])
        x = self.final_norm(params["final_norm"], x)
        logits = self.head(params["head"], x[:, m:])
        return logits.reshape(b, t, a, cfg.num_actions), jnp.zeros(
            (), jnp.float32)

    # -- incremental decode ---------------------------------------------------
    #
    # The per-token factorization (encodings.GroupEncoding) means a cached
    # k~/v~ row depends only on that token's own features and pose, and the
    # block-causal times mask means a token's output never changes as the
    # scene grows — so `prefill` + repeated `step` reproduces `__call__`'s
    # logits exactly (tests/test_decode.py) at O(T) instead of O(T^2) work
    # per rollout step. See docs/rollout.md for the soundness argument.

    #: layer-stacked cache entries scanned alongside the block params
    _LAYER_CACHE_KEYS = ("k", "v", "k_scale", "v_scale")

    def init_cache(self, batch_size: int, max_len: int, dtype=None):
        """Preallocate the decode cache for ``batch_size`` scene slots.

        Layout: per-layer transformed keys/values stacked on a leading layer
        axis (the block parameters are scanned, so the cache scans too),
        plus layer-independent times / segment ids / per-slot cursors.
        Segment ids start at -1, so unwritten rows are always masked.

        k and v are stored **feature-major**, ``(L, B, H, c, S)``: the
        token axis is last. A TPU tiles an array's last two axes as
        (sublane, lane) = (8, 128) for float32, and S is 128-aligned by
        every caller (``SimServer``, ``RolloutEngine``), so the tokens
        fill the lanes and any row width c that is a multiple of 8 (200
        for se2_fourier, 24 for absolute) tiles with no padding. The
        row-major layout is then the compact one that the runtime picks
        for the donated buffer, and the decode kernel and the in-place
        writes use it as is. Rows last, ``(L, B, H, S, c)``, put c in
        the lanes: unless c is a multiple of 128 that layout is padded,
        the runtime stores the buffer S-minor instead, and every tick
        copies the whole cache between the two (and XLA's
        rematerializer compresses and restores it around each layer).
        When c is a multiple of 128 neither layout pads, so one layout
        serves every encoding. The scales ``(L, B, H, S)`` are S-minor
        already. (The LM stack's unstacked ``Attention`` cache keeps
        ``(B, H, S, D)``.)

        ``dtype`` selects the cache storage dtype: a jnp dtype or one of
        the strings "float32" / "bfloat16" / "int8" (the
        ``RolloutEngine(cache_dtype=...)`` spelling). int8 caches carry
        per-(head, token) float32 ``k_scale``/``v_scale`` arrays beside
        the rows (quantized on write, dequantized inside the decode
        kernel), shrinking the decode working set ~4x at the cost of one
        f32 scalar per row.
        """
        cfg = self.cfg
        dtype = canonical_cache_dtype(dtype, default=cfg.compute_dtype)
        ck, cv = self.attn.cache_dims
        l, b, h, s = cfg.num_layers, batch_size, cfg.num_heads, max_len
        cache = {
            "k": jnp.zeros((l, b, h, ck, s), dtype),
            "v": jnp.zeros((l, b, h, cv, s), dtype),
            "times": jnp.zeros((b, s), jnp.int32),
            "seg": jnp.full((b, s), -1, jnp.int32),
            "cursor": jnp.zeros((b,), jnp.int32),
        }
        if dtype == jnp.int8:
            # scale 0 dequantizes unwritten rows to exact zeros (they are
            # cursor-masked anyway)
            cache["k_scale"] = jnp.zeros((l, b, h, s), jnp.float32)
            cache["v_scale"] = jnp.zeros((l, b, h, s), jnp.float32)
        return cache

    def _extend(self, params, cache, x, pose, times, segment_ids, impl=None):
        """Feed ``n`` new tokens through every layer against the cache.

        x (B, n, d_model) embedded tokens; pose (B, n, 3) raw world poses;
        times/segment_ids (B, n). Returns (logits (B, n, A), new cache).
        Used for both prefill (n = whole history) and rollout steps (n =
        num_agents): the mask semantics are identical, so prefill is just a
        big first step. ``impl`` overrides the decode attention backend
        (see ``SimAttention.decode_step``).
        """
        cfg = self.cfg
        n = x.shape[1]
        cursor = cache["cursor"]
        enc_pose = pose.astype(jnp.float32) * jnp.asarray(
            [cfg.pos_scale, cfg.pos_scale, 1.0], jnp.float32)
        cache_times = _scatter_rows(cache["times"], times, cursor)
        cache_seg = _scatter_rows(cache["seg"], segment_ids, cursor)
        kv_cache = {k: cache[k] for k in self._LAYER_CACHE_KEYS
                    if k in cache}

        # Python loop, NOT lax.scan: the layer index must be static so
        # the decode kernels can address the stacked cache in place, and
        # scanning the cache through xs/ys would copy the whole
        # preallocated buffer every tick (see _scatter_layer_rows).
        # num_layers is small; the unrolled loop costs only compile time.
        for li in range(cfg.num_layers):
            lp = jax.tree.map(lambda a: a[li], params["blocks"])
            h = self.norm1(lp["norm1"], x)
            attn_out, kv_cache = self.attn.decode_step(
                lp["attn"], h, enc_pose, times, segment_ids,
                kv_cache, li, cache_times, cache_seg, cursor, impl=impl)
            x = x + attn_out
            with jax.named_scope("agent_sim.mlp"):
                h = self.norm2(lp["norm2"], x)
                x = x + self.mlp(lp["mlp"], h)

        with jax.named_scope("agent_sim.head"):
            x = self.final_norm(params["final_norm"], x)
            logits = self.head(params["head"], x)
        new_cache = {**kv_cache, "times": cache_times,
                     "seg": cache_seg, "cursor": cursor + n}
        return logits, new_cache

    def prefill(self, params, cache, batch, impl=None):
        """Write a scene's map + agent history into the cache.

        ``batch`` has the ``__call__`` layout with T = history length.
        Returns (logits (B, T, A, num_actions) for the history's agent
        tokens, updated cache). Requires max_len >= cursor + M + T*A.
        """
        cfg = self.cfg
        dt = cfg.compute_dtype
        b, m, _ = batch["map_feats"].shape
        _, t, a, _ = batch["agent_feats"].shape
        pose, times, segment_ids = self.tokenize(batch)
        mtok = self.map_enc(params["map_enc"], batch["map_feats"].astype(dt))
        atok = self.agent_enc(params["agent_enc"],
                              batch["agent_feats"].astype(dt))
        x = jnp.concatenate([mtok, atok.reshape(b, t * a, -1)], axis=1)
        if cfg.encoding == "absolute":
            x = x + self._pose_embedding(params, pose).astype(dt)
        logits, cache = self._extend(params, cache, x, pose, times,
                                     segment_ids, impl=impl)
        return logits[:, m:].reshape(b, t, a, cfg.num_actions), cache

    def admit_map(self, params, cache, map_feats, map_pose, map_valid,
                  impl=None):
        """Write ONLY a scene's map tokens into the cache.

        The continuous-batching admission primitive: map tokens are the
        one token block whose width (M) differs from the per-tick A agent
        tokens, so a sim server admits a scene by extending its slot with
        the map here and then streaming history steps through the shared
        tick (``step`` with teacher-forced inputs) — prefill becomes
        incremental, exactly like the LM server's token-by-token prompt
        prefill. map_feats (B, M, Fm); map_pose (B, M, 3); map_valid
        (B, M) bool. Returns (map-token logits — meaningless, discarded
        by callers — and the updated cache)."""
        cfg = self.cfg
        dt = cfg.compute_dtype
        b, m, _ = map_feats.shape
        x = self.map_enc(params["map_enc"], map_feats.astype(dt))
        if cfg.encoding == "absolute":
            x = x + self._pose_embedding(params, map_pose).astype(dt)
        times = jnp.zeros((b, m), jnp.int32)
        seg = jnp.where(map_valid, 0, -1).astype(jnp.int32)
        return self._extend(params, cache, x, map_pose, times, seg,
                            impl=impl)

    def step(self, params, cache, agent_feats, agent_pose, agent_valid,
             step_time, impl=None):
        """Advance every scene slot by one simulation step.

        agent_feats (B, A, Fa); agent_pose (B, A, 3); agent_valid (B, A)
        bool; step_time (B,) int32 — the simulation step index t of these
        tokens (their attention time is t + 1, matching ``tokenize``).
        Returns (action logits (B, A, num_actions), updated cache).
        """
        cfg = self.cfg
        dt = cfg.compute_dtype
        b, a, _ = agent_feats.shape
        x = self.agent_enc(params["agent_enc"], agent_feats.astype(dt))
        if cfg.encoding == "absolute":
            x = x + self._pose_embedding(params, agent_pose).astype(dt)
        times = jnp.broadcast_to((step_time + 1)[:, None], (b, a))
        times = times.astype(jnp.int32)
        segment_ids = jnp.where(agent_valid, 0, -1).astype(jnp.int32)
        return self._extend(params, cache, x, agent_pose, times, segment_ids,
                            impl=impl)


def action_nll(logits, actions, valid):
    """Mean NLL of ground-truth actions over valid agent steps."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, actions[..., None], axis=-1)[..., 0]
    w = valid.astype(jnp.float32)
    return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)
