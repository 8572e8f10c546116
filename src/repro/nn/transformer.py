"""Config-driven transformer models: decoder LMs (all 10 families) + enc-dec.

The model is assembled from :class:`repro.configs.base.ModelConfig` into a
sequence of *layer groups* ``(block_template, count)``; homogeneous groups
are scanned (``lax.scan`` over stacked parameters, with optional remat), so
the lowered HLO is O(#distinct layer types), not O(#layers) — essential to
keep 61-layer × 512-device dry-run compiles fast.

Group patterns cover the architectures' structure:
  * plain stacks (stablelm, phi4, granite, internvl, whisper, rwkv6)
  * leading dense layers before MoE (deepseek-v2, kimi-k2)
  * alternating local/global attention (gemma2) — scanned in pairs
  * mostly-local with a few global layers (hymba)
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.encodings import Rope1D, yarn_mscale
from repro.distributed.sharding import logical_constraint
from repro.nn.attention import Attention, MLAttention
from repro.nn.blocks import Block
from repro.nn.layers import (Dense, Embedding, LayerNorm, RMSNorm,
                             sinusoidal_positions)
from repro.nn.mlp import MLP, GatedMLP, RWKVChannelMix
from repro.nn.module import ParamSpec, stack_specs
from repro.nn.moe import MoE
from repro.nn.ssm import MambaMixer, RWKV6TimeMix


def _tree_stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


class TransformerLM:
    """Decoder-only LM (optionally with a stubbed modality prefix)."""

    def __init__(self, cfg: ModelConfig, impl: Optional[str] = None,
                 unroll: bool = False):
        self.cfg = cfg
        self.impl = impl
        # unroll=True expands the layer scans in the lowered HLO. Used by the
        # dry-run so cost_analysis / collective parsing see every layer
        # (XLA counts while-loop bodies once); rolled scans keep compiles
        # fast everywhere else.
        self.unroll = unroll
        self.embedding = Embedding(cfg.padded_vocab, cfg.d_model,
                                   scale_by_sqrt_dim=cfg.scale_embeddings)
        self.groups = self._build_groups()
        self.final_norm = self._norm()
        if not cfg.tie_embeddings:
            self.lm_head = Dense((cfg.d_model,), (cfg.padded_vocab,),
                                 ("embed",), ("vocab",))

    # ------------------------------------------------------------------
    def _norm(self):
        cfg = self.cfg
        if cfg.norm == "layer":
            return LayerNorm(cfg.d_model)
        if cfg.norm == "rms_offset":
            return RMSNorm(cfg.d_model, weight_offset=1.0)
        return RMSNorm(cfg.d_model)

    def _encoding(self):
        cfg = self.cfg
        if cfg.pos_enc == "rope1d":
            return Rope1D(head_dim=self._rot_dim(), base=cfg.rope_base)
        return None

    def _rot_dim(self):
        cfg = self.cfg
        rd = int(cfg.resolved_head_dim * cfg.rope_fraction)
        return rd - rd % 2

    def _attention(self, window=None):
        cfg = self.cfg
        if cfg.attention_kind == "none":
            return None
        if cfg.attention_kind == "mla":
            m = cfg.mla
            yarn, mult = None, 1.0
            rs = cfg.rope_scaling
            if rs is not None:
                # the rope's cos/sin carry yarn_mscale(mscale) /
                # yarn_mscale(mscale_all_dim), 1 for every registered arch
                if yarn_mscale(rs.factor, rs.mscale) != yarn_mscale(
                        rs.factor, rs.mscale_all_dim or rs.mscale):
                    raise NotImplementedError("rope amplitude != 1")
                yarn = (rs.factor, rs.original_max_position, rs.beta_fast,
                        rs.beta_slow)
                if rs.mscale_all_dim:
                    mult = yarn_mscale(rs.factor, rs.mscale_all_dim) ** 2
            return MLAttention(
                d_model=cfg.d_model, num_heads=cfg.num_q_heads,
                kv_lora_rank=m.kv_lora_rank, qk_nope_dim=m.qk_nope_dim,
                qk_rope_dim=m.qk_rope_dim, v_head_dim=m.v_head_dim,
                q_lora_rank=m.q_lora_rank, rope_base=cfg.rope_base,
                scale_mult=mult, yarn=yarn)
        return Attention(
            d_model=cfg.d_model, num_q_heads=cfg.num_q_heads,
            num_kv_heads=cfg.num_kv_heads, head_dim=cfg.resolved_head_dim,
            encoding=self._encoding(), rope_fraction=cfg.rope_fraction,
            causal=True, window=window, softcap=cfg.attn_softcap,
            query_scale=cfg.query_scale, use_bias=cfg.attn_bias)

    def _ssm(self):
        cfg = self.cfg
        if cfg.ssm is None:
            return None
        if cfg.ssm.kind == "rwkv6":
            return RWKV6TimeMix(d_model=cfg.d_model,
                                head_dim=cfg.ssm.head_dim,
                                chunk=cfg.ssm.chunk)
        return MambaMixer(d_model=cfg.d_model, d_inner=cfg.ssm.d_inner,
                          state_size=cfg.ssm.state_size,
                          conv_width=cfg.ssm.conv_width, chunk=cfg.ssm.chunk)

    def _mlp(self, d_ff=None, moe=False):
        cfg = self.cfg
        if moe:
            m = cfg.moe
            return MoE(d_model=cfg.d_model, num_experts=m.num_experts,
                       top_k=m.top_k, expert_ff=m.expert_ff,
                       num_shared=m.num_shared,
                       capacity_factor=m.capacity_factor,
                       aux_weight=m.aux_weight, activation=cfg.activation,
                       norm_topk_prob=m.norm_topk_prob)
        d_ff = d_ff or cfg.d_ff
        if cfg.mlp_kind == "rwkv":
            return RWKVChannelMix(cfg.d_model, d_ff)
        if cfg.mlp_kind == "plain":
            return MLP(cfg.d_model, d_ff, activation=cfg.activation,
                       use_bias=cfg.attn_bias)
        return GatedMLP(cfg.d_model, d_ff, activation=cfg.activation)

    def _block(self, window=None, moe=False, d_ff=None):
        cfg = self.cfg
        return Block(
            d_model=cfg.d_model,
            attention=self._attention(window=window),
            ssm=self._ssm(),
            mlp=self._mlp(d_ff=d_ff, moe=moe),
            norm=cfg.norm, post_norms=(cfg.norm == "rms_offset"),
            parallel_ssm=cfg.parallel_ssm)

    def _build_groups(self) -> List[Tuple[Block, int]]:
        cfg = self.cfg
        n = cfg.num_layers
        groups: List[Tuple[Block, int]] = []
        is_moe = cfg.moe is not None
        if is_moe and cfg.moe.first_k_dense:
            k = cfg.moe.first_k_dense
            groups.append((self._block(moe=False, d_ff=cfg.moe.dense_ff
                                       or cfg.d_ff), k))
            n -= k
        if cfg.window_pattern == "alternating":
            # scanned in (local, global) pairs
            assert n % 2 == 0, n
            groups.append((("pair", self._block(window=cfg.window, moe=is_moe),
                            self._block(window=None, moe=is_moe)), n // 2))
        elif cfg.window_pattern == "mostly_local":
            # global at the first, middle, and last layer (hymba)
            assert n >= 5, n
            mid1 = (n - 3) // 2
            mid2 = (n - 3) - mid1
            groups.append((self._block(window=None, moe=is_moe), 1))
            groups.append((self._block(window=cfg.window, moe=is_moe), mid1))
            groups.append((self._block(window=None, moe=is_moe), 1))
            groups.append((self._block(window=cfg.window, moe=is_moe), mid2))
            groups.append((self._block(window=None, moe=is_moe), 1))
        else:
            groups.append((self._block(window=cfg.window, moe=is_moe), n))
        return groups

    # ------------------------------------------------------------------
    def specs(self):
        cfg = self.cfg
        s: Dict[str, Any] = {"embedding": self.embedding.specs()}
        for gi, (blk, count) in enumerate(self.groups):
            if isinstance(blk, tuple):            # alternating pair
                _, a, b = blk
                sub = {"a": a.specs(), "b": b.specs()}
            else:
                sub = blk.specs()
            if count > 1:
                sub = stack_specs(sub, count)
            s[f"group{gi}"] = sub
        s["final_norm"] = self.final_norm.specs()
        if not cfg.tie_embeddings:
            s["lm_head"] = self.lm_head.specs()
        if cfg.learned_positions:
            s["pos_embedding"] = {"embedding": ParamSpec(
                (cfg.max_position, cfg.d_model), init="normal", scale=0.01,
                axes=(None, "embed"))}
        return s

    # ------------------------------------------------------------------
    def _apply_block(self, blk, params, x, pose, segment_ids, cache,
                     cache_index):
        if isinstance(blk, tuple):
            _, a, b = blk
            ca = cache.get("a") if cache else None
            cb = cache.get("b") if cache else None
            x, aux1, nca = a(params["a"], x, pose, segment_ids, ca, cache_index,
                             impl=self.impl)
            x, aux2, ncb = b(params["b"], x, pose, segment_ids, cb, cache_index,
                             impl=self.impl)
            nc = None
            if nca is not None or ncb is not None:
                nc = {"a": nca, "b": ncb}
            return x, aux1 + aux2, nc
        return blk(params, x, pose, segment_ids, cache, cache_index,
                   impl=self.impl)

    def _scan_group(self, blk, params, x, pose, segment_ids, cache,
                    cache_index, remat: bool):
        """lax.scan over a stacked layer group (optionally rematerialized)."""
        has_cache = cache is not None

        def body(x, xs):
            lp, lc = xs if has_cache else (xs, None)
            x, aux, nc = self._apply_block(blk, lp, x, pose, segment_ids, lc,
                                           cache_index)
            return x, (aux, nc) if has_cache else (aux, 0)

        if remat:
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.nothing_saveable)
        xs = (params, cache) if has_cache else params
        length = jax.tree.leaves(params)[0].shape[0]
        x, (auxs, ncs) = jax.lax.scan(body, x, xs,
                                      unroll=length if self.unroll else 1)
        return x, jnp.sum(auxs), (ncs if has_cache else None)

    def __call__(self, params, tokens, *, positions=None, prefix_embeds=None,
                 cache=None, cache_index=None, remat: bool = True,
                 return_hidden: bool = False):
        """tokens (B, S) int32 -> logits (B, S', padded_vocab).

        ``prefix_embeds`` (B, P, d): stubbed modality frontend output
        (internvl patches / whisper frames are handled by EncDec below);
        prepended before the token embeddings at prefill/train time.
        ``cache``/``cache_index``: decode path; S is the new-token chunk.
        """
        cfg = self.cfg
        dtype = cfg.compute_dtype
        if cache is not None and "latent" in cache:
            b, s = tokens.shape
            cursor = jnp.broadcast_to(jnp.asarray(cache_index, jnp.int32),
                                      (b,))
            (x,), new_cache = self.latent_forward(
                params, cache, [dict(tokens=tokens, cursor=cursor,
                                     kv_length=cursor + s)])
            return self.head(params, x), jnp.zeros((), jnp.float32), new_cache
        x = self.embedding(params["embedding"], tokens, dtype=dtype)
        if prefix_embeds is not None:
            x = jnp.concatenate([prefix_embeds.astype(dtype), x], axis=1)
        b, s, _ = x.shape
        if positions is None:
            start = 0 if cache_index is None else cache_index
            if getattr(start, "ndim", 0) == 1:      # per-slot cursors
                positions = start[:, None] + jnp.arange(s, dtype=jnp.int32)
            else:
                positions = jnp.broadcast_to(
                    start + jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))
        if cfg.learned_positions:
            pe = jnp.take(params["pos_embedding"]["embedding"], positions,
                          axis=0).astype(dtype)
            x = x + pe
        pose = positions.astype(jnp.float32)[..., None]
        x = logical_constraint(x, "act_batch", "act_seq", "act_embed")

        aux = jnp.zeros((), jnp.float32)
        new_cache: Dict[str, Any] = {}
        for gi, (blk, count) in enumerate(self.groups):
            gp = params[f"group{gi}"]
            gc = cache.get(f"group{gi}") if cache else None
            if count > 1:
                x, gaux, nc = self._scan_group(
                    blk, gp, x, pose, None, gc, cache_index,
                    remat=remat and cache is None)
            else:
                x, gaux, nc = self._apply_block(blk, gp, x, pose, None, gc,
                                                cache_index)
            aux = aux + gaux
            if nc is not None:
                new_cache[f"group{gi}"] = nc
        x = self.final_norm(params["final_norm"], x)
        if return_hidden:
            return x, aux, (new_cache or None)
        return self.head(params, x), aux, (new_cache or None)

    def head(self, params, x):
        """Final-normed hidden states (..., d) -> logits (..., vocab)."""
        cfg = self.cfg
        if cfg.tie_embeddings:
            logits = self.embedding.attend(params["embedding"], x)
        else:
            logits = self.lm_head(params["lm_head"], x)
        if cfg.final_softcap:
            logits = jnp.tanh(logits / cfg.final_softcap) * cfg.final_softcap
        return logical_constraint(logits, "act_batch", "act_seq", "act_vocab")

    # ------------------------------------------------------------------
    def latent_forward(self, params, cache, parts):
        """Run token groups through the layers against the stacked latent
        cache (MLA models); returns ([final-normed hidden (B', n, d) per
        part], new cache).

        ``parts``: dicts with ``tokens`` (B', n), ``cursor`` (B',) the
        position of each row's first token, ``kv_length`` (B',) (see
        ``MLAttention.cached``), and optionally ``slots`` (B',) and
        ``scope`` (a ``jax.named_scope`` for the part's own ops). Each part
        attends on its own; the channel mixers (dense MLP or the experts)
        run once over every part's tokens together, so the experts'
        weights are read once a layer for all of them.

        Python loop over layers, NOT ``lax.scan``: the layer index must be
        static so the decode kernel and the row writes address the stacked
        cache in place; scanning the cache through xs/ys would copy the
        whole buffer every call (``nn.agent_sim`` does the same).
        """
        cfg = self.cfg
        dtype = cfg.compute_dtype
        xs, poses = [], []
        for p in parts:
            with jax.named_scope(p.get("scope") or "lm"):
                tok = p["tokens"]
                xs.append(self.embedding(params["embedding"], tok,
                                         dtype=dtype))
                n = tok.shape[1]
                poses.append((p["cursor"][:, None] + jnp.arange(
                    n, dtype=jnp.int32)).astype(jnp.float32)[..., None])
        buf = cache["latent"]
        li = 0
        for gi, (blk, count) in enumerate(self.groups):
            assert blk.ssm is None and not blk.post_norms, blk
            gp = params[f"group{gi}"]
            norm = blk._norm()
            for j in range(count):
                lp = gp if count == 1 else jax.tree.map(lambda a: a[j], gp)
                if count > 1 and isinstance(blk.mlp, MoE):
                    # the experts take their whole stack and the layer
                    # index: a sliced (E, d, f) operand would be copied
                    lp["mlp"] = {**lp["mlp"], **{n: gp["mlp"][n] for n in
                                                 ("gate", "up", "down")}}
                for i, p in enumerate(parts):
                    with jax.named_scope(p.get("scope") or "lm"):
                        h = norm(lp["norm_mix"], xs[i])
                        a, buf = blk.attention.cached(
                            lp["attn"], h, poses[i], buf, li,
                            cursor=p["cursor"], kv_length=p["kv_length"],
                            slots=p.get("slots"), impl=self.impl)
                        xs[i] = xs[i] + a
                hs = [norm(lp["norm_mlp"], x).reshape(-1, cfg.d_model)
                      for x in xs]
                h = jnp.concatenate(hs, 0)[None]
                if isinstance(blk.mlp, MoE):
                    m_out, _ = blk.mlp(lp["mlp"], h,
                                       layer=j if count > 1 else None)
                else:
                    m_out = blk.mlp(lp["mlp"], h)
                m_out = m_out[0]
                at = 0
                for i, x in enumerate(xs):
                    t = x.shape[0] * x.shape[1]
                    xs[i] = x + m_out[at:at + t].reshape(x.shape)
                    at += t
                li += 1
        xs = [self.final_norm(params["final_norm"], x) for x in xs]
        return xs, {"latent": buf}

    # ------------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        if self.cfg.attention_kind == "mla":
            # one stacked, feature-major buffer for every layer: (L, B, 1,
            # kv_lora + rope_dim, max_len), the latent then the rope key
            attn = self.groups[0][0].attention
            return {"latent": jnp.zeros(
                (self.cfg.num_layers, batch, 1, attn.latent_dim, max_len),
                dtype)}
        cache = {}
        for gi, (blk, count) in enumerate(self.groups):
            if isinstance(blk, tuple):
                _, a, b = blk
                one = {"a": a.init_cache(batch, max_len, dtype),
                       "b": b.init_cache(batch, max_len, dtype)}
            else:
                one = blk.init_cache(batch, max_len, dtype)
            if count > 1:
                one = jax.tree.map(
                    lambda x: jnp.broadcast_to(x, (count,) + x.shape).copy(),
                    one)
            cache[f"group{gi}"] = one
        return cache


class EncDecLM:
    """Encoder-decoder transformer (whisper family; conv frontend stubbed —
    inputs are precomputed frame embeddings)."""

    def __init__(self, cfg: ModelConfig, impl: Optional[str] = None,
                 unroll: bool = False):
        assert cfg.enc_dec
        self.cfg = cfg
        self.impl = impl
        self.unroll = unroll
        d = cfg.d_model
        self.embedding = Embedding(cfg.padded_vocab, d)
        enc_attn = Attention(d_model=d, num_q_heads=cfg.num_q_heads,
                             num_kv_heads=cfg.num_kv_heads,
                             head_dim=cfg.resolved_head_dim, encoding=None,
                             causal=False, use_bias=True)
        self.enc_block = Block(d_model=d, attention=enc_attn,
                               mlp=MLP(d, cfg.d_ff, activation="gelu"),
                               norm="layer")
        dec_self = Attention(d_model=d, num_q_heads=cfg.num_q_heads,
                             num_kv_heads=cfg.num_kv_heads,
                             head_dim=cfg.resolved_head_dim, encoding=None,
                             causal=True, use_bias=True)
        self.dec_block = Block(d_model=d, attention=dec_self,
                               mlp=MLP(d, cfg.d_ff, activation="gelu"),
                               norm="layer")
        self.cross_attn = Attention(d_model=d, num_q_heads=cfg.num_q_heads,
                                    num_kv_heads=cfg.num_kv_heads,
                                    head_dim=cfg.resolved_head_dim,
                                    encoding=None, causal=False, use_bias=True)
        self.enc_norm = LayerNorm(d)
        self.dec_norm = LayerNorm(d)
        self.cross_norm = LayerNorm(d)

    def specs(self):
        cfg = self.cfg
        return {
            "embedding": self.embedding.specs(),
            "pos_embedding": {"embedding": ParamSpec(
                (cfg.max_position, cfg.d_model), init="normal", scale=0.01,
                axes=(None, "embed"))},
            "encoder": stack_specs(self.enc_block.specs(), cfg.encoder_layers),
            "decoder": stack_specs(self.dec_block.specs(), cfg.num_layers),
            "cross": stack_specs({"norm": self.cross_norm.specs(),
                                  "attn": self.cross_attn.specs()},
                                 cfg.num_layers),
            "enc_norm": self.enc_norm.specs(),
            "dec_norm": self.dec_norm.specs(),
        }

    def encode(self, params, frames):
        """frames (B, F, d_model): stubbed conv-frontend output."""
        cfg = self.cfg
        x = frames.astype(cfg.compute_dtype)
        pos = sinusoidal_positions(x.shape[1], cfg.d_model).astype(x.dtype)
        x = x + pos[None]

        def body(x, lp):
            x, _, _ = self.enc_block(lp, x)
            return x, 0

        x, _ = jax.lax.scan(body, x, params["encoder"],
                            unroll=self.cfg.encoder_layers if self.unroll
                            else 1)
        return self.enc_norm(params["enc_norm"], x)

    def decode(self, params, tokens, enc_out, cache=None, cache_index=None):
        cfg = self.cfg
        dtype = cfg.compute_dtype
        x = self.embedding(params["embedding"], tokens, dtype=dtype)
        b, s, _ = x.shape
        start = 0 if cache_index is None else cache_index
        positions = start + jnp.arange(s, dtype=jnp.int32)[None, :]
        pe = jnp.take(params["pos_embedding"]["embedding"],
                      jnp.broadcast_to(positions, (b, s)), axis=0)
        x = x + pe.astype(dtype)
        has_cache = cache is not None

        def body(x, xs):
            if has_cache:
                (dp, xp), lc = xs
            else:
                (dp, xp), lc = xs, None
            x, _, nc = self.dec_block(dp, x, cache=lc, cache_index=cache_index,
                                      impl=self.impl)
            h = self.cross_norm(xp["norm"], x)
            c_out, _ = self.cross_attn(xp["attn"], h, kv=enc_out,
                                       impl=self.impl)
            x = x + c_out
            return x, (0, nc) if has_cache else (0, 0)

        xs = ((params["decoder"], params["cross"]), cache) if has_cache else \
            (params["decoder"], params["cross"])
        x, (_, ncs) = jax.lax.scan(body, x, xs,
                                   unroll=self.cfg.num_layers if self.unroll
                                   else 1)
        x = self.dec_norm(params["dec_norm"], x)
        logits = self.embedding.attend(params["embedding"], x)
        return logits, (ncs if has_cache else None)

    def __call__(self, params, frames, tokens, cache=None, cache_index=None):
        enc_out = self.encode(params, frames)
        logits, nc = self.decode(params, tokens, enc_out, cache=cache,
                                 cache_index=cache_index)
        return logits, jnp.zeros((), jnp.float32), nc

    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        one = self.dec_block.init_cache(batch, max_len, dtype)
        return jax.tree.map(
            lambda x: jnp.broadcast_to(
                x, (self.cfg.num_layers,) + x.shape).copy(), one)


def build_model(cfg: ModelConfig, impl: Optional[str] = None,
                unroll: bool = False):
    if cfg.enc_dec:
        return EncDecLM(cfg, impl=impl, unroll=unroll)
    return TransformerLM(cfg, impl=impl, unroll=unroll)
