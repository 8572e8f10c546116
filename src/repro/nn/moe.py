"""Mixture-of-Experts with shard-local capacity dispatch + expert parallelism.

Dispatch strategy (all static shapes; sort/scatter provably shard-local):

  1. tokens are grouped into ``G`` dispatch groups matching the mesh's
     data-parallel shards (``G = pod x data``; 1 without a mesh);
  2. router top-k over ``E`` experts per token (plain SPMD einsum);
  3. the group-local work — stable-sort assignments by expert id, rank
     within expert via ``searchsorted``, scatter into a per-group
     ``(E, C_g, d)`` buffer with capacity dropping — runs inside a
     ``shard_map`` over the DP axes, so XLA lowers it as purely local
     sorts/gathers (GSPMD's gather partitioner otherwise replicates these
     at global token count, which is exactly the quadratic-ish blow-up this
     layer exists to avoid);
  4. expert FFN ``(G, E, C, d) x (E, d, f)`` back in SPMD-land: the buffer
     is sharded on its group dim (data) and constrained on its expert dim
     (model), so GSPMD inserts the dispatch all-to-all and the expert
     einsums run where the weights live;
  5. combine: a second shard_map gathers each group's expert outputs back
     to token order and applies router weights (the EP combine collective
     is the buffer's model-axis unshard at the shard_map boundary).

A shared-experts branch (deepseek/kimi) runs densely. Load-balance aux loss
follows Switch Transformer. Capacity semantics are GShard-style per
(group, expert) — the standard "dropping" strategy.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import (active_mesh, dp_shard_count,
                                        logical_constraint)
from repro.nn.layers import ACTIVATIONS
from repro.nn.mlp import GatedMLP
from repro.nn.module import ParamSpec


def _dispatch_local(xt, eid, w, cap: int, num_experts: int):
    """Group-local dispatch. xt (Tg, d); eid/w (Tg, k).

    Returns buf (E, cap, d), and sorted (eid_s, tok_s, w_s, pos) each
    (Tg*k,) for the combine step."""
    tg, d = xt.shape
    k = eid.shape[-1]
    flat_eid = eid.reshape(tg * k)
    flat_tok = jnp.arange(tg * k, dtype=jnp.int32) // k
    flat_w = w.reshape(tg * k)
    order = jnp.argsort(flat_eid, stable=True)
    eid_s = flat_eid[order]
    tok_s = flat_tok[order]
    w_s = flat_w[order]
    first = jnp.searchsorted(eid_s, eid_s, side="left")
    pos = jnp.arange(tg * k, dtype=jnp.int32) - first.astype(jnp.int32)
    pos = jnp.where(pos < cap, pos, cap)                       # cap -> drop
    buf = jnp.zeros((num_experts, cap + 1, d), xt.dtype)
    buf = buf.at[eid_s, pos].set(xt[tok_s], mode="drop")
    return buf[:, :cap], eid_s, tok_s, w_s, pos


def _combine_local(eo, eid_s, tok_s, w_s, pos, cap: int, tg: int):
    """Group-local combine. eo (E, cap, d) -> y (Tg, d) float32."""
    d = eo.shape[-1]
    gathered = eo[eid_s, jnp.minimum(pos, cap - 1)]            # (Tg*k, d)
    valid = (pos < cap)[:, None]
    contrib = jnp.where(valid, gathered.astype(jnp.float32)
                        * w_s[:, None].astype(jnp.float32), 0.0)
    return jnp.zeros((tg, d), jnp.float32).at[tok_s].add(contrib)


@dataclasses.dataclass(frozen=True)
class MoE:
    d_model: int
    num_experts: int
    top_k: int
    expert_ff: int
    num_shared: int = 0
    capacity_factor: float = 1.25
    aux_weight: float = 0.01
    activation: str = "silu"
    routed_scale: float = 1.0

    def _shared(self) -> Optional[GatedMLP]:
        if self.num_shared == 0:
            return None
        return GatedMLP(self.d_model, self.num_shared * self.expert_ff,
                        self.activation)

    def specs(self):
        d, e, f = self.d_model, self.num_experts, self.expert_ff
        s = {
            "router": ParamSpec((d, e), init="normal", scale=0.006,
                                axes=("embed_no_fsdp", None)),
            "gate": ParamSpec((e, d, f), init="fan_in",
                              axes=("experts", "embed", "mlp")),
            "up": ParamSpec((e, d, f), init="fan_in",
                            axes=("experts", "embed", "mlp")),
            "down": ParamSpec((e, f, d), init="fan_in",
                              axes=("experts", "mlp", "embed")),
        }
        shared = self._shared()
        if shared is not None:
            s["shared"] = shared.specs()
        return s

    def capacity(self, tokens_per_group: int) -> int:
        cap = int(tokens_per_group * self.top_k * self.capacity_factor
                  / self.num_experts)
        return max(8, cap + (-cap) % 8)

    def __call__(self, params, x) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """x: (B, S, d). Returns (y, aux_loss)."""
        b, s, d = x.shape
        e, k = self.num_experts, self.top_k
        t = b * s
        mesh = active_mesh()
        groups = dp_shard_count()
        if t % groups != 0 or (b % groups != 0 and groups > 1):
            groups = 1
        tg = t // groups
        cap = self.capacity(tg)
        xt = x.reshape(groups, tg, d)
        xt = logical_constraint(xt, "act_tokens", None, None)

        logits = jnp.einsum(
            "gtd,de->gte", xt.astype(jnp.float32),
            params["router"].astype(jnp.float32))                # (G, Tg, E)
        probs = jax.nn.softmax(logits, axis=-1)
        top_logits, top_ids = jax.lax.top_k(logits, k)           # (G, Tg, k)
        weights = jax.nn.softmax(top_logits, axis=-1) * self.routed_scale

        # ---- aux load-balance loss (Switch-style) ----
        density = jnp.zeros((e,), jnp.float32).at[top_ids.reshape(-1)].add(
            1.0) / (t * k)
        mean_prob = probs.mean(axis=(0, 1))
        aux = self.aux_weight * e * jnp.sum(density * mean_prob)

        # ---- shard-local dispatch ----
        if mesh is not None and groups > 1:
            dp = tuple(a for a in ("pod", "data") if a in mesh.shape)
            dspec = P(dp if len(dp) > 1 else dp[0])

            def disp(xt_l, eid_l, w_l):
                buf, eid_s, tok_s, w_s, pos = _dispatch_local(
                    xt_l[0], eid_l[0], w_l[0], cap, e)
                return (buf[None], eid_s[None], tok_s[None], w_s[None],
                        pos[None])

            buf, eid_s, tok_s, w_s, pos = jax.shard_map(
                disp, mesh=mesh,
                in_specs=(dspec, dspec, dspec),
                out_specs=(dspec,) * 5,
                check_vma=False)(xt, top_ids, weights)
        else:
            buf, eid_s, tok_s, w_s, pos = jax.vmap(
                lambda a, b_, c: _dispatch_local(a, b_, c, cap, e))(
                    xt, top_ids, weights)
        expert_in = logical_constraint(buf, "act_tokens", "act_experts",
                                       None, None)               # (G, E, C, d)

        # ---- expert FFN (SPMD: data x experts sharding) ----
        act = ACTIVATIONS[self.activation]
        g = jnp.einsum("gecd,edf->gecf", expert_in,
                       params["gate"].astype(expert_in.dtype))
        u = jnp.einsum("gecd,edf->gecf", expert_in,
                       params["up"].astype(expert_in.dtype))
        h = act(g) * u
        h = logical_constraint(h, "act_tokens", "act_experts", None,
                               "act_mlp")
        eo = jnp.einsum("gecf,efd->gecd", h,
                        params["down"].astype(h.dtype))          # (G, E, C, d)
        eo = logical_constraint(eo, "act_tokens", "act_experts", None, None)

        # ---- shard-local combine ----
        if mesh is not None and groups > 1:
            def comb(eo_l, eid_l, tok_l, w_l, pos_l):
                y = _combine_local(eo_l[0], eid_l[0], tok_l[0], w_l[0],
                                   pos_l[0], cap, tg)
                return y[None]

            y = jax.shard_map(comb, mesh=mesh,
                              in_specs=(dspec,) * 5, out_specs=dspec,
                              check_vma=False)(eo, eid_s, tok_s, w_s, pos)
        else:
            y = jax.vmap(lambda a, b_, c, dd, ee: _combine_local(
                a, b_, c, dd, ee, cap, tg))(eo, eid_s, tok_s, w_s, pos)
        y = logical_constraint(y, "act_tokens", None, None)

        shared = self._shared()
        if shared is not None:
            y = y + shared(params["shared"], xt).astype(jnp.float32)
        y = y.astype(x.dtype).reshape(b, s, d)
        return logical_constraint(y, "act_batch", "act_seq", "act_embed"), aux
