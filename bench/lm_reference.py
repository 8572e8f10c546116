"""Plain reference of DeepSeek-V2 (arXiv:2405.04434), in ``jax.numpy``.

Written from the published description and the configuration file (the
Hugging Face ``config.json`` keys), independently of the program under
test: it imports nothing from ``repro`` and takes nothing the program made.
One sequence at a time, float32, every matmul at the caller's
``jax.default_matmul_precision`` (``"highest"`` in the check):

* token embedding; pre-norm blocks, RMSNorm (``rms_norm_eps``) -> attention
  -> residual, RMSNorm -> feed-forward -> residual; a final RMSNorm and the
  output head;
* multi-head latent attention, **not absorbed**: per-head queries
  (``qk_nope_head_dim`` + ``qk_rope_head_dim``), a ``kv_lora_rank`` latent
  (RMS-normed) expanded to per-head keys and values, one shared rope key;
  causal softmax at ``(qk_nope + qk_rope) ** -0.5 * mscale ** 2`` with
  ``mscale = 0.1 * mscale_all_dim * ln(factor) + 1``;
* YaRN rope: frequencies ``base ** (-2i / d)`` blended with the same
  divided by ``factor`` by a linear ramp between the correction indices of
  ``beta_fast`` and ``beta_slow`` rotations over the original context.
  Departure, shared with the program: rope pairs feature i with i + d/2
  (split-half); the published code de-interleaves q and k_rope first, a
  fixed permutation of those 64 weight columns;
* layer ``< first_k_dense_replace``: a SwiGLU of ``intermediate_size``;
  the others: softmax router over ``n_routed_experts``, greedy top
  ``num_experts_per_tok`` (exact: every expert's logit is scored, and each
  expert then runs on exactly the tokens that chose it), weights the
  softmax taken at the top k
  (``norm_topk_prob`` false) times ``routed_scaling_factor``, SwiGLU experts
  of ``moe_intermediate_size``, plus ``n_shared_experts`` shared ones as one
  SwiGLU of that many times the width.

Attention goes in blocks of at most ``BLOCK`` query positions.
``operand_bits`` rounds every matmul's operands to that many stored
mantissa bits first (3: float8_e4m3's significand, the control one step
below the bfloat16 the configuration states; the exponent range is not
modelled).

The weights are made here too, from the seed, in bfloat16 (the stated
parameter dtype), so the program and the reference read the same numbers;
the reference casts one layer at a time to float32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30
#: query positions a block of the reference's attention computes
BLOCK = 1024
#: an expert's tokens are padded to a multiple of this many rows (few
#: distinct shapes to compile)
EXPERT_ROWS = 256


# -- weights -------------------------------------------------------------------

def _dims(cfg):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    return d, h, dn, dr, dv, cfg["kv_lora_rank"]


def layout(cfg):
    """{path: (shape, std)} of the parameters, in the program's layout;
    std None marks a norm scale (1 + N(0, 0.1)). A leading layer axis
    stacks the expert layers."""
    d, h, dn, dr, dv, r = _dims(cfg)
    nd = cfg["first_k_dense_replace"]
    nm = cfg["num_hidden_layers"] - nd
    e, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    fs = cfg["n_shared_experts"] * f
    v = cfg["vocab_size"]

    def attn(pre, lead):
        return {
            f"{pre}/attn/q/kernel": (lead + (d, h, dn + dr), d),
            f"{pre}/attn/kv_down/kernel": (lead + (d, r), d),
            f"{pre}/attn/kv_norm/scale": (lead + (r,), None),
            f"{pre}/attn/k_rope/kernel": (lead + (d, dr), d),
            f"{pre}/attn/k_up/kernel": (lead + (r, h, dn), r),
            f"{pre}/attn/v_up/kernel": (lead + (r, h, dv), r),
            f"{pre}/attn/o/kernel": (lead + (h, dv, d), h * dv),
            f"{pre}/norm_mix/scale": (lead + (d,), None),
            f"{pre}/norm_mlp/scale": (lead + (d,), None),
        }

    out = {"embedding/embedding": ((v, d), 1),
           "final_norm/scale": ((d,), None),
           "lm_head/kernel": ((d, v), d)}
    groups = []
    if nd:
        groups.append(("dense", nd))
    if nm:
        groups.append(("moe", nm))
    for gi, (kind, n) in enumerate(groups):
        pre, lead = f"group{gi}", ((n,) if n > 1 else ())
        out.update(attn(pre, lead))
        if kind == "dense":
            ff = cfg["intermediate_size"]
            out.update({f"{pre}/mlp/gate/kernel": (lead + (d, ff), d),
                        f"{pre}/mlp/up/kernel": (lead + (d, ff), d),
                        f"{pre}/mlp/down/kernel": (lead + (ff, d), ff)})
        else:
            out.update({
                f"{pre}/mlp/router": (lead + (d, e), d),
                f"{pre}/mlp/gate": (lead + (e, d, f), d),
                f"{pre}/mlp/up": (lead + (e, d, f), d),
                f"{pre}/mlp/down": (lead + (e, f, d), f),
                f"{pre}/mlp/shared/gate/kernel": (lead + (d, fs), d),
                f"{pre}/mlp/shared/up/kernel": (lead + (d, fs), d),
                f"{pre}/mlp/shared/down/kernel": (lead + (fs, d), fs)})
    return {p: (shape, None if fan is None else 1.0 / math.sqrt(fan))
            for p, (shape, fan) in out.items()}


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**63."""
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def make_params(cfg, seed: int):
    """The nested parameter dict in bfloat16, on the default device."""
    lay = sorted(layout(cfg).items())

    @jax.jit
    def build(key):
        flat = {}
        for i, (path, (shape, std)) in enumerate(lay):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            flat[path] = (1.0 + 0.1 * z if std is None
                          else z * std).astype(jnp.bfloat16)
        return flat

    tree = {}
    for path, leaf in build(seed_key(seed)).items():
        node = tree
        *parents, name = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[name] = leaf
    return tree


def layers(cfg):
    """[(kind, group name, index in the group or None)] of every layer, in
    order: the program's layout stacks a group of more than one layer."""
    nd = cfg["first_k_dense_replace"]
    kinds = ["dense"] * nd + ["moe"] * (cfg["num_hidden_layers"] - nd)
    out, gi, j = [], 0, 0
    for li, kind in enumerate(kinds):
        if li and kind != kinds[li - 1]:
            gi, j = gi + 1, 0
        out.append((kind, f"group{gi}", j if kinds.count(kind) > 1 else None))
        j += 1
    return out


def _static(cfg):
    """The configuration's numbers, and its rope scaling, as a hashable
    static argument."""
    out = [(k, v) for k, v in cfg.items()
           if isinstance(v, (int, float, str, bool))]
    if cfg.get("rope_scaling"):
        out.append(("rope_scaling", tuple(sorted(
            cfg["rope_scaling"].items()))))
    return tuple(sorted(out))


def _unstatic(items):
    return {k: dict(v) if k == "rope_scaling" and v else v
            for k, v in items}


# -- the mathematics -------------------------------------------------------------

def _op(x, bits):
    """A matmul operand in float32, its significand rounded to ``bits``
    stored bits (None: as it is)."""
    x = x.astype(jnp.float32)
    if bits is None:
        return x
    m, e = jnp.frexp(x)
    step = float(2 ** (bits + 1))
    return jnp.ldexp(jnp.round(m * step) / step, e)


def _ein(spec, a, b, bits):
    return jnp.einsum(spec, _op(a, bits), _op(b, bits))


def _rms(x, scale, eps):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return y * scale.astype(jnp.float32)


def yarn(cfg):
    """(rope frequencies (dr/2,), softmax scale) of the configuration."""
    dr, base = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    qk = cfg["qk_nope_head_dim"] + dr
    i = np.arange(dr // 2)
    freq = base ** (-2.0 * i / dr)
    rs = cfg.get("rope_scaling")
    if not rs:
        return freq, qk ** -0.5
    fac, orig = rs["factor"], rs["original_max_position_embeddings"]

    def index(rot):
        return dr * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    lo = max(math.floor(index(rs["beta_fast"])), 0)
    hi = min(math.ceil(index(rs["beta_slow"])), dr - 1)
    ramp = np.clip((i - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    freq = freq / fac * ramp + freq * (1.0 - ramp)
    m = 0.1 * rs["mscale_all_dim"] * math.log(fac) + 1.0
    return freq, qk ** -0.5 * m * m


def _rope(x, pos, freq):
    """Rotate x (..., S, dr) at positions pos (S,): feature i pairs with
    i + dr/2."""
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(freq, jnp.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    h = x.shape[-1] // 2
    x0, x1 = x[..., :h], x[..., h:]
    return jnp.concatenate([x0 * cos - x1 * sin, x1 * cos + x0 * sin], -1)


def _swiglu(x, gate, up, down, bits):
    g = _ein("td,df->tf", x, gate, bits)
    u = _ein("td,df->tf", x, up, bits)
    return _ein("tf,fd->td", jax.nn.silu(g) * u, down, bits)


def _layer_params(group, j, stacked):
    return jax.tree.map(lambda w: w[j], group) if stacked else group


@functools.partial(jax.jit,
                   static_argnames=("cfg_items", "stacked", "bits"))
def _attention(x, pos, group, j, *, cfg_items, stacked, bits):
    """The attention half of one block over the whole (padded) sequence x
    (S, d); its bfloat16 parameters are layer ``j`` of ``group`` (the group
    itself unless ``stacked``), each cast to float32 where it is used.
    Returns (x after the attention residual, its RMS-normed feed-forward
    input)."""
    cfg = _unstatic(cfg_items)
    d, h, dn, dr, dv, r = _dims(cfg)
    eps = cfg["rms_norm_eps"]
    freq, scale = yarn(cfg)
    lp = _layer_params(group, j, stacked)
    s = x.shape[0]
    a = lp["attn"]
    y = _rms(x, lp["norm_mix"]["scale"], eps)
    q = _ein("td,dhc->htc", y, a["q"]["kernel"], bits)       # (H, S, dn+dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos, freq)], -1)
    ckv = _rms(_ein("td,dr->tr", y, a["kv_down"]["kernel"], bits),
               a["kv_norm"]["scale"], eps)
    kr = _rope(_ein("td,dc->tc", y, a["k_rope"]["kernel"], bits), pos, freq)
    kn = _ein("tr,rhc->htc", ckv, a["k_up"]["kernel"], bits)
    v = _ein("tr,rhc->htc", ckv, a["v_up"]["kernel"], bits)
    k = jnp.concatenate([kn, jnp.broadcast_to(kr[None], (h, s, dr))], -1)
    outs = []
    for r0 in range(0, s, BLOCK):
        r1 = min(s, r0 + BLOCK)
        sc = _ein("hnc,hmc->hnm", q[:, r0:r1], k[:, :r1], bits) * scale
        mask = pos[None, :r1] <= pos[r0:r1, None]
        p = jax.nn.softmax(jnp.where(mask[None], sc, NEG_INF), -1)
        outs.append(_ein("hnm,hmc->hnc", p, v[:, :r1], bits))
    o = jnp.concatenate(outs, 1)                              # (H, S, dv)
    x = x + _ein("htc,hcd->td", o, a["o"]["kernel"], bits)
    return x, _rms(x, lp["norm_mlp"]["scale"], eps)


@functools.partial(jax.jit, static_argnames=("stacked", "bits"))
def _dense_mlp(x, y, group, j, *, stacked, bits):
    m = _layer_params(group, j, stacked)["mlp"]
    return x + _swiglu(y, m["gate"]["kernel"], m["up"]["kernel"],
                       m["down"]["kernel"], bits)


@functools.partial(jax.jit,
                   static_argnames=("cfg_items", "stacked", "bits"))
def _route(y, group, j, *, cfg_items, stacked, bits):
    """Each token's experts (S, k) and their weights: the softmax over all
    routed experts' logits, greedy top k, renormalised only if
    ``norm_topk_prob``, times ``routed_scaling_factor``."""
    cfg = _unstatic(cfg_items)
    m = _layer_params(group, j, stacked)["mlp"]
    probs = jax.nn.softmax(_ein("td,de->te", y, m["router"], bits), -1)
    top, ids = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    return ids, top * cfg["routed_scaling_factor"]


@functools.partial(jax.jit, static_argnames=("stacked", "bits"),
                   donate_argnums=(0,))
def _expert_add(acc, y, rows, w, group, j, e, *, stacked, bits):
    """acc + expert ``e``'s output on the tokens ``rows``, each times its
    routing weight ``w`` (a padding row has weight 0)."""
    m = group["mlp"]
    at = (j, e) if stacked else (e,)
    out = _swiglu(y[rows], m["gate"][at], m["up"][at], m["down"][at], bits)
    return acc.at[rows].add(out * w[:, None])


@functools.partial(jax.jit, static_argnames=("stacked", "bits"))
def _shared_mlp(x, y, routed, group, j, *, stacked, bits):
    sh = _layer_params(group, j, stacked)["mlp"]["shared"]
    return x + routed + _swiglu(y, sh["gate"]["kernel"], sh["up"]["kernel"],
                                sh["down"]["kernel"], bits)


def _layer(x, pos, group, j, *, cfg_items, kind, stacked, bits, live):
    """One block over the whole (padded) sequence x (S, d). The routed
    experts run on the host's schedule: each expert on exactly the tokens
    among the first ``live`` that chose it (padded to a multiple of
    ``EXPERT_ROWS`` with weight-0 rows), so the work is that of top-k
    routing, not of every expert on every token; tokens past ``live`` are
    padding that no real position attends to."""
    x, y = _attention(x, pos, group, j, cfg_items=cfg_items, stacked=stacked,
                      bits=bits)
    if kind == "dense":
        return _dense_mlp(x, y, group, j, stacked=stacked, bits=bits)
    ids, top = _route(y, group, j, cfg_items=cfg_items, stacked=stacked,
                      bits=bits)
    ids, top = np.asarray(ids)[:live], np.asarray(top)[:live]
    routed = jnp.zeros_like(x)
    for e in range(group["mlp"]["gate"].shape[-3]):
        tok, slot = np.nonzero(ids == e)
        if not len(tok):
            continue
        n = -(-len(tok) // EXPERT_ROWS) * EXPERT_ROWS
        rows = np.zeros((n,), np.int32)
        rows[:len(tok)] = tok
        w = np.zeros((n,), np.float32)
        w[:len(tok)] = top[tok, slot]
        routed = _expert_add(routed, y, jnp.asarray(rows), jnp.asarray(w),
                             group, j, jnp.int32(e), stacked=stacked,
                             bits=bits)
    return _shared_mlp(x, y, routed, group, j, stacked=stacked, bits=bits)


@functools.partial(jax.jit, static_argnames=("eps", "bits"))
def _head(x, norm, head, *, eps, bits):
    return _ein("td,dv->tv", _rms(x, norm, eps), head, bits)


def logits(cfg, params, tokens, at, *, length: int, operand_bits=None):
    """Logits (len(at), vocab) of one sequence ``tokens`` (S,) at the
    positions ``at``, from a forward pass over the sequence padded to
    ``length`` (a fixed shape, so one compilation serves every sequence;
    causality keeps the padding out of the real positions)."""
    s = len(tokens)
    assert s <= length and max(at) < s
    tok = np.zeros((length,), np.int32)
    tok[:s] = tokens
    pos = jnp.arange(length, dtype=jnp.int32)
    x = params["embedding"]["embedding"][jnp.asarray(tok)].astype(
        jnp.float32)
    items = _static(cfg)
    for kind, group, j in layers(cfg):
        x = _layer(x, pos, params[group], jnp.int32(j or 0), cfg_items=items,
                   kind=kind, stacked=j is not None, bits=operand_bits,
                   live=s)
    x = x[jnp.asarray(np.asarray(at))]
    return _head(x, params["final_norm"]["scale"], params["lm_head"]["kernel"],
                 eps=cfg["rms_norm_eps"], bits=operand_bits)


# -- sampling ---------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(3,))
def gumbel_noise(seed, uid, positions, vocab):
    """The Gumbel noise the server adds to the logits of request ``uid``
    at each of ``positions``: ``gumbel(fold_in(fold_in(key(seed), uid),
    position), (vocab,))``."""
    base = jax.random.fold_in(jax.random.key(seed), uid)
    return jax.vmap(lambda p: jax.random.gumbel(
        jax.random.fold_in(base, p), (vocab,), jnp.float32))(positions)
