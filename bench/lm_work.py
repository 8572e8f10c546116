"""Work of the DeepSeek-V2 serving tick, counted from shapes.

Each function counts what the model's mathematics requires of one tick,
whatever implements it, from the configuration file's keys (Hugging Face
``config.json`` names). Elementwise work (norms, rope, softmax, sampling)
is not counted. Rooflines divide these counts by measured device time.
"""
from __future__ import annotations

CACHE_ITEMSIZE = {"float32": 4, "bfloat16": 2}
PARAM_ITEMSIZE = 2          # bfloat16 weights


def latent_width(cfg):
    """Width of one cached row: the latent, then the shared rope key."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def moe_layers(cfg):
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def mla_decode_work(cfg, live_rows, cache_dtype="bfloat16"):
    """FLOPs and bytes of one tick's absorbed latent decode: each decoding
    slot's ``live`` rows (its cursor after the tick's row) read once a
    layer, every head scoring them over the full row and averaging their
    latent part."""
    live = float(sum(live_rows))
    c, r = latent_width(cfg), cfg["kv_lora_rank"]
    n = cfg["num_hidden_layers"]
    flops = 2 * cfg["num_attention_heads"] * live * (c + r) * n
    return flops, live * c * CACHE_ITEMSIZE[cache_dtype] * n


def expert_params(cfg):
    """Parameters of one layer's routed experts."""
    return (cfg["n_routed_experts"] * 3 * cfg["hidden_size"]
            * cfg["moe_intermediate_size"])


def moe_work(cfg, tokens):
    """FLOPs and bytes of one tick's routed experts over ``tokens`` tokens:
    every held expert's weights read once a layer, each routed token's
    input row read and output row written."""
    k, d = cfg["num_experts_per_tok"], cfg["hidden_size"]
    n = moe_layers(cfg)
    flops = 2 * 3 * d * cfg["moe_intermediate_size"] * tokens * k * n
    nbytes = (expert_params(cfg) + 2 * tokens * k * d) * PARAM_ITEMSIZE * n
    return flops, nbytes


def dense_flops_per_token(cfg):
    """Matmul FLOPs of one token through every layer's projections and
    feed-forward (routed experts at k per token, shared and router
    included), without attention over the context and without the head."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    r = cfg["kv_lora_rank"]
    attn = d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) + h * dv * d
    dense = 3 * d * cfg["intermediate_size"]
    f = cfg["moe_intermediate_size"]
    moe = 3 * d * f * (cfg["num_experts_per_tok"] + cfg["n_shared_experts"]) \
        + d * cfg["n_routed_experts"]
    nd = cfg["first_k_dense_replace"]
    return 2 * (cfg["num_hidden_layers"] * attn + nd * dense
                + moe_layers(cfg) * moe)


def attention_flops_per_pair(cfg):
    """Scores and values of one (query, key) pair, over all heads and
    layers, in the published (expanded) form."""
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (2 * cfg["num_attention_heads"] * (qk + cfg["v_head_dim"])
            * cfg["num_hidden_layers"])


def head_flops(cfg):
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]


def tick_model_flops(cfg, live_rows, chunk_tokens, chunk_start,
                     chunk_last):
    """Model FLOPs of one tick: each decoding slot's token (its ``live``
    rows of context, then the head), and a prefill chunk of
    ``chunk_tokens`` tokens from position ``chunk_start`` (causal context;
    the head on its last token when it ends the prompt)."""
    n = len(live_rows)
    flops = n * (dense_flops_per_token(cfg) + head_flops(cfg)) \
        + sum(live_rows) * attention_flops_per_pair(cfg)
    if chunk_tokens:
        c = chunk_tokens
        pairs = c * chunk_start + c * (c + 1) / 2
        flops += c * dense_flops_per_token(cfg) \
            + pairs * attention_flops_per_pair(cfg) \
            + (head_flops(cfg) if chunk_last else 0)
    return flops
