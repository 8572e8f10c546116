"""Work the algorithm needs, counted from shapes.

Each function counts what the model's mathematics requires, whatever
implements it: attention pairs (each query over every live row, counted
without regard to validity), K/V rows a decode must read at the cache's
dtype, and dense matmul FLOPs per token. Elementwise work (norms, the
SE(2) transforms, softmax) is not counted. Rooflines divide these counts by
measured device time.
"""
from __future__ import annotations

import numpy as np

CACHE_ITEMSIZE = {"float32": 4, "bfloat16": 2, "int8": 1}


def row_widths(model):
    """(key, value) width of one cached row of one head."""
    hd = model["head_dim"]
    if model["encoding"] == "se2_fourier":
        c = (hd // 6) * (4 * model["fourier_terms"] + 2)
        return c, c
    return hd, hd


def dense_flops_per_token(model):
    """Matmul FLOPs of one token through every layer and the head."""
    d, hhd, ff = model["d_model"], model["num_heads"] * model["head_dim"], \
        model["d_ff"]
    per_layer = 2 * (3 * d * hhd + hhd * d + 3 * d * ff)
    return model["num_layers"] * per_layer + 2 * d * model["num_actions"]


def embed_flops_per_token(model, feat_dim):
    extra = 2 * 48 * model["d_model"] if model["encoding"] == "absolute" else 0
    return 2 * feat_dim * model["d_model"] + extra


def attention_flops_per_pair(model):
    """QK^T and PV of one (query, key) pair, over all heads and layers."""
    ck, cv = row_widths(model)
    return model["num_layers"] * model["num_heads"] * 2 * (ck + cv)


def kv_row_bytes(model, cache_dtype):
    """Bytes of one cached token's K and V rows, all heads and layers
    (plus the per-row scales of an int8 cache)."""
    ck, cv = row_widths(model)
    per_head = (ck + cv) * CACHE_ITEMSIZE[cache_dtype]
    if cache_dtype == "int8":
        per_head += 2 * 4
    return model["num_layers"] * model["num_heads"] * per_head


def decode_work(model, cache_dtype, num_agents, live_rows):
    """FLOPs and bytes of one tick's decode attention: each active slot's
    ``num_agents`` queries over its ``live_rows`` (the cursor after the
    tick's rows are written). ``live_rows``: iterable, one per slot."""
    live = float(np.sum(live_rows))
    flops = num_agents * live * attention_flops_per_pair(model)
    return flops, live * kv_row_bytes(model, cache_dtype)


def tick_model_flops(model, num_agents, agent_feat_dim, live_rows):
    """Model FLOPs of one tick: the agent tokens of every active slot
    through the dense layers, plus their decode attention."""
    n = len(live_rows)
    dense = n * num_agents * (dense_flops_per_token(model)
                              + embed_flops_per_token(model, agent_feat_dim))
    return dense + decode_work(model, "float32", num_agents, live_rows)[0]


def admit_model_flops(model, num_map, map_feat_dim):
    """Model FLOPs of one admission: the map tokens through the model."""
    dense = num_map * (dense_flops_per_token(model)
                       + embed_flops_per_token(model, map_feat_dim))
    return dense + num_map * num_map * attention_flops_per_pair(model)


def least_time(flops, nbytes, peak):
    """(seconds, bound) of the roofline: the larger of compute and memory
    time, and which one it is."""
    tc = flops / peak["flops_bf16"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
