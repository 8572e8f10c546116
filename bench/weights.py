"""Random weights of the agent-sim model, made on the device from a seed.

The benchmark makes the weights, so the reference and the program are fed
the same numbers and neither takes anything from the other. One jitted call
makes every leaf, in float32 (the configurations' parameter dtype).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def layout(model):
    """{path: (shape, std)}; std None marks a norm scale (1 + N(0, 0.1))."""
    d, h, hd = model["d_model"], model["num_heads"], model["head_dim"]
    ff, n = model["d_ff"], model["num_layers"]
    out = {
        "map_enc/kernel": ((model["map_feat_dim"], d), model["map_feat_dim"]),
        "agent_enc/kernel": ((model["agent_feat_dim"], d),
                             model["agent_feat_dim"]),
        "blocks/attn/q/kernel": ((n, d, h, hd), d),
        "blocks/attn/k/kernel": ((n, d, h, hd), d),
        "blocks/attn/v/kernel": ((n, d, h, hd), d),
        "blocks/attn/o/kernel": ((n, h, hd, d), h * hd),
        "blocks/mlp/gate/kernel": ((n, d, ff), d),
        "blocks/mlp/up/kernel": ((n, d, ff), d),
        "blocks/mlp/down/kernel": ((n, ff, d), ff),
        "blocks/norm1/scale": ((n, d), None),
        "blocks/norm2/scale": ((n, d), None),
        "final_norm/scale": ((d,), None),
        "head/kernel": ((d, model["num_actions"]), d),
    }
    if model["encoding"] == "absolute":
        out["pose_proj/kernel"] = ((48, d), 48)
    return {p: (shape, None if fan is None else 1.0 / np.sqrt(fan))
            for p, (shape, fan) in out.items()}


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**63."""
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def make_params(model, seed: int):
    """The nested parameter dict, on the default device."""
    lay = sorted(layout(model).items())

    @jax.jit
    def build(key):
        flat = {}
        for i, (path, (shape, std)) in enumerate(lay):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            flat[path] = 1.0 + 0.1 * z if std is None else z * std
        return flat

    tree = {}
    for path, leaf in build(seed_key(seed)).items():
        node = tree
        *parents, name = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[name] = leaf
    return tree
