"""Logical-axis sharding (MaxText-style) for params and activations.

Parameters carry *logical* axis names (``"embed"``, ``"heads"``, ``"mlp"``,
``"experts"``, ``"vocab"``, ...). A rule set maps logical names to mesh axes;
``sharding_for_specs`` resolves a whole parameter spec tree to
``NamedSharding``s, silently dropping any mesh axis that does not divide the
tensor dimension (GSPMD could pad, but replication is cheaper than uneven
layouts for the odd cases here — e.g. hymba's 25 query heads).

Activation constraints go through :func:`logical_constraint`, which is a
no-op unless a mesh + rule context is active (so model code is runnable on a
single CPU device without ceremony).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Logical axis -> tuple of mesh axes, in priority order. "fsdp" axes shard
# the big parameter matrices over the data-parallel axes (ZeRO-3 style);
# "model" is tensor parallelism.
DEFAULT_RULES: Dict[str, Tuple[str, ...]] = {
    # parameters
    "vocab": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "mlp": ("model",),
    "experts": ("model",),
    "embed": ("pod", "data"),        # FSDP storage sharding
    "embed_no_fsdp": (),
    "head_dim": (),
    "kv_lora": (),
    "layers": (),
    "state": (),
    "conv": (),
    "basis": (),
    # activations
    "act_batch": ("pod", "data"),
    # sequence parallelism: the residual stream (and any seq-major
    # activation) shards its sequence dim over the model axis wherever the
    # head/mlp dims aren't already using it. This is what keeps the
    # remat-saved per-layer carries at 1/16 size on the big configs.
    "act_seq": ("model",),
    "act_embed": (),
    "act_heads": ("model",),
    "act_mlp": ("model",),
    "act_vocab": ("model",),
    "act_experts": ("model",),
    "act_kv": ("model",),
    # decode caches: prefer sharding KV heads over the model axis; when the
    # head count doesn't divide (MQA / kv=8 on a 16-wide axis), the spec
    # resolver falls through to sharding the cache length instead
    # (flash-decode style distributed softmax).
    "act_kvlen": ("model",),
    # flattened token dim in the MoE dispatch path (batch*seq collapsed)
    "act_tokens": ("pod", "data"),
    "act_cap": (),
}


class _Ctx(threading.local):
    mesh: Optional[Mesh] = None
    rules: Optional[Dict[str, Tuple[str, ...]]] = None


_CTX = _Ctx()


@contextlib.contextmanager
def use_mesh_rules(mesh: Mesh, rules: Optional[Dict[str, Tuple[str, ...]]] = None):
    """Activate a mesh + logical rules for constraints inside model code."""
    prev = (_CTX.mesh, _CTX.rules)
    _CTX.mesh, _CTX.rules = mesh, dict(rules or DEFAULT_RULES)
    try:
        with mesh:
            yield
    finally:
        _CTX.mesh, _CTX.rules = prev


@contextlib.contextmanager
def without_mesh_rules():
    """Turn logical constraints off, e.g. while tracing a ``shard_map``
    body, whose per-device code an enclosing mesh's rules do not describe.
    Also a decorator: ``without_mesh_rules()(fn)``."""
    prev = (_CTX.mesh, _CTX.rules)
    _CTX.mesh, _CTX.rules = None, None
    try:
        yield
    finally:
        _CTX.mesh, _CTX.rules = prev


def active_mesh() -> Optional[Mesh]:
    return _CTX.mesh


def dp_shard_count() -> int:
    """Number of data-parallel shards (pod x data) in the active mesh.

    The MoE layer uses this as its dispatch-group count so token sorting,
    capacity, and scatter/gather all stay local to a DP shard (the dispatch
    buffer then carries both a data-sharded group dim and a model-sharded
    expert dim — no global-token-count gathers in the lowered HLO)."""
    mesh = _CTX.mesh
    if mesh is None:
        return 1
    n = 1
    for a in ("pod", "data"):
        n *= mesh.shape.get(a, 1)
    return n


def _resolve_axis(dim: int, logical: Optional[str], mesh: Mesh,
                  rules: Dict[str, Tuple[str, ...]], used: set):
    """Mesh axes for one tensor dim, honoring divisibility and axis reuse."""
    if logical is None:
        return None
    axes = [a for a in rules.get(logical, ()) if a in mesh.shape]
    chosen = []
    size = 1
    for a in axes:
        if a in used:
            continue
        if dim % (size * mesh.shape[a]) == 0:
            chosen.append(a)
            size *= mesh.shape[a]
    for a in chosen:
        used.add(a)
    if not chosen:
        return None
    return tuple(chosen) if len(chosen) > 1 else chosen[0]


def spec_for(shape: Sequence[int], logical_axes: Sequence[Optional[str]],
             mesh: Mesh, rules: Optional[Dict[str, Tuple[str, ...]]] = None
             ) -> P:
    """PartitionSpec for one tensor given its logical axes."""
    rules = rules or DEFAULT_RULES
    used: set = set()
    parts = [_resolve_axis(d, ax, mesh, rules, used)
             for d, ax in zip(shape, logical_axes)]
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def logical_constraint(x, *logical_axes: Optional[str]):
    """Sharding constraint by logical activation axis names (no-op w/o mesh)."""
    mesh, rules = _CTX.mesh, _CTX.rules
    if mesh is None:
        return x
    spec = spec_for(x.shape, logical_axes, mesh, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def per_batch_head_shard(fn, q, k, v, *rows):
    """``fn(q, k, v, *rows)`` on each device's block of the active mesh.

    Attention is independent per (batch, head), so under a mesh it runs as
    a ``shard_map``: q/k/v ``(B, H, S, d)`` split batch over the DP axes and
    heads over the model axis, per-token ``rows`` ``(B, S)`` split like the
    batch. The compiler cannot partition a Pallas kernel itself (a Mosaic
    custom call). Without an active mesh this is ``fn(q, k, v, *rows)``.
    """
    mesh, rules = _CTX.mesh, _CTX.rules
    if mesh is None or mesh.size == 1:
        return fn(q, k, v, *rows)
    axes = ("act_batch", "act_heads", None, None)
    q_spec, k_spec = spec_for(q.shape, axes, mesh, rules), \
        spec_for(k.shape, axes, mesh, rules)
    pad = lambda spec: tuple(spec) + (None,) * (2 - len(spec))
    (qb, qh), (kb, kh) = pad(q_spec)[:2], pad(k_spec)[:2]
    if qh != kh:      # grouped kv heads that do not split like q's heads
        qh = kh = None
    q_spec, k_spec, row = P(qb, qh), P(kb, kh), P(qb)
    return jax.shard_map(without_mesh_rules()(fn), mesh=mesh,
                         in_specs=(q_spec, k_spec, k_spec) + (row,) * len(rows),
                         out_specs=q_spec, check_vma=False)(q, k, v, *rows)


def sharding_for_specs(spec_tree, mesh: Mesh,
                       rules: Optional[Dict[str, Tuple[str, ...]]] = None):
    """Map a ParamSpec tree to a NamedSharding tree."""
    from repro.nn.module import ParamSpec  # cycle-free: nn imports nothing here

    def one(spec):
        assert isinstance(spec, ParamSpec), spec
        return NamedSharding(mesh, spec_for(spec.shape, spec.axes, mesh, rules))

    return jax.tree.map(one, spec_tree,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


def batch_sharding(mesh: Mesh, shape: Sequence[int], rules=None) -> NamedSharding:
    """Sharding for a batch-leading array (tokens, labels, ...).

    Falls back to replication when the batch does not divide the DP axes
    (e.g. the batch=1 long-context shape).
    """
    logical = ["act_batch"] + [None] * (len(shape) - 1)
    return NamedSharding(mesh, spec_for(shape, logical, mesh, rules))


def derive_opt_shardings(spec_tree, opt_state, mesh, rules=None):
    """NamedShardings for an optimizer-state tree.

    Optimizer leaves mirror parameters (adamw mu/nu; adafactor unfactored v)
    or are factored reductions of them (adafactor vr/vc) — shardings are
    derived from the parameter ParamSpec logical axes so ZeRO-style state
    sharding follows the parameter layout exactly.
    """
    from repro.nn.module import ParamSpec, is_spec

    rules = rules or DEFAULT_RULES
    repl = NamedSharding(mesh, P())
    spec_leaves, spec_treedef = jax.tree.flatten(
        spec_tree, is_leaf=lambda x: isinstance(x, ParamSpec))

    def param_like(subtree):
        # shardings come from the ParamSpecs alone; the subtree only
        # proves the pytree structure matches (flatten_up_to would raise)
        spec_treedef.flatten_up_to(subtree)
        out = [NamedSharding(mesh, spec_for(s.shape, s.axes, mesh, rules))
               for s in spec_leaves]
        return spec_treedef.unflatten(out)

    def factored(subtree):
        leaves = spec_treedef.flatten_up_to(subtree)
        out = []
        for spec, leaf in zip(spec_leaves, leaves):
            if isinstance(leaf, dict) and "vr" in leaf:
                out.append({
                    "vr": NamedSharding(mesh, spec_for(
                        spec.shape[:-1], spec.axes[:-1], mesh, rules)),
                    "vc": NamedSharding(mesh, spec_for(
                        spec.shape[:-2] + spec.shape[-1:],
                        spec.axes[:-2] + spec.axes[-1:], mesh, rules)),
                })
            else:
                out.append({"v": NamedSharding(mesh, spec_for(
                    spec.shape, spec.axes, mesh, rules))})
        return spec_treedef.unflatten(out)

    def walk(node):
        if isinstance(node, tuple):
            return tuple(walk(x) for x in node)
        if isinstance(node, dict):
            out = {}
            for k, v in node.items():
                if k == "step":
                    out[k] = repl
                elif k in ("mu", "nu"):
                    out[k] = param_like(v)
                elif k == "v":
                    out[k] = factored(v)
                else:
                    out[k] = walk(v)
            return out
        return repl

    return walk(opt_state)
