"""Helpers the per-layer readers share."""
from __future__ import annotations


def kernel_seconds(ctx, patterns):
    """Summed device time of the ops whose name holds any of ``patterns``;
    None without a trace or when no such op ran."""
    tr = ctx.get("trace")
    if not tr:
        return None
    t = sum(s for n, s in tr["op_seconds"].items()
            if any(p in n for p in patterns))
    return t or None


def idle_share(ctx):
    tr = ctx.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def mfu(ctx):
    """Model FLOPs of the traced window over its length at peak, in %."""
    tr, flops = ctx.get("trace"), ctx.get("work", {}).get("model_flops")
    if not tr or not flops:
        return None
    return 100.0 * flops / (tr["window_s"] * ctx["peak"]["flops_bf16"])


def roofline(ctx, flops_key, bytes_key, patterns):
    """Least time of the counted work over the kernels' device time, in %."""
    from bench.work import least_time

    w = ctx.get("work", {})
    t = kernel_seconds(ctx, patterns)
    if t is None or not w.get(flops_key):
        return None
    return 100.0 * least_time(w[flops_key], w[bytes_key], ctx["peak"])[0] / t


def scope_seconds(ctx, name):
    """Device seconds of the traced window under the named scope ``name``
    (``jax.named_scope``), summed over devices; None without a trace or
    when no op carries the scope."""
    tr = ctx.get("trace")
    if not tr:
        return None
    return tr["scope_seconds"].get(name)


def scope_share(ctx, name):
    """``scope_seconds`` over the devices' busy time, in %."""
    t, tr = scope_seconds(ctx, name), ctx.get("trace")
    if t is None or tr["busy_s"] <= 0:
        return None
    return 100.0 * t / (tr["busy_s"] * tr["devices"])


def spans(ctx, name):
    """(start_s, end_s) of the traced window's host spans called ``name``,
    clipped to the window, in order; [] without a trace."""
    tr = ctx.get("trace")
    if not tr:
        return []
    return [(s, e) for n, s, e in tr["spans"] if n == name]
