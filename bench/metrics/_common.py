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
