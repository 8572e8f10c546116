"""Routed experts (``nn/moe.py``'s dropless grouped matmuls, named scope
``moe.experts``): the least time of the traced ticks' expert work (every
held expert's weights read once a layer, 2 x 3 x d x f FLOPs a routed
token) over the experts' device time. That time is the ops under the
scope plus the grouped-matmul kernels a TPU compiles ``jax.lax.ragged_dot``
into (``ragged-dot-*`` custom calls), which XLA emits without the scope."""
from bench.metrics._common import kernel_seconds, scope_seconds

#: XLA's grouped-matmul kernels (and their tile metadata) in the trace
PATTERNS = ("%ragged-dot",)


def read(ctx):
    from bench.work import least_time

    w = ctx.get("work", {})
    t = (scope_seconds(ctx, "moe.experts") or 0.0) \
        + (kernel_seconds(ctx, PATTERNS) or 0.0)
    if not t or not w.get("moe_flops"):
        return None
    return 100.0 * least_time(w["moe_flops"], w["moe_bytes"],
                              ctx["peak"])[0] / t
