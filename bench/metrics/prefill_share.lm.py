"""Model step: device time of the prefill chunk's own ops (named scope
``lm_server.prefill``: its embedding, projections and expanded attention
over its slot's latent rows; the experts it shares with the decode rows
are not in it) over the device's busy time in the traced window."""
from bench.metrics._common import scope_share


def read(ctx):
    return scope_share(ctx, "lm_server.prefill")
