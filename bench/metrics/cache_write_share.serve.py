"""Model step: device time of the decode cache's writes (the named scope
``agent_sim.cache_write``: each tick's new rows and each admission's map
rows) over the device's busy time in the traced window."""
from bench.metrics._common import scope_share


def read(ctx):
    return scope_share(ctx, "agent_sim.cache_write")
