"""Model step: device time of the SE(2) transforms of queries and keys
(the named scope ``agent_sim.se2_transform``) over the device's busy time
in the traced window."""
from bench.metrics._common import scope_share


def read(ctx):
    return scope_share(ctx, "agent_sim.se2_transform")
