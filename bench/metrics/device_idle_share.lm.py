"""Device: 1 - busy / window over the traced window of an LM serving cell,
where busy is the union of the device's operation intervals."""
from bench.metrics._common import idle_share


def read(ctx):
    return idle_share(ctx)
