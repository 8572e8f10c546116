"""Runtime: the host's own time in a serve tick, the median over the
traced window's ``sim_server.tick`` spans of the tick's length less the
time its ``sim_server.drain`` child waits for an earlier tick's outputs."""
import statistics

from bench.metrics._common import spans


def read(ctx):
    ticks = spans(ctx, "sim_server.tick")
    if not ticks:
        return None
    drains = spans(ctx, "sim_server.drain")
    host = [(e - s) - sum(min(e, de) - max(s, ds) for ds, de in drains
                          if ds < e and de > s)
            for s, e in ticks]
    return 1e3 * statistics.median(host)
