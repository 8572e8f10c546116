"""Whole tick (``sim_server`` tick and admissions): model FLOPs of the
traced ticks (dense layers plus decode attention over live rows, and the
map tokens of each admission) over the traced window at the bf16 peak."""
from bench.metrics._common import mfu


def read(ctx):
    return mfu(ctx)
