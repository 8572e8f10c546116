"""Whole LM tick (``Server``'s latent tick): model FLOPs of the traced
ticks (each decoding token through every layer and the head, attention
over its live context, and each prefill chunk's tokens over their causal
context) over the traced window at the bf16 peak."""
from bench.metrics._common import mfu


def read(ctx):
    return mfu(ctx)
