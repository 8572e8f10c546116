"""Ragged decode kernel (``kernels/flash_decode.py``): the least time of
the decode work of the traced ticks (each active slot's agent queries over
its live K/V rows at the cache's dtype) over the kernel's device time."""
from bench.metrics._common import roofline

#: Pallas kernels in the device trace: the trace gives no kernel names, and
#: these kernels are the only Pallas calls in the cell's programs
PATTERNS = ('custom_call_target="tpu_custom_call"',)


def read(ctx):
    return roofline(ctx, "decode_flops", "decode_bytes", PATTERNS)
