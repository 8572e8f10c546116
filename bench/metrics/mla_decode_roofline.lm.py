"""Latent decode kernel (``kernels/flash_decode.py``, compiled as
``flash_decode_latent``): the least time of the traced ticks' absorbed MLA
decode (each decoding slot's live 576-wide latent rows read once a layer,
16 heads scoring them over 576 features and averaging 512) over the
kernel's device time."""
from bench.metrics._common import roofline

#: the latent decode's Pallas calls, by the kernel's name in the program
PATTERNS = ("%flash_decode_latent",)


def read(ctx):
    return roofline(ctx, "decode_flops", "decode_bytes", PATTERNS)
