"""The int8 uplink kernels (``kernels/comm`` quantize and dequant-FMA into
the aggregate, each once per client and dtype group) against their HBM
roofline, in %."""
from lib.roofline import share


def read(ctx):
    return share(ctx, ("quantize_i8", "dequant_i8_fma"),
                 ("_quantize_i8_kernel", "_dequant_i8_fma_kernel"))
