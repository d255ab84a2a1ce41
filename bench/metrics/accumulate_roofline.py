"""The cohort accumulate kernel (``kernels/fused_update`` ``accumulate_pass``,
acc + w * g once per client and dtype group) against its HBM roofline, in
%."""
from lib.roofline import share


def read(ctx):
    return share(ctx, ("accumulate",), ("_accumulate_kernel",))
