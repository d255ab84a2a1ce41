"""The server update kernel (``kernels/fused_update`` ``update_pass``: clip,
optimizer and parameter write in one sweep, once per dtype group) against
its HBM roofline, in %."""
from lib.roofline import share


def read(ctx):
    return share(ctx, ("update_sgd", "update_adam"), ("_update_kernel",))
