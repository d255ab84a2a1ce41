"""Device time per round, in ms, of the uplink codec's XLA ops: the ops
under the program's ``repro.codec`` scope that are not Mosaic calls (the
per-client amax and scale reductions).  The codec kernels are
``codec_roofline``'s."""
from lib.scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "repro.codec")
