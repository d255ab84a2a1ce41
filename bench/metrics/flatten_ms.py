"""Device time per round, in ms, of the flat-buffer passes: the ops under
the program's ``repro.flatten`` scope (the concatenate and pad of each
client's gradient into the ``(rows, 128)`` buffers, and the unflatten
slices)."""
from lib.scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "repro.flatten")
