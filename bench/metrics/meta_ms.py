"""Device time per round, in ms, of the FedMeta step: the ops under the
program's ``repro.meta`` scope (the meta batch's forward and backward and
the parameter step)."""
from lib.scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "repro.meta")
