"""Device time per round, in ms, of attention forward and backward: the ops
under the program's ``repro.attention`` scope, wherever it nests (inside
the clients' updates and the meta step, so this time is also part of
``client_ms`` and ``meta_ms``)."""
from lib.scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "repro.attention")
