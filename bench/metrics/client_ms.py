"""Device time per round, in ms, of the clients' local updates: the ops
under the program's ``repro.client`` scope (keep-trace local steps,
gradient evaluations, Hessian-vector products; attention included)."""
from lib.scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "repro.client")
