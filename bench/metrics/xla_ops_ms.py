"""Device time per round, in ms, of the ops that are neither Mosaic kernels
nor collectives: the clients' forward and backward passes, the meta step
and the flatten passes, together."""


def read(ctx):
    t = ctx.trace
    ns = sum(v for n, v in t.op_ns.items()
             if n not in t.custom_calls and not t.is_collective(n))
    if ns <= 0:
        return None
    return ns / 1e6 / ctx.rounds
