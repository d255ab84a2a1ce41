"""A kernel family's share of its roofline, from measured device time.

share = max(FLOPs / peak FLOP/s, bytes / peak bytes/s) / kernel time, with
the FLOPs and bytes a round asks of the family (``lib.work``) and the
family's device time per round from the trace.

A Mosaic call belongs to a family when one of the family's kernel function
names is a word of its text (the names ``trace.kernels_from_hlo`` read from
the compiled program).  A family with no such call in the trace gives
nothing: no guess by how often a call runs.
"""
from __future__ import annotations


def kernel_ops(ctx, names):
    t = ctx.trace
    return sorted(n for n in t.custom_calls
                  if set(names) & set(t.op_text.get(n, n).split()))


def share(ctx, kinds, names):
    work = [ctx.work[k] for k in kinds if k in ctx.work]
    ops = kernel_ops(ctx, names)
    if not work or not ops:
        return None
    ctx.matched[names] = ops
    ns = sum(ctx.trace.op_ns[n] for n in ops) / ctx.rounds
    if ns <= 0:
        return None
    flops = sum(w[0] for w in work)
    nbytes = sum(w[1] for w in work)
    bound = max(flops / ctx.peaks.flops, nbytes / ctx.peaks.hbm_bw)
    return 100.0 * bound / (ns / 1e9)
