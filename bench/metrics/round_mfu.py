"""The whole round's share of the chips' peak, in %: the FLOPs the round
requires (counted from shapes by the configuration's ``round_flops``, no
recomputation) over the device trace's window per round (the span of the
traced rounds' dispatch and device-sync annotations, so neither the
profiler's start and stop nor the host before the first round) times the
chips times the chip's published peak."""


def read(ctx):
    if ctx.trace.window_ns <= 0:
        return None
    round_s = ctx.trace.window_ns / 1e9 / ctx.rounds
    return 100.0 * ctx.round_flops / (round_s * ctx.chips * ctx.peaks.flops)
