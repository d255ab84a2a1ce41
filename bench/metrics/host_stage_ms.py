"""Host staging per round: the trainer's ``sample_stack`` phase span (cohort
sampling, stacking and the transfer to the device), mean over the traced
rounds, in ms."""


def read(ctx):
    spans = [p["dur_s"] for p in ctx.phases if p["phase"] == "sample_stack"]
    if not spans:
        return None
    return 1e3 * sum(spans) / ctx.rounds
