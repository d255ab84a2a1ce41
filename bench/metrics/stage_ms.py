"""Handing the sampled inputs to the runtime per round: the trainer's
``stage`` phase span (nested in ``sample_stack``), mean over the traced
rounds, in ms."""


def read(ctx):
    spans = [p["dur_s"] for p in ctx.phases if p["phase"] == "stage"]
    if not spans:
        return None
    return 1e3 * sum(spans) / ctx.rounds
