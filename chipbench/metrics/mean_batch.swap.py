"""Scheduler: tokens emitted per decode step over the window (engine
counters `tokens_emitted` and `decode_steps`)."""


def read(ctx):
    steps = ctx.c1["decode_steps"] - ctx.c0["decode_steps"]
    if steps <= 0:
        return None
    return (ctx.c1["tokens_emitted"] - ctx.c0["tokens_emitted"]) / steps
