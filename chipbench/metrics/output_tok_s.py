"""Tokens emitted in the window over the window's seconds."""


def read(ctx):
    n = sum(1 for r in ctx.requests for t in r["tokens"]
            if ctx.ws <= t < ctx.we)
    return n / (ctx.we - ctx.ws)
