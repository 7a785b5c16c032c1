"""Process start to the start of the traffic: weights, engine, and the
warm-up of every shape the cell's traffic uses (compiling included)."""


def read(ctx):
    return ctx.setup_s
