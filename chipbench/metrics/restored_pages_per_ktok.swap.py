"""Page pool: pages restored from the host tier (`page_faults`) per 1000
tokens emitted in the window."""


def read(ctx):
    toks = ctx.c1["tokens_emitted"] - ctx.c0["tokens_emitted"]
    if toks <= 0:
        return None
    return 1000.0 * (ctx.c1["page_faults"] - ctx.c0["page_faults"]) / toks
