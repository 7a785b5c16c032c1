"""Model step, prefill: share of device busy time in the compiled prefill
programs."""
from chipbench.stats import program_share


def read(ctx):
    return program_share(ctx, "prefill")
