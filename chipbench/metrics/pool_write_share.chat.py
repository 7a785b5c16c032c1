"""Page pool: share of device busy time in the eager programs outside the
compiled prefill and decode (page writes, their slices and pads)."""
from chipbench.stats import program_share


def read(ctx):
    return program_share(ctx, "eager")
