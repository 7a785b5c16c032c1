"""Device: share of the traced window in which no operation ran."""
from chipbench.stats import idle_share


def read(ctx):
    return idle_share(ctx)
