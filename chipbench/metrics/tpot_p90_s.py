"""90th percentile over requests of the time per output token: for each
request, the span from its first to its last token in the window over the
tokens between, where that span is at least 0.25 s (many engine steps, so
the host clock's error stays small). A tick that holds a prefill lengthens
the span of every request decoding through it."""
from chipbench.stats import pct, tpots


def read(ctx):
    return pct(tpots(ctx), 90)
