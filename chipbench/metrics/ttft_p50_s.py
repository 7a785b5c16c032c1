"""Median time to first token, from each request's due time.

Every request due in the window counts. One with no first token by the
window's end enters at (window end - due time). The median, because a chat
window at 0.8 of the knee holds about twenty requests: no higher percentile
has ten samples beyond it."""
from chipbench.stats import pct, ttfts


def read(ctx):
    return pct(ttfts(ctx), 50)
