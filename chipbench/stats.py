"""Shared arithmetic of the metric readers (chipbench/metrics/)."""
from __future__ import annotations

import numpy as np

from chipbench import programs, trace


def pct(xs, q: float):
    """q-th percentile (linear), or None for an empty sample."""
    xs = list(xs)
    if not xs:
        return None
    return float(np.percentile(np.asarray(xs, np.float64), q))


def due_in_window(ctx) -> list:
    return [r for r in ctx.requests if ctx.ws <= r["due"] < ctx.we]


def ttfts(ctx) -> list:
    return [(r["tokens"][0] if r["tokens"] and r["tokens"][0] < ctx.we
             else ctx.we) - r["due"] for r in due_in_window(ctx)]


MIN_SPAN_S = 0.25     # shortest host-clock span a time per token is read over


def tpots(ctx) -> list:
    out = []
    for r in ctx.requests:
        ts = [t for t in r["tokens"] if ctx.ws <= t < ctx.we]
        if len(ts) >= 2 and ts[-1] - ts[0] >= MIN_SPAN_S:
            out.append((ts[-1] - ts[0]) / (len(ts) - 1))
    return out


def program_share(ctx, kind: str):
    """% of device busy time (traced window, mean over chips) spent in
    programs of `kind` (chipbench/programs.py)."""
    if ctx.trace is None or not ctx.trace.devices:
        return None
    lo, hi = ctx.tw
    part = tot = 0.0
    for d in ctx.trace.devices:
        by = programs.by_kind(trace.busy_by_program(d, lo, hi))
        part += by[kind]
        tot += sum(by.values())
    if tot <= 0 or part <= 0:
        return None
    return 100.0 * part / tot


def idle_share(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    lo, hi = ctx.tw
    busy = np.mean([trace.busy(d, lo, hi) for d in ctx.trace.devices])
    return 100.0 * (1.0 - busy / (hi - lo))
