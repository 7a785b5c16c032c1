"""The program's own host spans, set against the device trace.

While the profiler records, the program's default tracer
(`repro.obs.DEFAULT_TRACER`) keeps each span it opens (`serve.tick`,
`serve.admit`, `serve.decode.fetch`, ...) with its start and end in seconds
of `time.perf_counter()`. The profile's host clock is the same clock less
one constant. That constant is fitted here from the harness's own
`engine.step` spans, which the trace holds on its clock and `ctx.steps` on
`perf_counter`. Then each idle instant of the device is put down to the
innermost program span open at that instant.

Beside `adapter.py`, this is the one module of the harness that imports the
program (`repro.obs`). A program without the default tracer has no spans,
and every reading here is None.
"""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np

from chipbench import trace

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

MAX_RESIDUAL_S = 50e-6   # widest residual of the offset fit
NO_SPAN = "(none)"       # idle time inside no program span


def program_spans() -> list:
    """[(name, start, end)] of the default tracer's spans, on
    `perf_counter`; [] where the program has no default tracer."""
    try:
        from repro.obs import DEFAULT_TRACER
    except ImportError:
        return []
    return [(s.name, s.start, s.end) for s in DEFAULT_TRACER.spans()]


def fit_offset(host: list, steps: list):
    """(offset, residual), with trace time = perf_counter + offset.

    Each traced `engine.step` span of `host` lies inside the same call's
    (start, end, ...) in `steps`, so the offset lies between the largest
    (trace end - end) and the smallest (trace start - start) of the steps.
    A pause of the host between a clock reading and the span widens that
    step's room but moves neither bound. The offset is the middle of the
    two bounds, the residual half their distance: the most by which a
    mapped instant can be off, or where the bounds cross, by which the
    steps disagree. The traced spans are matched in order to the run of as
    many steps that fits best. None where the residual is over
    MAX_RESIDUAL_S, or nothing matches."""
    traced = np.array(sorted((s, e) for n, s, e in host
                             if n == "engine.step"), np.float64)
    run = np.array([st[:2] for st in steps], np.float64)
    n = len(traced)
    if n == 0 or len(run) < n:
        return None
    best = None
    for k in range(len(run) - n + 1):
        lo = (traced[:, 1] - run[k:k + n, 1]).max()
        hi = (traced[:, 0] - run[k:k + n, 0]).min()
        if best is None or abs(hi - lo) < abs(best[1] - best[0]):
            best = (lo, hi)
    lo, hi = best
    residual = float(abs(hi - lo) / 2)
    if residual > MAX_RESIDUAL_S:
        return None
    return float((lo + hi) / 2), residual


def innermost(spans: list) -> dict:
    """{name: [(start, end)]}: where each named span is the innermost one
    open. `spans` [(name, start, end)] nest, as one thread's spans do."""
    out: dict = {}

    def add(name, a, b):
        if b > a:
            out.setdefault(name, []).append((a, b))

    stack, t = [], -np.inf
    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            top, end = stack.pop()
            add(top, t, end)
            t = max(t, end)
        if stack:
            add(stack[-1][0], t, s)
        t = max(t, s)
        stack.append((name, e))
    while stack:
        top, end = stack.pop()
        add(top, t, end)
        t = max(t, end)
    return out


def idle_by_span(ctx):
    """{span name: seconds of the traced window `ctx.tw` in which the
    device is idle and that span is the innermost program span open}, mean
    over chips, with NO_SPAN for idle time inside none. None without a
    device trace, program spans or an offset that fits."""
    if ctx.trace is None or not ctx.trace.devices:
        return None
    spans = program_spans()
    fit = fit_offset(ctx.trace.host, ctx.steps) if spans else None
    if fit is None:
        return None
    offset = fit[0]
    lo, hi = ctx.tw
    own = innermost([(n, s + offset, e + offset) for n, s, e in spans
                     if e + offset > lo and s + offset < hi])
    out: dict = {}
    for dev in ctx.trace.devices:
        idle = trace.gaps(trace.union([(s, e) for _, s, e in dev.ops],
                                      lo, hi), lo, hi)
        left = trace.length(idle)
        for name, iv in own.items():
            t = trace.overlap(idle, trace.union(iv, lo, hi))
            out[name] = out.get(name, 0.0) + t
            left -= t
        out[NO_SPAN] = out.get(NO_SPAN, 0.0) + left
    return {k: v / len(ctx.trace.devices) for k, v in out.items()}


def idle_share(ctx, names) -> float | None:
    """% of the traced window in which the device is idle and the
    innermost program span open is one of `names`."""
    by = idle_by_span(ctx)
    if by is None:
        return None
    lo, hi = ctx.tw
    return 100.0 * sum(by.get(n, 0.0) for n in names) / (hi - lo)
