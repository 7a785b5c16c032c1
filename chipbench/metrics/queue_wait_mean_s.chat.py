"""Scheduler: mean of due time -> the wall time of the engine tick that
first admitted the request (`Request.admit_tick`), over the requests due in
the window. One not admitted by the window's end counts at (window end -
due time)."""
import numpy as np

from chipbench.stats import due_in_window


def read(ctx):
    waits = [(r["admit_t"] if r["admit_t"] is not None else ctx.we)
             - r["due"] for r in due_in_window(ctx)]
    return float(np.mean(waits)) if waits else None
