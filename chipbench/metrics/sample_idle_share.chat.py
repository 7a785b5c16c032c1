"""Engine host, sampling: % of the traced window in which the device is
idle and the host is inside the program's `serve.sample` span (argmax, emit
and finish of the step's tokens; the innermost span open,
chipbench/program_spans.py)."""
from chipbench.program_spans import idle_share


def read(ctx):
    return idle_share(ctx, ("serve.sample",))
