"""Engine host, decode preparation: % of the traced window in which the
device is idle and the host is inside the program's `serve.decode.prepare`
span (tail pages, page table, cache tree, dispatch of the decode step; the
innermost span open, chipbench/program_spans.py)."""
from chipbench.program_spans import idle_share


def read(ctx):
    return idle_share(ctx, ("serve.decode.prepare",))
