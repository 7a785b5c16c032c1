"""Engine host, logits fetch: % of the traced window in which the device is
idle and the host is inside the program's `serve.decode.fetch` span (the
copy of the decode step's logits to the host; the innermost span open,
chipbench/program_spans.py)."""
from chipbench.program_spans import idle_share


def read(ctx):
    return idle_share(ctx, ("serve.decode.fetch",))
