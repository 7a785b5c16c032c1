"""Engine host, admission: % of the traced window in which the device is
idle and the host is inside the program's `serve.admit`, `serve.prefill` or
`pool.write_page` span (the innermost one open; chipbench/program_spans.py)."""
from chipbench.program_spans import idle_share


def read(ctx):
    return idle_share(ctx, ("serve.admit", "serve.prefill",
                            "pool.write_page"))
