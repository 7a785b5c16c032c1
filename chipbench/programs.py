"""Which device programs and kernels of the trace belong to which layer.

Names as they appear in a TPU profile of `PagedServingEngine` (XLA module
names lose their "(id)" suffix in `trace.busy_by_program`). The prefill is
jitted from a lambda ("jit__lambda"), and the decode step from a
`functools.partial`, which has no name ("jit__unknown"). The paged sweep
kernel is a Mosaic custom call inside the decode step, with no name of its
own in the trace. Every other program is an eager operation the
engine or its page pool dispatches one by one (page writes, slices, pads,
reads of evicted pages).
"""
from __future__ import annotations

import re

# the second alternatives hold once the programs get names of their own
# (this file is the benchmark's, which later PRs do not edit)
PREFILL = re.compile(r"^jit__lambda|prefill")
DECODE = re.compile(r"^jit__unknown|paged_decode")
# the decode step's only Mosaic kernel is the paged sweep
SWEEP_KERNEL = re.compile(r"tpu_custom_call")


def kind(program: str) -> str:
    if PREFILL.search(program):
        return "prefill"
    if DECODE.search(program):
        return "decode"
    return "eager"


def by_kind(busy_by_program: dict) -> dict:
    out = {"prefill": 0.0, "decode": 0.0, "eager": 0.0}
    for name, t in busy_by_program.items():
        out[kind(name)] += t
    return out
