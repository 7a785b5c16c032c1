"""Reduced cells and mixes the CPU tests (and CPU rehearsals) run: the
programs' reduced widths, 4 slots of 256 tokens, prompts up to 120."""
import copy
import json

from conftest import BENCH, reduced_arch

SMALL_CELL = {"slots": 4, "max_seq": 256, "prefill_buckets": [32, 64, 128]}


def small(workload: str):
    cell = json.loads((BENCH / "cells" / f"{workload}.json").read_text())
    cell.update(SMALL_CELL, rate_per_s=2.0)
    mix = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    mix = copy.deepcopy(mix)
    mix["warm_s"] = 2
    if mix["system_prompts"]["count"]:
        mix["system_prompts"]["tokens"] = 32
    for c in mix["classes"]:
        c["prompt"].update(median=48, min=8, max=120)
        c["output"].update(median=8, min=2, max=24)
    return reduced_arch(cell["config"]), cell, mix

# the small cells' own limit on the widest served-token logit gap, set as
# chipbench/calibrate.py sets a cell's: CPU readings at these sizes, seeds
# 1, 2, 3 and 2**40 + 5 of qwen3-1.7b.chat, gave 0.0041 at most for the
# program and 0.0183 at least for the float8 control
SMALL_LIMIT = 0.011


def run_small(workload, seed, seconds=6.0, **kw):
    from chipbench import run as R
    arch, cell, mix = small(workload)
    cell["check"] = dict(cell["check"], max_logit_gap=SMALL_LIMIT)
    return R.run(workload, seed, seconds, False, arch=arch, cell=cell,
                 mix=mix, require_tpu=False, **kw)[0]
