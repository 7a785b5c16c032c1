"""Readings that set a cell's correctness limit: the widest served-token
logit gap of sound runs of the program over many seeds (the lower reading)
and of the float8 control on the same requests (the upper reading).

    python chipbench/calibrate.py --workload <cell> --seeds s1,s2,... \
        --seconds 20

One process, one run per seed at the cell's own load and sizes; prints one
JSON line per seed and a summary line. Benchmark runs never run the
control.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import run as R  # noqa: E402


def calibrate(workload, seeds, seconds, **kw):
    rows = []
    for seed in seeds:
        res, _ = R.run(workload, seed, seconds, False, control=True, **kw)
        row = {"seed": seed,
               "program": res["check"]["max_logit_gap"]["value"],
               "control": res["control_max_logit_gap"],
               "failed": res["failed"]}
        print(json.dumps(row), flush=True)
        rows.append(row)
        gc.collect()
    prog = [r["program"] for r in rows if r["program"] is not None]
    ctrl = [r["control"] for r in rows if r["control"] is not None]
    summary = {"workload": workload, "lower": max(prog, default=None),
               "upper": min(ctrl, default=None), "seeds": len(rows)}
    print(json.dumps(summary), flush=True)
    return rows, summary


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    try:
        calibrate(args.workload, [int(s) for s in args.seeds.split(",")],
                  args.seconds)
    except R.NoChip as e:
        R.log(f"FAIL: {e}")
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
