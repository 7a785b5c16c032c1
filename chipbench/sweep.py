"""Knee sweep of one cell: the highest offered rate whose backlog does not
grow through a 51-second window. Run once when a cell is defined; the rate
the cell file states comes from it.

    python chipbench/sweep.py --workload <cell> --seed <n> \
        --rates 0.5,1,1.5,2 [--seconds 51]

One process: the weights are made once, and each rate gets a fresh engine,
warm-up, warm period and window. Prints one JSON line per rate: offered
rate, requests due and completed in the window, backlog (requests waiting
in the engine's queue, preempted ones included) at the window's start and
end, TTFT p90 and tokens per second.
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

from chipbench import discover  # noqa: E402
from chipbench import run as R  # noqa: E402


def sweep(workload, seed, rates, seconds, *, arch=None, cell=None, mix=None,
          require_tpu=True):
    import jax
    import numpy as np
    from chipbench import adapter as A
    from chipbench import weights
    c = discover.cell(workload)
    cell, mix, arch = cell or c["cell"], mix or c["mix"], arch or c["arch"]
    if require_tpu:
        R.require_chips(jax, cell["chips"])
    A.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = R.CompileClock(jax)
    cfg = A.model_config(cell["config"], arch)
    tree = A.params(arch, cfg, weights.make(arch, seed, A.vocab_rows(cfg)))
    preempts = len({k["priority"] for k in mix["classes"]}) > 1
    out = []
    for rate in rates:
        eng = A.engine(cfg, tree, cell)
        R.warm_up(A, eng, cell, arch["vocab_size"], preempts)
        sched = c["generator"].schedule(
            mix, rate=rate, seconds=seconds, seed=seed,
            vocab=arch["vocab_size"], max_total=cell["max_seq"] - 1)
        d = R.drive(jax, A, eng, sched, warm_s=mix["warm_s"],
                    seconds=seconds, trace=False, clock=clock)
        recs, ws, we = d["records"], d["ws"], d["we"]
        due = [r for r in recs if ws <= r["due"] < we]
        ttft = [(r["tokens"][0] if r["tokens"] else we) - r["due"]
                for r in due]
        toks = sum(1 for r in recs for t in r["tokens"] if ws <= t < we)
        row = {"workload": workload, "rate": rate, "due": len(due),
               "completed": sum(1 for r in due if r["done"]),
               "backlog_start": d["c0"]["queued"],
               "backlog_end": d["c1"]["queued"],
               "ttft_p90_s": float(np.percentile(ttft, 90)) if ttft else None,
               "output_tok_s": toks / (we - ws),
               "preemptions": d["c1"]["preemptions"] - d["c0"]["preemptions"],
               "compiles_in_window": d["compiles_in_window"]}
        print(json.dumps(row), flush=True)
        out.append(row)
        del eng, d
        gc.collect()
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args(argv)
    try:
        sweep(args.workload, args.seed,
              [float(x) for x in args.rates.split(",")], args.seconds)
    except R.NoChip as e:
        R.log(f"FAIL: {e}")
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
