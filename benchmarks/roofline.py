"""Roofline report: three terms per (arch x shape x mesh) cell.

Combines the dry-run artifacts (results/dryrun/*.json: memory analysis,
per-device collective wire bytes from the compiled HLO) with the analytic
execution-cost model (benchmarks/flops.py — authoritative for FLOPs/bytes
because XLA's cost_analysis counts scan bodies once; the compiled counter is
still reported as a cross-check).

Hardware constants (TPU v5e per chip):
  peak bf16   197 TFLOP/s
  HBM bw      819 GB/s
  ICI         ~50 GB/s/link

Terms (seconds, per the assignment's formulas — numbers are global/chips):
  compute    = EXEC_FLOPS  / (chips * peak)
  memory     = EXEC_BYTES  / (chips * hbm_bw)
  collective = COLLECTIVE_BYTES / (chips * link_bw)
               with COLLECTIVE_BYTES = per-device wire bytes x chips, so the
               term reduces to per-device bytes / link_bw.

Usage: PYTHONPATH=src python -m benchmarks.roofline [--dryrun-dir results/dryrun]
Writes results/roofline.csv and prints the markdown table for EXPERIMENTS.md.

Serving mode (``--serving``) turns the same roofline constants on the paged
serving engine instead of the dry-run artifacts: it runs a small
deterministic workload (with one preempt/resume, so the cold tier actually
moves bytes) through the fused-sweep paged decode path, computes each KV
tier's achieved-vs-peak bandwidth fraction from the pool's tick-exact byte
counters (``repro.obs.serving_roofline`` — modeled, NOT wall time), merges
the report into ``BENCH_serving.json``, and gates the fractions against
``benchmarks/baselines/roofline_serving.json`` the same way serving_slo.py
gates TTFT. Exits non-zero on a gate failure so CI can enforce it.
"""
from __future__ import annotations

import os
import sys

# pin CPU-backend threading before jax loads (serving mode only needs it,
# but env must be set before any repro import pulls jax in)
os.environ.setdefault("OMP_NUM_THREADS", "1")
if "--xla_cpu_multi_thread_eigen" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_cpu_multi_thread_eigen=false").strip()

import argparse
import csv
import glob
import json
from pathlib import Path

from repro.configs import CONFIGS, SHAPES, get_config
from benchmarks.flops import cell_cost, active_params, total_params

PEAK_BF16 = 197e12
HBM_BW = 819e9
LINK_BW = 50e9


def analyze(dryrun_dir: str):
    rows = []
    for f in sorted(glob.glob(f"{dryrun_dir}/*.json")):
        r = json.load(open(f))
        if r["status"] != "ok":
            if r["status"] == "skipped":
                rows.append({"arch": r["arch"], "shape": r["shape"],
                             "mesh": r["mesh"], "status": "skipped",
                             "note": r.get("reason", "")})
            continue
        cfg = get_config(r["arch"])
        shape = SHAPES[r["shape"]]
        chips = r["chips"]
        cost = cell_cost(cfg, shape, accum=r.get("accum") or 0)
        t_compute = cost.exec_flops / (chips * PEAK_BF16)
        t_memory = cost.exec_bytes / (chips * HBM_BW)
        # collective: HLO bytes, corrected for lax.scan trip counts by
        # while-nesting depth (depth-1 = accum or groups scan, depth-2 =
        # groups scan inside accum; deeper scans get the same cap)
        accum = r.get("accum") or 1
        chunk_trips = max(1, shape.seq_len // max(cfg.chunk_size, 1)) \
            if cfg.ssm_heads and shape.kind != "decode" else 32
        if shape.kind == "train":
            trips = ([accum] if accum > 1 else []) + [cfg.num_groups,
                                                      chunk_trips]
        else:
            trips = [cfg.num_groups, chunk_trips]
        by_depth = r.get("collective_bytes_by_depth",
                         {"0": r["collective_bytes_per_dev"]})
        coll_dev = 0.0
        for depth_s, nb in by_depth.items():
            mult = 1.0
            for d in range(min(int(depth_s), len(trips))):
                mult *= trips[d]
            coll_dev += nb * mult
        t_coll = coll_dev / LINK_BW
        terms = {"compute": t_compute, "memory": t_memory,
                 "collective": t_coll}
        dominant = max(terms, key=terms.get)
        step_time = max(terms.values())
        # roofline fraction: MFU-style for train/prefill (useful compute vs
        # bottleneck), MBU-style for decode (achieved bandwidth vs HBM peak)
        t_useful = cost.model_flops / (chips * PEAK_BF16)
        if shape.kind == "decode":
            frac = t_memory / step_time if step_time > 0 else 0.0
        else:
            frac = t_useful / step_time if step_time > 0 else 0.0
        rows.append({
            "arch": r["arch"], "shape": r["shape"], "mesh": r["mesh"],
            "status": "ok", "chips": chips,
            "t_compute_s": t_compute, "t_memory_s": t_memory,
            "t_collective_s": t_coll, "dominant": dominant,
            "roofline_frac": frac,
            "model_flops": cost.model_flops, "exec_flops": cost.exec_flops,
            "useful_ratio": cost.model_flops / cost.exec_flops,
            "exec_bytes": cost.exec_bytes,
            "hlo_flops_per_dev(xcheck)": r["flops_per_dev"],
            "peak_gib_per_dev": r["peak_bytes_per_dev"] / 2**30,
            "accum": r.get("accum"),
        })
    return rows


# ---------------------------------------------------------------------- #
# serving mode: achieved-vs-peak bandwidth per KV tier
# ---------------------------------------------------------------------- #
# which peak each pool tier rooflines against: the hot tier is device HBM,
# the cold (spill) tier crosses the interconnect
SERVING_TIER_BW = {"hot": HBM_BW, "cold": LINK_BW}

# metrics a baselines/roofline_serving.json entry may gate, by key
_SERVING_METRICS = {
    "hot_bw_fraction": lambda r: r["tiers"]["hot"]["bw_fraction"],
    "cold_bw_fraction": lambda r: r["tiers"]["cold"]["bw_fraction"],
    "hot_bytes_per_token": lambda r: r["tiers"]["hot"]["bytes_per_token"],
    "cold_bytes_per_token": lambda r: r["tiers"]["cold"]["bytes_per_token"],
}


def run_serving(arch: str, steps: int = 160):
    """Run the deterministic serving workload; return the roofline report.

    Mirrors examples/serve_lm.py's shape: 6 requests over 3 slots with a
    shared-prefix pair, warm-up decode, then one preempt/resume so
    evictions + restores (the cold tier's traffic) appear in the counters.
    """
    import dataclasses

    import jax
    import numpy as np

    from repro.models import build_model
    from repro.obs import serving_roofline
    from repro.serving import PagedServingEngine, Request, ServingConfig

    cfg = get_config(arch).reduced()
    model = build_model(dataclasses.replace(cfg, paged_kv=True))
    params = model.init(jax.random.PRNGKey(0))
    eng = PagedServingEngine(cfg, params, ServingConfig(
        batch_slots=3, max_seq=96, page_tokens=8,
        prefill_buckets=(8, 16, 32)))

    rng = np.random.default_rng(0)
    shared = rng.integers(1, cfg.vocab_size, size=8).tolist()
    for i in range(6):
        if i < 2:
            prompt = shared + rng.integers(
                1, cfg.vocab_size, size=rng.integers(1, 6)).tolist()
        else:
            prompt = rng.integers(1, cfg.vocab_size,
                                  size=rng.integers(3, 12)).tolist()
        eng.submit(Request(rid=i, prompt=prompt, max_new_tokens=10))
    for _ in range(4):
        eng.step()
    victim = next((i for i, r in enumerate(eng.slot_req) if r is not None),
                  None)
    if victim is not None:
        eng.preempt(victim)
        eng.step()
        eng.resume(victim)
    out = eng.run(max_ticks=steps)
    assert all(len(v) == 10 for v in out.values()), \
        "serving workload did not finish every request"
    assert eng.pool.metrics.evictions >= 1 and \
        eng.pool.metrics.page_faults >= 1, \
        "preempt/resume moved no bytes through the cold tier"

    n_params = int(sum(x.size for x in jax.tree_util.tree_leaves(params)))
    roof = serving_roofline(econ=eng.economics(), n_params=n_params,
                            tokens_emitted=eng.metrics.tokens_emitted,
                            peak_flops=PEAK_BF16, hot_bw=HBM_BW,
                            cold_bw=LINK_BW)
    roof["arch"] = arch
    roof["steps"] = steps
    return roof


def evaluate_serving_gate(roof, baseline_path):
    """Gate serving roofline metrics against checked-in baselines.

    Every metric here is counter-derived and deterministic, so the gate is
    a two-sided band: measured must sit within [baseline / threshold,
    baseline * threshold]. Above-band = traffic regression (e.g. a copy
    crept back into the zero-copy path, or the fused commit double-writes);
    below-band = the byte accounting itself broke.
    """
    with open(baseline_path) as f:
        base = json.load(f)
    checks = []
    for metric, spec in sorted(base.items()):
        if metric.startswith("_"):      # _comment etc.
            continue
        measured = _SERVING_METRICS[metric](roof)
        lo = spec["baseline"] / spec["threshold"]
        hi = spec["baseline"] * spec["threshold"]
        checks.append({
            "metric": metric,
            "measured": measured,
            "baseline": spec["baseline"],
            "threshold": spec["threshold"],
            "pass": lo <= measured <= hi,
        })
    return {
        "baseline": baseline_path,
        "checks": checks,
        "pass": all(c["pass"] for c in checks),
    }


def _merge_serving_report(out_path, roof, gate):
    """Merge roofline + gate into BENCH_serving.json, preserving whatever
    serving_slo.py already wrote there."""
    report = {"benchmark": "serving_roofline"}
    if os.path.exists(out_path):
        with open(out_path) as f:
            report = json.load(f)
    report["roofline"] = roof
    report["roofline_gate"] = gate
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)


def serving_main(args):
    roof = run_serving(args.serving_arch, steps=args.serving_steps)
    m = roof["modeled"]
    print(f"serving roofline [{roof['arch']}]: "
          f"{roof['tokens_emitted']} tokens, critical path "
          f"{m['critical_path_s'] * 1e6:.1f}us ({m['dominant']}-bound)")
    for tier, t in roof["tiers"].items():
        print(f"  {tier:>4}: {t['bytes_moved']:>9} B moved "
              f"({t['bytes_per_token']:.0f} B/tok), achieved "
              f"{t['achieved_bw'] / 1e9:.2f} GB/s of "
              f"{t['peak_bw'] / 1e9:.0f} GB/s peak "
              f"= {t['bw_fraction']:.2%}")
    gate = evaluate_serving_gate(roof, args.serving_baseline)
    _merge_serving_report(args.out, roof, gate)
    print(f"wrote {args.out}")
    for c in gate["checks"]:
        status = "PASS" if c["pass"] else "FAIL"
        print(f"   gate {c['metric']}: {c['measured']:.4g} vs baseline "
              f"{c['baseline']:.4g} (band {c['threshold']}x) [{status}]")
    if not gate["pass"]:
        print("serving roofline gate: FAIL")
        return 1
    print("serving roofline gate: PASS")
    return 0


FIX_HINTS = {
    "compute": "raise useful_ratio: drop MoE einsum dispatch / lighter remat",
    "memory": "cut optimizer+activation traffic: larger microbatch, fp8/int8 "
              "moments, fused optimizer",
    "collective": "reshard to cut all-gathers: sequence-shard saves, overlap "
                  "FSDP gathers across groups (ICI preload)",
}


def to_markdown(rows):
    out = ["| arch | shape | mesh | compute s | memory s | collective s | "
           "dominant | MODEL/HLO | roofline frac | peak GiB/dev |",
           "|---|---|---|---|---|---|---|---|---|---|"]
    for r in rows:
        if r["status"] == "skipped":
            out.append(f"| {r['arch']} | {r['shape']} | {r['mesh']} | — | — |"
                       f" — | skipped | — | — | — |")
            continue
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} "
            f"| {r['t_compute_s']:.4f} | {r['t_memory_s']:.4f} "
            f"| {r['t_collective_s']:.4f} | **{r['dominant']}** "
            f"| {r['useful_ratio']:.2f} | {r['roofline_frac']:.2%} "
            f"| {r['peak_gib_per_dev']:.1f} |")
    return "\n".join(out)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun-dir", default="results/dryrun")
    ap.add_argument("--csv", default="results/roofline.csv")
    ap.add_argument("--serving", action="store_true",
                    help="roofline the paged serving engine's KV tiers "
                         "instead of the dry-run artifacts; gates vs "
                         "--serving-baseline and merges into --out")
    ap.add_argument("--serving-arch", default="qwen3-1.7b")
    ap.add_argument("--serving-steps", type=int, default=160)
    ap.add_argument("--serving-baseline",
                    default="benchmarks/baselines/roofline_serving.json")
    ap.add_argument("--out", default="BENCH_serving.json",
                    help="serving mode: BENCH JSON to merge the roofline "
                         "report into")
    args = ap.parse_args(argv)
    if args.serving:
        sys.exit(serving_main(args))
    rows = analyze(args.dryrun_dir)
    ok = [r for r in rows if r["status"] == "ok"]
    Path(args.csv).parent.mkdir(parents=True, exist_ok=True)
    if ok:
        with open(args.csv, "w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=list(ok[0].keys()))
            w.writeheader()
            for r in ok:
                w.writerow(r)
    print(to_markdown(rows))
    # summary: worst cells per criterion (the hillclimb candidates)
    if ok:
        worst_frac = min(ok, key=lambda r: r["roofline_frac"])
        worst_coll = max(ok, key=lambda r: r["t_collective_s"]
                         / max(1e-12, max(r["t_compute_s"], r["t_memory_s"])))
        print(f"\nworst roofline fraction: {worst_frac['arch']} x "
              f"{worst_frac['shape']} x {worst_frac['mesh']} "
              f"({worst_frac['roofline_frac']:.2%}, "
              f"dominant {worst_frac['dominant']})")
        print(f"most collective-bound: {worst_coll['arch']} x "
              f"{worst_coll['shape']} x {worst_coll['mesh']}")
        for r in (worst_frac, worst_coll):
            print(f"  fix hint [{r['dominant']}]: {FIX_HINTS[r['dominant']]}")
    return rows


if __name__ == "__main__":
    main()
