"""Serving launcher: paged, PUL-tiered continuous batching over the zoo.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --reduced \
      --requests 8 --max-new 12 --page-tokens 8 --slots 4

`--dense` falls back to the monolithic-cache reference engine. Page-pool
knobs: --page-tokens (page size), --hot-pages (fast-tier frames; 0 = fit
everything), --distance (preload distance for page restores; 0 = planner
d*). Scheduling knobs: --policy (fcfs | priority | slo-edf; the latter two
preempt running requests, swapping their pages to the cold tier),
--prefill-chunk (page-aligned chunked prefill so long prompts don't stall
decode), --high-priority-every / --ttft-deadline to shape a mixed-urgency
workload. A per-tick metrics line reports tokens/s, page faults,
shared-prefix hits, and the modeled fraction of restore latency the
preload plan hides.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import zoo
from repro.obs import Tracer, validate_chrome_trace
from repro.serving import (
    PagedServingEngine,
    Request,
    ServingConfig,
    ServingEngine,
)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--dense", action="store_true",
                    help="use the dense-cache reference engine")
    ServingConfig.add_flags(ap)
    ap.add_argument("--high-priority-every", type=int, default=0,
                    help="mark every Nth request high-priority with a TTFT "
                         "deadline (0 = uniform workload)")
    ap.add_argument("--ttft-deadline", type=int, default=8,
                    help="TTFT deadline in ticks for high-priority requests")
    ap.add_argument("--log-every", type=int, default=8)
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="record a unified Chrome/Perfetto trace of the run "
                         "(engine spans, scheduler decisions, page "
                         "lifecycle, DMA twin) to PATH")
    ap.add_argument("--metrics", metavar="PATH", default=None,
                    help="dump the final metrics registry (engine counters "
                         "+ cache economics) to PATH — Prometheus text for "
                         ".prom, JSON otherwise")
    args = ap.parse_args(argv)
    if args.dense and (args.trace or args.metrics):
        ap.error("--trace/--metrics instrument the paged engine; "
                 "drop --dense")

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = zoo.build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    # ONE config for both engines: each projects the facade onto its layer
    serving_cfg = ServingConfig.from_flags(args)
    if args.dense:
        eng = ServingEngine(cfg, params, serving_cfg)
    else:
        hook = (lambda s: print(
            f"[serve] tick {s['tick']:4d}  {s['tokens_per_sec']:6.1f} tok/s"
            f"  live {s['live_slots']}  queued {s['queued']}"
            f"  faults {s['page_faults']}  shared {s['shared_page_hits']}"
            f"  hidden {s['modeled_restore_latency_hidden']:.0%}")
            if s["tick"] % args.log_every == 0 else None)
        tracer = Tracer() if args.trace else None
        eng = PagedServingEngine(cfg, params, serving_cfg,
                                 metrics_hook=hook, tracer=tracer)
        print(f"[serve] paged KV: {eng.layout.features} packed features/token"
              f", {args.page_tokens} tokens/page, planned d*="
              f"{eng.pool.distance}")

    prompts = np.random.default_rng(0).integers(
        1, cfg.vocab_size, size=(args.requests, 8)).tolist()
    t0 = time.time()
    for i, p in enumerate(prompts):
        hp = args.high_priority_every and (i % args.high_priority_every == 0)
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=args.max_new,
                           priority=1 if hp else 0,
                           ttft_deadline=args.ttft_deadline if hp else -1))
    out = eng.run()
    dt = time.time() - t0
    total = sum(len(v) for v in out.values())
    for rid, toks in sorted(out.items()):
        print(f"[serve] req {rid}: {toks}")
    print(f"[serve] {total} tokens in {dt:.2f}s "
          f"({total / max(dt, 1e-9):.1f} tok/s, {args.slots} slots)")
    if not args.dense:
        snap = eng.snapshot()
        print(f"[serve] pages allocated {snap['pages_allocated']}, faults "
              f"{snap['page_faults']}, evictions {snap['evictions']}, "
              f"shared hits {snap['shared_page_hits']}, mean queue wait "
              f"{snap['mean_queue_latency']:.1f} ticks")
        print(f"[serve] policy {snap['policy']}: preemptions "
              f"{snap['preemptions']}, readmissions {snap['readmissions']}, "
              f"chunk passes {snap['chunk_passes']}, SLO violations "
              f"{snap['slo_violations']}, rejected {snap['rejected']}")
        econ = eng.economics()
        for tier, t in econ["tiers"].items():
            print(f"[serve] {tier} tier: {t['bytes_moved']} bytes moved "
                  f"({t['bytes_per_token']:.0f} B/token)")
        if args.trace:
            doc = eng.tracer.to_chrome(args.trace)
            errs = validate_chrome_trace(doc)
            assert not errs, "\n".join(errs)
            print(f"[serve] trace: {len(doc['traceEvents'])} events -> "
                  f"{args.trace} (load in ui.perfetto.dev, or "
                  "tools/trace_view.py)")
        if args.metrics:
            reg = eng.metrics_registry()
            if args.metrics.endswith(".prom"):
                reg.dump_prometheus(args.metrics)
            else:
                reg.dump_json(args.metrics)
            print(f"[serve] metrics -> {args.metrics}")


if __name__ == "__main__":
    main()
