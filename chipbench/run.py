"""One benchmark run of one cell on the chip.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The run fails (exit 2, no result) unless JAX finds a TPU with the chips the
cell asks for. It makes the weights on the device from the seed, builds the
program's paged serving engine, warms up every shape the cell's traffic
uses, then drives the engine's `submit` and `step` from a seeded open-loop
schedule in this process: the traffic's warm period, then `--seconds` of
measured window. Each request is timed from its due time. After the window
it checks what the engine served against the float32 reference of the
configuration's family (chipbench/reference/<family>.py) and prints, as the
last line of standard output, one JSON object: `correct`, `attempted`,
`failed`, `metrics`, `device`, with `--trace 1` also `breakdown`, and last
`check`, the numbers compared with their limits. `--trace 0` reports the
cell's end-to-end metrics; `--trace 1` traces the start of the window with
the profiler and reports its per-layer metrics.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from chipbench import discover  # noqa: E402

TRACE_SECONDS = 10.0       # longest stretch of the window the profiler sees
FINISH_RESUMED_S = 60.0    # longest wait past the window for a resumed
                           # request to finish (cells whose traffic preempts)
OUT = ROOT / ".chipbench_out"


class NoChip(RuntimeError):
    pass


class CompileClock:
    """Compilations and their seconds, from JAX's own monitoring events
    (persistent-cache hits included)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self, jax):
        self.seconds = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration
        if event == self.EVENTS[-1]:
            self.compiles += 1


@dataclasses.dataclass
class Window:
    """What the metric readers (chipbench/metrics/) read."""
    arch: dict
    cell: dict
    peaks: dict | None
    setup_s: float
    ws: float                 # window start and end, host clock
    we: float
    requests: list            # per request: due, prompt_len, tokens, ...
    steps: list               # (start, end, tokens emitted) per engine step
    c0: dict                  # engine and pool counters at ws and at we
    c1: dict
    trace: object = None      # chipbench.trace.Trace of the traced stretch
    tw: tuple = (0.0, 0.0)    # traced window on the trace's clock
    traced_decode: list = dataclasses.field(default_factory=list)


def require_chips(jax, chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} TPU chips, found {len(devs)}")
    return devs


# --------------------------------------------------------------------------
# warm-up: every shape the cell's traffic uses
# --------------------------------------------------------------------------
def drain(A, eng):
    while A.busy(eng):
        eng.step()


def warm_up(A, eng, cell: dict, vocab: int, preempts: bool):
    """Prefill every bucket with prompts whose last page holds 1..P
    tokens (the page writes slice and pad per tail length), decode, and
    where the traffic preempts, swap a slot out to the host tier and back."""
    rng = np.random.default_rng(0)
    P, slots = cell["page_tokens"], cell["slots"]
    rid = -1
    prev = 0
    for bucket in cell["prefill_buckets"]:
        prefix = rng.integers(0, vocab, prev).tolist()
        for k in range(P):
            prompt = prefix + rng.integers(0, vocab, k + 1).tolist()
            eng.submit(A.request(rid, prompt, 2, 0))
            rid -= 1
        drain(A, eng)
        prev = bucket
    if preempts:
        for _ in range(slots):
            eng.submit(A.request(rid, rng.integers(0, vocab, 2 * P + 3)
                                 .tolist(), 6, 0))
            rid -= 1
        eng.step()
        eng.submit(A.request(rid, rng.integers(0, vocab, P + 5).tolist(),
                             2, 1))
        drain(A, eng)


# --------------------------------------------------------------------------
# the measured run
# --------------------------------------------------------------------------
def drive(jax, A, eng, schedule, *, warm_s, seconds, trace, clock,
          finish_resumed: float = 0.0):
    """Open loop: submit each request at its due time, step the engine
    while it has work. After the window, keep stepping for at most
    `finish_resumed` seconds, with no new requests, until a request that
    was preempted and resumed has finished. Returns the readings."""
    span = (jax.profiler.TraceAnnotation if trace
            else lambda name: contextlib.nullcontext())
    t0 = time.perf_counter()
    ws, we = t0 + warm_s, t0 + warm_s + seconds
    recs, live = [], {}
    steps, tick_t = [], {}
    traced_decode = []
    c0 = c1 = None
    compiles0 = 0
    tracing = False
    trace_dir = OUT / "trace"
    t_stop = ws + min(TRACE_SECONDS, seconds)
    late = []
    nxt = 0
    while True:
        now = time.perf_counter()
        if c0 is None and now >= ws:
            c0, compiles0 = A.counters(eng), clock.compiles
            if trace:
                shutil.rmtree(trace_dir, ignore_errors=True)
                jax.profiler.start_trace(str(trace_dir))
                tracing = True
        if tracing and now >= t_stop:
            jax.profiler.stop_trace()
            tracing = False
        if now >= we:
            if c1 is None:
                c1 = A.counters(eng)
            # past the window only to let a resumed request finish, so that
            # the check sees decode over restored pages (not measured)
            if (now >= we + finish_resumed or not A.busy(eng)
                    or any(r["done"] and r["preemptions"] for r in recs)):
                break
        with span("engine.submit"):
            while (now < we and nxt < len(schedule)
                   and t0 + schedule[nxt]["due"] <= now):
                q = schedule[nxt]
                rec = {"rid": nxt, "due": t0 + q["due"], "submit": now,
                       "prompt": q["prompt"], "prompt_len": len(q["prompt"]),
                       "cls": q["cls"], "tokens": [], "admit_t": None,
                       "done": False, "failed": False, "preemptions": 0,
                       "out": None}
                late.append(now - rec["due"])
                req = A.request(nxt, q["prompt"], q["max_new"], q["priority"])
                try:
                    eng.submit(req)
                    live[nxt] = (req, rec)
                except ValueError:
                    rec["failed"] = True
                recs.append(rec)
                nxt += 1
        if not A.busy(eng):
            nd = t0 + schedule[nxt]["due"] if nxt < len(schedule) else we
            nd = nd if nd < we else we
            with span("generator.wait"):
                time.sleep(max(0.0, min(nd, we, t_stop if tracing else we)
                               - time.perf_counter()))
            continue
        tick = A.tick(eng)
        a = time.perf_counter()
        tick_t[tick] = a
        with span("engine.step"):
            eng.step()
        b = time.perf_counter()
        emitted = 0
        for rid, (req, rec) in list(live.items()):
            new = len(req.out_tokens) - len(rec["tokens"])
            if new:
                if tracing and a >= ws:
                    n, seen = rec["prompt_len"], len(rec["tokens"])
                    traced_decode.extend(
                        n + j - 1 for j in range(max(seen, 1), seen + new))
                rec["tokens"].extend([b] * new)
                emitted += new
            if rec["admit_t"] is None and req.admit_tick >= 0:
                rec["admit_t"] = tick_t.get(req.admit_tick)
            rec["preemptions"] = req.preemptions
            if req.done:
                rec.update(done=not req.failed, failed=req.failed,
                           out=list(req.out_tokens))
                del live[rid]
        steps.append((a, b, emitted))
    if tracing:
        jax.profiler.stop_trace()
    for req, rec in live.values():
        rec["preemptions"] = req.preemptions
    return {"t0": t0, "ws": ws, "we": we, "records": recs, "steps": steps,
            "c0": c0 or A.counters(eng), "c1": c1,
            "compiles_in_window": clock.compiles - compiles0,
            "late": late, "traced_decode": traced_decode,
            "trace_dir": trace_dir if trace else None}


# --------------------------------------------------------------------------
# correctness
# --------------------------------------------------------------------------
def sample(recs, k: int, seed: int) -> list:
    """Up to k finished requests, drawn from the seed: the one with the
    most served tokens, one that was preempted and resumed if any, and
    the rest at random."""
    done = [r for r in recs if r["done"] and r["out"]]
    if not done:
        return []
    pick = [max(done, key=lambda r: (len(r["out"]), -r["rid"]))]
    resumed = [r for r in done if r["preemptions"] and r not in pick]
    if resumed:
        pick.append(resumed[0])
    rest = [r for r in done if r not in pick]
    rng = np.random.default_rng(seed)
    for i in rng.permutation(len(rest))[:max(0, k - len(pick))]:
        pick.append(rest[int(i)])
    return pick


def served_gaps(arch, w, picks, max_seq: int, *, control: bool = False):
    """Per request: the widest gap by which a served token's reference
    logit lies below the reference's best. With `control`, the served
    tokens are replaced by the argmax of the float8 control at the same
    positions of the same sequences."""
    R = discover.family(arch).reference
    out = []
    for r in picks:
        seq = r["prompt"] + r["out"]
        n, m = r["prompt_len"], len(r["out"])
        tokens = np.zeros(max_seq, np.int32)
        tokens[:len(seq) - 1] = seq[:-1]
        targets = np.zeros(max_seq, np.int32)
        targets[n - 1:n - 1 + m] = r["out"]
        h = R.hidden(arch, w, tokens)
        if control:
            hq = R.hidden(arch, w, tokens, quant=True)
            _, _, best = R.head_stats(arch, w, hq, targets, quant=True)
            targets[n - 1:n - 1 + m] = np.asarray(best)[n - 1:n - 1 + m]
        top, at, _ = R.head_stats(arch, w, h, targets)
        gap = np.asarray(top - at)[n - 1:n - 1 + m]
        out.append(float(gap.max()) if np.isfinite(gap).all()
                   else float("inf"))
    return out


# --------------------------------------------------------------------------
def run(workload: str, seed: int, seconds: float, trace: bool, *,
        arch: dict | None = None, cell: dict | None = None,
        mix: dict | None = None, require_tpu: bool = True,
        control: bool = False):
    """One run; returns (result dict, check lines). `arch`, `cell` and `mix`
    replace the cell's files (the CPU tests run reduced sizes). `control`
    also reads the float8 control's gaps at the same positions (the
    calibration of the limit; benchmark runs never do)."""
    import jax
    bench = discover.benchmark()
    c = discover.cell(workload)
    cell, mix, arch = cell or c["cell"], mix or c["mix"], arch or c["arch"]
    discover.family(arch)       # a family with a file missing fails here
    devs = (require_chips(jax, cell["chips"]) if require_tpu
            else jax.devices())
    from chipbench import adapter as A
    from chipbench import trace as T
    from chipbench import weights
    A.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = CompileClock(jax)

    cfg = A.model_config(cell["config"], arch)
    schedule = c["generator"].schedule(
        mix, rate=cell["rate_per_s"], seconds=seconds, seed=seed,
        vocab=arch["vocab_size"], max_total=cell["max_seq"] - 1)
    w = weights.make(arch, seed, A.vocab_rows(cfg))
    eng = A.engine(cfg, A.params(arch, cfg, w), cell)
    preempts = len({k["priority"] for k in mix["classes"]}) > 1
    warm_up(A, eng, cell, arch["vocab_size"], preempts)
    A.wait(eng)
    setup_s = time.perf_counter() - T_START
    setup_compile_s = clock.seconds

    d = drive(jax, A, eng, schedule, warm_s=mix["warm_s"], seconds=seconds,
              trace=trace, clock=clock,
              finish_resumed=FINISH_RESUMED_S if preempts else 0.0)
    peak = max((dv.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for dv in devs[:cell["chips"]])
    try:
        peaks = T.load_peaks(devs[0].device_kind)
    except KeyError:
        if require_tpu:
            raise
        peaks = None
    win = Window(arch=arch, cell=cell, peaks=peaks, setup_s=setup_s,
                 ws=d["ws"], we=d["we"], requests=d["records"],
                 steps=d["steps"], c0=d["c0"], c1=d["c1"],
                 traced_decode=d["traced_decode"])
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    breakdown = None
    if trace:
        tr = T.load(d["trace_dir"])
        shutil.rmtree(d["trace_dir"], ignore_errors=True)
        win.trace = tr
        if tr.host:
            win.tw = tr.window()
        lo, hi = win.tw
        busy = [T.busy(dv, lo, hi) for dv in tr.devices]
        device["busy_s"] = float(np.mean(busy)) if busy else 0.0
        device["window_s"] = hi - lo
        if tr.devices:
            breakdown = {"device_ops": T.top_ops(tr.devices[0], lo, hi),
                         "idle_gaps": T.idle_by_host(tr, tr.devices[0], lo,
                                                     hi)}
    group = "per_layer" if trace else "end_to_end"
    units = {m["name"]: m["unit"] for m in bench[group]}
    metrics = {}
    for name in discover.metric_names(bench, workload, group):
        v = discover.metric(name).read(win)
        if v is not None:
            metrics[name] = {"value": float(v), "unit": units[name]}

    due = [r for r in d["records"] if d["ws"] <= r["due"] < d["we"]]
    started = sum(1 for r in due if r["tokens"])
    completed = sum(1 for r in due if r["done"])
    failed = sum(1 for r in due if r["failed"])
    late = np.asarray(d["late"] or [0.0])
    log(f"{workload} seed {seed}: setup {setup_s:.3f} s, of it compiling "
        f"{setup_compile_s:.3f} s; compilations inside the window: "
        f"{d['compiles_in_window']}")
    log(f"requests due in the window {len(due)}, started {started}, "
        f"completed {completed}, failed {failed}; generator late by "
        f"p50 {np.median(late):.4f} s, max {late.max():.4f} s")
    log(f"counters over the window: " + json.dumps(
        {k: d["c1"][k] - d["c0"][k] for k in d["c1"]}))

    # check: the served tokens against the float32 reference
    picks = sample(d["records"], cell["check"]["samples"], seed)
    del eng, d
    gc.collect()
    t = time.perf_counter()
    gaps = served_gaps(arch, w, picks, cell["max_seq"])
    limit = cell["check"]["max_logit_gap"]
    worst = max(gaps) if gaps else float("inf")
    served = sum(len(r["out"]) for r in picks)
    log(f"check: {len(picks)} requests "
        f"({sum(1 for r in picks if r['preemptions'])} preempted and "
        f"resumed), {served} served tokens, reference "
        f"{time.perf_counter() - t:.3f} s; widest gap per request "
        + ", ".join(f"{g:.4f}" for g in gaps))
    if control:
        result_control = served_gaps(arch, w, picks, cell["max_seq"],
                                     control=True)
        log("control: widest gap per request "
            + ", ".join(f"{g:.4f}" for g in result_control))
    correct = bool(picks) and worst <= limit and failed == 0
    check = {"max_logit_gap": {"value": worst if np.isfinite(worst)
                               else None, "limit": limit}}
    result = {"correct": correct, "attempted": len(due), "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if control:
        result["control_max_logit_gap"] = max(result_control, default=None)
    result["check"] = check
    lines = [f"check max_logit_gap {worst!r} limit {limit!r}"]
    return result, lines


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result, lines = run(args.workload, args.seed, args.seconds,
                            bool(args.trace))
    except NoChip as e:
        log(f"FAIL: {e}")
        return 2
    for line in lines:
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
