"""Smoke run of the main paths on TPU, at full published model width.

    python chip_smoke.py               # one chip: serve qwen3-1.7b
    python chip_smoke.py --four-chips  # 2x2 chips: sharded qwen3-1.7b trainer

One chip (the default). Full-width qwen3-1.7b (28 layers, d_model 2048,
16 heads over 8 KV heads, head_dim 128, vocab 151936, bf16, weights drawn
from a seed) serves 8 prompts of 16 to 200 tokens, two of them sharing a
page-aligned prefix, through `PagedServingEngine` with the compiled fused
paged-sweep decode. One slot is paused and resumed mid-run, so its pages go
to the host tier and back. The same prompts go through the dense-cache
`ServingEngine`, the oracle. Greedy streams must agree; where a stream
parts from the oracle, the two engines' logits at that step must agree
within `LOGIT_TOL` and the two picks must be a near-tie within it. The
jitted sweep step the engine ran must lower to a Mosaic kernel
(`tpu_custom_call`), not the interpreter.

Four chips (`--four-chips`, and nothing else). `repro.launch.train` on a
(data=2, model=2) mesh at full qwen3-1.7b width for a few steps. The first
step's loss must match the loss of the same parameters and batch computed
on one chip within `LOSS_TOL`, and the loss must fall.

Every number printed is a smoke reading of one run, not a benchmark
result. Without a TPU, or outside the repository, the script exits
non-zero without a result; any failed check exits non-zero. The last line
of a passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

ARCH = "qwen3-1.7b"
SEED = 0

# Paged vs dense logits. Both engines run the same bf16 model through
# different programs (prefill at other widths; decode attention in the
# float32 Pallas kernel vs XLA), so activations round differently and the
# bf16 logits drift apart by a few bf16 ulps: on a TPU v5e the largest gap
# over all 128 steps was 0.125, with every stream identical. Logits have
# std ~0.9 (tied embedding std 0.02 over d_model 2048); a wrong page, mask
# or commit moves them by O(1) and parts a stream without a near-tie.
LOGIT_TOL = 0.25

# Sharded vs one-chip first-step loss (13.78 at random init on a v5e, the
# two 3e-4 apart). The 2x2 step sums bf16 matmul partials in another
# order; per-token losses move by bf16 rounding and their mean over
# batch x seq tokens by far less. A missing or doubled reduction moves the
# loss by whole units.
LOSS_TOL = 0.01


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def fail(msg: str) -> None:
    print(f"[smoke] FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def require_tpu(count: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU: JAX runs on {devs[0].platform}")
    if len(devs) < count:
        fail(f"needs {count} TPU chips, found {len(devs)}")
    return devs


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (persistent
    cache hits included), from its own monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration


# -------------------------------------------------------------------------- #
# one chip: serving
# -------------------------------------------------------------------------- #
def smoke_prompts(vocab: int, seed: int):
    """8 prompts of 16..200 tokens; rids 2 and 3 share a 64-token prefix
    (4 whole pages of 16)."""
    rng = np.random.default_rng(seed)
    draw = lambda n: rng.integers(1, vocab, size=n).tolist()
    prefix = draw(64)
    return [draw(16), draw(37), prefix + draw(29), prefix + draw(51),
            draw(128), draw(150), draw(181), draw(200)]


def record_paged_logits(eng):
    """{rid: [logits per emitted token]} of a PagedServingEngine."""
    logs, emit = {}, eng._emit_token

    def emit_token(slot, logits):
        logs.setdefault(eng.slot_req[slot].rid, []).append(
            np.asarray(logits, np.float32))
        emit(slot, logits)

    eng._emit_token = emit_token
    return logs


def record_dense_logits(eng):
    """{rid: [logits per emitted token]} of a dense ServingEngine."""
    logs, emit = {}, eng._emit

    def emit_all(logits):
        for i, r in enumerate(eng.slot_req):
            if r is not None:
                logs.setdefault(r.rid, []).append(
                    np.asarray(logits[i], np.float32))
        emit(logits)

    eng._emit = emit_all
    return logs


def record_sweep_args(eng):
    """Shapes of the first call of the engine's jitted sweep step, and a
    call counter."""
    seen = {"calls": 0, "shapes": None}
    step = eng._sweep_decode

    def sweep(*args):
        if seen["shapes"] is None:     # before the call donates the planes
            seen["shapes"] = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
        seen["calls"] += 1
        return step(*args)

    eng._sweep_decode = sweep
    return seen, step


def compare_streams(got, want, got_logits, want_logits):
    """Check each paged stream against the dense one; returns the worst
    logit gap seen and the number of streams that parted at a near-tie."""
    worst, parted = 0.0, 0
    for rid in sorted(want):
        a, b = got[rid], want[rid]
        if len(a) != len(b):
            fail(f"request {rid}: {len(a)} tokens, oracle {len(b)}")
        split = next((k for k in range(len(a)) if a[k] != b[k]), None)
        last = len(a) - 1 if split is None else split
        for k in range(last + 1):
            la, lb = got_logits[rid][k], want_logits[rid][k]
            if not (np.isfinite(la).all() and np.isfinite(lb).all()):
                fail(f"request {rid} step {k}: non-finite logits")
            gap = float(np.max(np.abs(la - lb)))
            worst = max(worst, gap)
            if gap > LOGIT_TOL:
                fail(f"request {rid} step {k}: logits differ by {gap:.4f} "
                     f"> {LOGIT_TOL}")
        if split is not None:
            ta, tb = a[split], b[split]
            tie = max(abs(float(l[ta] - l[tb]))
                      for l in (got_logits[rid][split],
                                want_logits[rid][split]))
            if tie > LOGIT_TOL:
                fail(f"request {rid} parts at step {split} ({ta} vs {tb}) "
                     f"without a near-tie: picks {tie:.4f} apart")
            parted += 1
            log(f"request {rid} parts from the oracle at step {split} "
                f"after a near-tie ({ta} vs {tb}, {tie:.4f} apart)")
    return worst, parted


def serve_phase(cfg, *, slots=8, max_seq=1024, page_tokens=16,
                buckets=(64, 256), max_new=16):
    """Serve the smoke prompts through the paged engine (fused sweep
    decode, one pause/resume) and the dense oracle; check agreement.
    Returns the readings to print."""
    from repro.models import build_model
    from repro.serving import (PagedServingEngine, Request, ServingConfig,
                               ServingEngine)

    clock = CompileClock()
    t0 = time.perf_counter()
    params = jax.jit(build_model(cfg).init)(jax.random.PRNGKey(SEED))
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"{cfg.name}: {n_params} parameters made from seed {SEED} in "
        f"{time.perf_counter() - t0:.2f} s")

    scfg = ServingConfig(batch_slots=slots, max_seq=max_seq,
                         page_tokens=page_tokens, prefill_buckets=buckets,
                         prefill_bucket=buckets[-1], use_paged_kernel=True,
                         sweep_decode=True)
    prompts = smoke_prompts(cfg.vocab_size, SEED)
    requests = lambda: [Request(rid=i, prompt=list(p), max_new_tokens=max_new)
                        for i, p in enumerate(prompts)]

    eng = PagedServingEngine(cfg, params, scfg)
    logits = record_paged_logits(eng)
    sweep, sweep_step = record_sweep_args(eng)
    t0 = time.perf_counter()
    for r in requests():
        eng.submit(r)
    eng.step()
    eng.step()
    # pause the 200-token request: its private pages spill to the host
    # tier; the next decode after resume restores them
    paused = next(i for i, r in enumerate(eng.slot_req)
                  if r is not None and r.rid == len(prompts) - 1)
    eng.preempt(paused)
    eng.step()
    eng.resume(paused)
    got = eng.run()
    jax.block_until_ready(eng.pool.planes)
    served_s = time.perf_counter() - t0
    compile_s = clock.seconds
    pm = eng.pool.metrics
    tokens = sum(len(v) for v in got.values())
    if tokens != len(prompts) * max_new:
        fail(f"paged engine emitted {tokens} tokens, "
             f"expected {len(prompts) * max_new}")
    if not (pm.evictions and pm.page_faults):
        fail(f"pause/resume moved no pages ({pm.evictions} evictions, "
             f"{pm.page_faults} restores)")
    if not pm.shared_hits:
        fail("the shared prefix was not served from shared pages")
    if not sweep["calls"]:
        fail("the fused sweep decode never ran")
    hlo = sweep_step.lower(*sweep["shapes"]).as_text()
    kernels = hlo.count("tpu_custom_call")

    dense = ServingEngine(cfg, params, scfg)
    want_logits = record_dense_logits(dense)
    for r in requests():
        dense.submit(r)
    want = dense.run()
    worst, parted = compare_streams(got, want, logits, want_logits)
    oracle = np.stack([l for ls in want_logits.values() for l in ls])

    # warm: the same shapes again on the same engine, nothing to compile
    t0 = time.perf_counter()
    for i, p in enumerate(smoke_prompts(cfg.vocab_size, SEED + 1)):
        eng.submit(Request(rid=100 + i, prompt=p, max_new_tokens=max_new))
    warm_tokens = sum(len(v) for v in eng.run().values())
    jax.block_until_ready(eng.pool.planes)
    warm_s = time.perf_counter() - t0

    return {
        "tpu_custom_call": kernels,
        "sweep_calls": sweep["calls"],
        "compile_s": compile_s,
        "served_s_cold": served_s,
        "tokens": tokens,
        "served_s_warm": warm_s,
        "tokens_warm": warm_tokens,
        "evictions": pm.evictions,
        "restores": pm.page_faults,
        "shared_page_hits": pm.shared_hits,
        "streams_identical": len(want) - parted,
        "streams_parted_at_near_tie": parted,
        "max_logit_gap": worst,
        "logit_std": float(oracle.std()),
        "logit_absmax": float(np.abs(oracle).max()),
    }


# -------------------------------------------------------------------------- #
# four chips: the sharded trainer
# -------------------------------------------------------------------------- #
def train_phase(argv):
    """`repro.launch.train` on a 2x2 mesh; first-step loss vs one chip."""
    from repro.launch import train as T
    from repro.models import build_model

    clock = CompileClock()
    args = T.parse_args(argv)
    cfg = T.model_config(args)
    mesh = T.mesh_of(args)
    with jax.set_mesh(mesh):
        params, opt_state = T.init_state(cfg, mesh)
    # the same parameters and step-0 batch, on one chip
    one = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    batch = jax.device_put(next(T.data_pipeline(cfg, args)), one)
    ref = float(jax.jit(build_model(cfg).loss)(
        jax.device_put(params, one), batch))
    del batch
    t0 = time.perf_counter()
    with jax.set_mesh(mesh):
        losses = T.train(cfg, args, params, opt_state)
    train_s = time.perf_counter() - t0
    log(f"losses over {len(losses)} steps: "
        + ", ".join(f"{x:.4f}" for x in losses))
    if not np.isfinite(losses).all():
        fail("non-finite loss")
    if abs(losses[0] - ref) > LOSS_TOL:
        fail(f"first-step loss {losses[0]:.5f} on the 2x2 mesh vs "
             f"{ref:.5f} on one chip: differ by more than {LOSS_TOL}")
    if not losses[-1] < losses[0]:
        fail(f"loss did not fall: {losses[0]:.4f} -> {losses[-1]:.4f}")
    return {"loss_first_step_mesh": losses[0], "loss_first_step_one_chip": ref,
            "loss_last": losses[-1], "steps": len(losses),
            "train_s_incl_compile": train_s, "compile_s": clock.seconds}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded trainer on a 2x2 mesh")
    args = ap.parse_args(argv)
    devs = require_tpu(4 if args.four_chips else 1)
    cache = Path(enable_compile_cache())
    warm = sum(1 for _ in cache.glob("*")) if cache.is_dir() else 0
    log(f"device {devs[0].device_kind} x{len(devs)}; compile cache {cache} "
        f"({warm} entries at start)")

    if args.four_chips:
        readings = train_phase([
            "--arch", ARCH, "--mesh", "2x2", "--steps", "8", "--batch", "8",
            "--seq", "256", "--log-every", "1"])
    else:
        cfg = get_config(ARCH)
        widths = (cfg.num_layers, cfg.d_model, cfg.num_heads,
                  cfg.num_kv_heads, cfg.head_dim, cfg.vocab_size)
        if widths != (28, 2048, 16, 8, 128, 151936):
            fail(f"{ARCH} is not at its published widths: {widths}")
        readings = serve_phase(cfg)
        if not readings["tpu_custom_call"]:
            fail("the sweep decode step lowers without a Mosaic kernel")
    readings["peak_bytes_in_use"] = devs[0].memory_stats()["peak_bytes_in_use"]
    for k, v in readings.items():
        log(f"smoke reading (not a benchmark result): {k} = {v}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
