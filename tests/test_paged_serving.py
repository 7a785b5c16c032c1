"""Differential + invariant tests for the paged, PUL-tiered serving engine.

The core contract: the paged engine's greedy token streams are IDENTICAL to
a dense-cache reference decode (same model fns, monolithic per-slot cache),
for mixed prompt lengths, mid-stream slot refills, prefix-shared pages, and
preempt/evict/restore round-trips through the cold tier.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.serving import (
    PagedEngineConfig,
    PagedServingEngine,
    Request,
)

pytestmark = pytest.mark.paged

# dense archs only: MoE capacity dispatch mixes tokens across the batch, so
# MoE outputs are not bitwise batch-composition-invariant (documented trade)
ZOO_SUBSET = ("qwen3-1.7b", "gemma2-27b", "qwen2.5-32b")

_MODELS = {}


def _model(arch):
    """Reduced paged-mode model + params, cached across tests."""
    if arch not in _MODELS:
        cfg = get_config(arch).reduced()
        m = build_model(dataclasses.replace(cfg, paged_kv=True))
        params = m.init(jax.random.PRNGKey(0))
        _MODELS[arch] = (cfg, m, params)
    return _MODELS[arch]


def _set_idx(tree, vec):
    flat, td = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        keys = tuple(getattr(p, "key", str(p)) for p in path)
        if keys[-1] == "idx":
            leaf = jnp.broadcast_to(jnp.asarray(vec, jnp.int32), leaf.shape)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(td, out)


def _pick_bucket(buckets, n, max_seq=64):
    """Mirror of AdmissionScheduler.pick_bucket: prompts longer than the
    largest configured bucket prefill at max_seq (the implicit top bucket)
    instead of being truncated."""
    for b in buckets:
        if n <= b:
            return b
    return max(max_seq, buckets[-1])


def _dense_decode(model, params, prompt, max_new, bucket, *, B, max_seq,
                  forced=None):
    """Per-request decode over a monolithic dense cache: `max_new` tokens,
    each the argmax of its logits or, with `forced`, forced's token there.
    Returns (tokens, the logits of each)."""
    prompt = prompt[-bucket:]
    toks = np.zeros((B, bucket), np.int32)
    toks[0, :len(prompt)] = prompt
    lengths = np.ones((B,), np.int32)
    lengths[0] = len(prompt)
    logits, caches = jax.jit(
        lambda p, b: model.prefill(p, b, max_seq=max_seq))(
        params, {"tokens": jnp.asarray(toks), "lengths": jnp.asarray(lengths)})
    seen, out = [], []

    def pick(logits):
        seen.append(np.asarray(logits, np.float32)[0])
        out.append(int(np.argmax(seen[-1])) if forced is None
                   else forced[len(out)])

    pick(logits)
    pos = np.zeros((B,), np.int32)
    pos[0] = len(prompt)
    caches = _set_idx(caches, pos)
    dec = jax.jit(model.decode_step)
    for _ in range(max_new - 1):
        step = np.zeros((B, 1), np.int32)
        step[0, 0] = out[-1]
        logits, caches = dec(params, {"tokens": jnp.asarray(step),
                                      "pos0": jnp.asarray(pos)}, caches)
        pos = pos + 1
        caches = _set_idx(caches, pos)
        pick(logits)
    return out, seen


def dense_reference(model, params, prompt, max_new, bucket, *, B, max_seq):
    """Per-request greedy decode over a monolithic dense cache — the oracle.

    Uses the same compiled shapes as the engine (batch B, right-padded
    bucket prefill, per-slot idx), so row 0's math matches the engine's
    up to the sweep kernel's rounding."""
    return _dense_decode(model, params, prompt, max_new, bucket, B=B,
                         max_seq=max_seq)[0]


# the sweep's logits and the dense path's agree within this (the decode
# parity tolerance below); two tokens whose logits tie within it may come
# out in either order
TIE = 0.05


def assert_reference_stream(model, params, prompt, got, bucket, *, B,
                            max_seq):
    """`got` is the dense reference's greedy stream wherever the
    reference's logits single out one token: decoding `got` itself on the
    dense path, each of its tokens is the best there or ties with the best
    within TIE."""
    _, logits = _dense_decode(model, params, prompt, len(got), bucket, B=B,
                              max_seq=max_seq, forced=got)
    for k, ref in enumerate(logits):
        below = float(ref.max() - ref[got[k]])
        assert below <= TIE, (f"token {k} of {got}: {got[k]} is {below} "
                              "below the reference's best")


# --------------------------------------------------------------------------
# differential: paged engine == dense reference
# --------------------------------------------------------------------------
@pytest.mark.parametrize("arch", ZOO_SUBSET + (
    "deepseek-v2-236b",     # MLA: the sweep over compressed-KV planes
    "zamba2-7b",            # hybrid: the sweep plus recurrent `resident`
    "rwkv6-7b",             # no pageable KV: the plain decode of `resident`
))
def test_paged_engine_matches_dense_reference(arch):
    """Mixed prompt lengths, more requests than slots (mid-stream refills):
    greedy token streams match the dense-cache reference exactly."""
    cfg, model, params = _model(arch)
    buckets = (8, 16, 32)
    eng = PagedServingEngine(cfg, params, PagedEngineConfig(
        batch_slots=2, max_seq=64, page_tokens=8, prefill_buckets=buckets))
    rng = np.random.default_rng(42)
    prompts = [rng.integers(1, cfg.vocab_size,
                            size=int(n)).tolist()
               for n in (3, 17, 8, 29, 11)]
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=6))
    got = eng.run()
    assert eng.metrics.prefills >= 3     # slots refilled mid-stream
    # the sweep decoded wherever there is pageable KV, and `resident` holds
    # no copy of it: each pageable leaf is a placeholder
    assert (eng.metrics.sweep_pages > 0) == bool(eng.layout.features)
    for e in eng.layout.entries:
        leaf = eng.resident
        for k in e.keys:
            leaf = leaf[k]
        assert leaf.size <= e.shape[0]
    for i, p in enumerate(prompts):
        want = dense_reference(model, params, p, 6,
                               _pick_bucket(buckets, len(p)),
                               B=2, max_seq=64)
        assert got[i] == want, f"{arch} req {i}: {got[i]} != {want}"


def test_paged_kv_decode_parity_full_forward():
    """paged_kv decode (dense local caches + explicit window mask) agrees
    with the full forward pass — the ground truth, not just the ring path."""
    cfg, model, params = _model("gemma2-27b")
    assert cfg.sliding_window == 16
    B, S = 1, 40                                    # window wraps (40 > 16)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (B, S), 0,
                                cfg.vocab_size, jnp.int32)
    logits_full = model.prefill(params, {"tokens": tokens})[0]
    _, caches = model.prefill(params, {"tokens": tokens[:, :S - 1]},
                              max_seq=S)
    caches = _set_idx(caches, np.full((B,), S - 1, np.int32))
    logits_dec, _ = model.decode_step(
        params, {"tokens": tokens[:, S - 1:],
                 "pos0": jnp.full((B,), S - 1, jnp.int32)}, caches)
    np.testing.assert_allclose(np.asarray(logits_dec),
                               np.asarray(logits_full), atol=0.05)


# --------------------------------------------------------------------------
# prefix sharing
# --------------------------------------------------------------------------
def test_prefix_sharing_reuses_physical_pages_and_matches():
    cfg, model, params = _model("qwen3-1.7b")
    base = list(range(5, 21))                        # 2 full pages of 8
    p1, p2 = base + [33, 34], base + [77]
    eng = PagedServingEngine(cfg, params, PagedEngineConfig(
        batch_slots=2, max_seq=64, page_tokens=8, prefill_buckets=(32,)))
    eng.submit(Request(rid=0, prompt=p1, max_new_tokens=5))
    eng.submit(Request(rid=1, prompt=p2, max_new_tokens=5))
    reqs = {r.rid: r for r in eng.scheduler.queue}
    eng.step()                                       # both admitted together
    s0 = next(i for i, r in enumerate(eng.slot_req) if r and r.rid == 0)
    s1 = next(i for i, r in enumerate(eng.slot_req) if r and r.rid == 1)
    assert eng.slot_pages[s0][:2] == eng.slot_pages[s1][:2]   # same pages
    assert eng.slot_pages[s0][2:] != eng.slot_pages[s1][2:]   # private tails
    eng.run()
    assert eng.pool.metrics.shared_hits == 2
    for rid, p in ((0, p1), (1, p2)):
        want = dense_reference(model, params, p, 5, 32, B=2, max_seq=64)
        assert reqs[rid].out_tokens == want

    # sharing off: same outputs, no shared pages
    eng2 = PagedServingEngine(cfg, params, PagedEngineConfig(
        batch_slots=2, max_seq=64, page_tokens=8, prefill_buckets=(32,),
        share_prefix_pages=False))
    eng2.submit(Request(rid=0, prompt=p1, max_new_tokens=5))
    eng2.submit(Request(rid=1, prompt=p2, max_new_tokens=5))
    out2 = eng2.run()
    assert eng2.pool.metrics.shared_hits == 0
    assert out2[0] == reqs[0].out_tokens and out2[1] == reqs[1].out_tokens


# --------------------------------------------------------------------------
# tiering: preempt -> evict -> cold -> restore, bit-identical
# --------------------------------------------------------------------------
def test_preempt_evict_restore_roundtrip_is_exact():
    cfg, model, params = _model("qwen3-1.7b")
    rng = np.random.default_rng(3)
    pA = rng.integers(1, cfg.vocab_size, size=20).tolist()
    pB = rng.integers(1, cfg.vocab_size, size=12).tolist()
    want = dense_reference(model, params, pA, 10, 32, B=2, max_seq=64)

    eng = PagedServingEngine(cfg, params, PagedEngineConfig(
        batch_slots=2, max_seq=64, page_tokens=8, prefill_buckets=(32,)))
    eng.submit(Request(rid=0, prompt=pA, max_new_tokens=10))
    eng.submit(Request(rid=1, prompt=pB, max_new_tokens=10))
    reqs = {r.rid: r for r in eng.scheduler.queue}
    for _ in range(4):
        eng.step()
    slot = next(i for i, r in enumerate(eng.slot_req) if r and r.rid == 0)
    eng.preempt(slot)                   # A's pages spill to the cold tier
    assert eng.pool.metrics.evictions > 0
    assert len(eng.pool.cold) > 0
    for _ in range(3):
        eng.step()                      # B keeps decoding with A swapped out
    eng.resume(slot)
    eng.run()
    assert eng.pool.metrics.page_faults >= eng.pool.metrics.evictions
    assert reqs[0].out_tokens == want   # restore was bit-exact
    assert len(eng.pool.cold) == 0      # everything drained


def test_pool_releases_everything_after_run():
    cfg, model, params = _model("qwen3-1.7b")
    eng = PagedServingEngine(cfg, params, PagedEngineConfig(
        batch_slots=2, max_seq=64, page_tokens=8, prefill_buckets=(16,)))
    rng = np.random.default_rng(9)
    for i in range(5):
        eng.submit(Request(
            rid=i, prompt=rng.integers(1, cfg.vocab_size, size=9).tolist(),
            max_new_tokens=4))
    eng.run()
    assert eng.pool.hot_in_use() == 0
    assert not eng.pool.pages            # all refcounts returned to zero
    assert not eng.pool.cold
    assert not eng.pool.prefix_index
    assert len(eng.pool.free_frames) == eng.pool.capacity
    assert eng.pool.metrics.pages_allocated > 0


# --------------------------------------------------------------------------
# scheduling: token budget + queue latency
# --------------------------------------------------------------------------
def test_token_budget_serializes_admission_and_records_latency():
    cfg, model, params = _model("qwen3-1.7b")
    # budget fits ONE request (16 + 6 = 22 <= 24 < 44), so the 4 slots are
    # throttled down to sequential admission
    eng = PagedServingEngine(cfg, params, PagedEngineConfig(
        batch_slots=4, max_seq=64, page_tokens=8, prefill_buckets=(16,),
        max_active_tokens=24))
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, cfg.vocab_size, size=10).tolist()
               for _ in range(3)]
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=6))
    got = eng.run()
    lats = eng.scheduler.queue_latencies()
    assert len(lats) == 3
    assert lats[0] == 0 and lats[1] > 0 and lats[2] > lats[1]
    for i, p in enumerate(prompts):
        want = dense_reference(model, params, p, 6, 16, B=4, max_seq=64)
        assert got[i] == want

    with pytest.raises(ValueError):     # oversized requests are rejected
        eng.submit(Request(rid=99, prompt=list(range(1, 12)),
                           max_new_tokens=30))


def test_metrics_hook_sees_page_faults_and_throughput():
    cfg, model, params = _model("qwen3-1.7b")
    snaps = []
    eng = PagedServingEngine(cfg, params, PagedEngineConfig(
        batch_slots=2, max_seq=32, page_tokens=8, prefill_buckets=(16,)),
        metrics_hook=snaps.append)
    eng.submit(Request(rid=0, prompt=[3, 1, 4, 1, 5], max_new_tokens=4))
    eng.run()
    assert snaps
    for key in ("tokens_per_sec", "page_faults", "page_faults_step",
                "shared_page_hits", "mean_queue_latency", "mean_queue_wait_s",
                "preload_distance", "modeled_restore_latency_hidden"):
        assert key in snaps[-1]
    assert snaps[-1]["tokens_emitted"] == 4


def test_preempt_resume_preserves_recurrent_state_hybrid():
    """Hybrid (SSM) archs: a paused slot's recurrent state must not be
    advanced by the dummy tokens it rides through the batched decode with —
    preempt/resume must yield the same stream as an undisturbed run."""
    cfg = get_config("zamba2-7b").reduced()
    model = build_model(dataclasses.replace(cfg, paged_kv=True))
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(21)
    pA = rng.integers(1, cfg.vocab_size, size=10).tolist()
    pB = rng.integers(1, cfg.vocab_size, size=7).tolist()

    def serve(preempt: bool):
        eng = PagedServingEngine(cfg, params, PagedEngineConfig(
            batch_slots=2, max_seq=32, page_tokens=8, prefill_buckets=(16,)))
        eng.submit(Request(rid=0, prompt=list(pA), max_new_tokens=8))
        eng.submit(Request(rid=1, prompt=list(pB), max_new_tokens=8))
        for _ in range(3):
            eng.step()
        if preempt:
            slot = next(i for i, r in enumerate(eng.slot_req)
                        if r and r.rid == 0)
            eng.preempt(slot)
            for _ in range(2):
                eng.step()       # B decodes while A's state must stay frozen
            eng.resume(slot)
        return eng.run()

    assert serve(preempt=True)[0] == serve(preempt=False)[0]


def test_sampling_uses_model_distribution():
    """greedy=False draws from softmax(logits): reproducible for a fixed
    seed, seed-dependent, and concentrated on high-probability tokens
    (sanity: a tiny overfit-free model still has non-uniform logits)."""
    cfg, model, params = _model("qwen3-1.7b")
    def serve(seed):
        eng = PagedServingEngine(cfg, params, PagedEngineConfig(
            batch_slots=2, max_seq=32, page_tokens=8, prefill_buckets=(16,),
            greedy=False, sample_seed=seed))
        eng.submit(Request(rid=0, prompt=[3, 1, 4, 1, 5], max_new_tokens=8))
        eng.submit(Request(rid=1, prompt=[2, 7, 1, 8], max_new_tokens=8))
        return eng.run()
    a, b, c = serve(0), serve(0), serve(1)
    assert a == b                        # deterministic per seed
    assert a != c                        # seed actually matters
    assert a[0] != a[1]                  # slots don't share one draw


# --------------------------------------------------------------------------
# decode over the page planes: partial tail pages, window wrap, MLA
# --------------------------------------------------------------------------
@pytest.mark.parametrize("arch,sizes", [
    ("qwen3-1.7b", (3, 17, 8)),          # GQA + qk_norm
    ("gemma2-27b", (3, 17, 40)),         # sliding window + softcap
    ("qwen2.5-32b", (3, 17, 8)),         # GQA + qkv bias
])
def test_paged_kernel_decode_matches_dense_reference(arch, sizes):
    """Greedy token streams of the sweep over page planes are identical to
    the dense-cache reference: mixed prompt lengths, mid-stream slot
    refills, partial tail pages, and window wrap (gemma2's 40 > window
    16)."""
    cfg, model, params = _model(arch)
    buckets = (8, 16, 32)
    eng = PagedServingEngine(cfg, params, PagedEngineConfig(
        batch_slots=2, max_seq=64, page_tokens=8, prefill_buckets=buckets))
    rng = np.random.default_rng(42)
    prompts = [rng.integers(1, cfg.vocab_size, size=int(n)).tolist()
               for n in sizes]
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=6))
    got = eng.run()
    assert eng.metrics.prefills >= 2     # slots refilled mid-stream
    for i, p in enumerate(prompts):
        want = dense_reference(model, params, p, 6,
                               _pick_bucket(buckets, len(p)),
                               B=2, max_seq=64)
        assert got[i] == want, f"{arch} req {i}: {got[i]} != {want}"


def test_paged_kernel_mla_matches_dense_reference():
    """MLA (deepseek): absorbed decode straight over compressed-KV pages is
    token-identical to the dense reference. Single-request runs keep the
    batch composition identical (MoE capacity dispatch is composition-
    sensitive); lengths cover multi-page, sub-page, and partial tails."""
    cfg = get_config("deepseek-v2-236b").reduced()
    model = build_model(dataclasses.replace(cfg, paged_kv=True))
    params = model.init(jax.random.PRNGKey(0))
    buckets = (8, 16, 32)
    for seed, plen in ((2, 19), (3, 5), (7, 13)):
        p = np.random.default_rng(seed).integers(
            1, cfg.vocab_size, size=plen).tolist()
        eng = PagedServingEngine(cfg, params, PagedEngineConfig(
            batch_slots=2, max_seq=64, page_tokens=8,
            prefill_buckets=buckets))
        eng.submit(Request(rid=0, prompt=list(p), max_new_tokens=10))
        got = eng.run()[0]
        want = dense_reference(model, params, p, 10,
                               _pick_bucket(buckets, plen), B=2, max_seq=64)
        assert got == want, f"len {plen}: {got} != {want}"


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "deepseek-v2-236b"])
def test_empty_prompt_admission(arch):
    """Empty-prompt requests admit cleanly (no pages at prefill, tail page
    on the first decode step) and match the dense reference, on the GQA and
    the MLA sweep."""
    cfg, model, params = _model(arch)
    eng = PagedServingEngine(cfg, params, PagedEngineConfig(
        batch_slots=2, max_seq=32, page_tokens=8, prefill_buckets=(8,)))
    eng.submit(Request(rid=0, prompt=[], max_new_tokens=5))
    got = eng.run()[0]
    assert len(got) == 5
    want = dense_reference(model, params, [], 5, 8, B=2, max_seq=32)
    assert got == want


# --------------------------------------------------------------------------
# prefix-cache COMPUTE reuse: fully-shared prompts skip prefill entirely
# --------------------------------------------------------------------------
@pytest.mark.parametrize("arch", ["qwen3-1.7b", "deepseek-v2-236b"])
def test_fully_shared_prompt_skips_prefill_compute(arch):
    """A request whose whole (page-aligned) prompt is already resident as
    shared pages admits with ZERO prefill compute: the prefill counter is
    unchanged, the shared pages are ref'd, and the stream is identical to
    an undisturbed solo run — on the GQA and the MLA sweep."""
    cfg, model, params = _model(arch)
    prompt = list(range(5, 21))                     # 16 tokens = 2 full pages
    ecfg = dict(batch_slots=2, max_seq=64, page_tokens=8,
                prefill_buckets=(16,))
    eng = PagedServingEngine(cfg, params, PagedEngineConfig(**ecfg))
    eng.submit(Request(rid=0, prompt=list(prompt), max_new_tokens=12))
    for _ in range(3):
        eng.step()
    assert eng.metrics.prefills == 1
    eng.submit(Request(rid=1, prompt=list(prompt), max_new_tokens=12))
    eng.step()                                      # admitted from shared pages
    assert eng.metrics.prefills == 1                # ZERO additional compute
    assert eng.metrics.prefill_skips == 1
    assert eng.pool.metrics.shared_hits == 2        # both prompt pages reused
    out = eng.run()

    solo = PagedServingEngine(cfg, params, PagedEngineConfig(**ecfg))
    solo.submit(Request(rid=0, prompt=list(prompt), max_new_tokens=12))
    want = solo.run()[0]
    assert out[0] == want and out[1] == want

    # non-page-aligned prompts never skip (the partial tail needs compute)
    eng2 = PagedServingEngine(cfg, params, PagedEngineConfig(
        batch_slots=2, max_seq=64, page_tokens=8, prefill_buckets=(16,)))
    eng2.submit(Request(rid=0, prompt=list(prompt[:-3]), max_new_tokens=12))
    for _ in range(3):
        eng2.step()
    eng2.submit(Request(rid=1, prompt=list(prompt[:-3]), max_new_tokens=4))
    eng2.run()
    assert eng2.metrics.prefill_skips == 0
    assert eng2.metrics.prefills == 2


def test_recurrent_archs_never_skip_prefill():
    """Hybrid (SSM) archs carry non-pageable state that pages cannot
    rebuild: identical prompts must still prefill."""
    cfg = get_config("zamba2-7b").reduced()
    model = build_model(dataclasses.replace(cfg, paged_kv=True))
    params = model.init(jax.random.PRNGKey(0))
    prompt = list(range(1, 17))                     # page-aligned on purpose
    eng = PagedServingEngine(cfg, params, PagedEngineConfig(
        batch_slots=2, max_seq=64, page_tokens=8, prefill_buckets=(16,)))
    eng.submit(Request(rid=0, prompt=list(prompt), max_new_tokens=10))
    for _ in range(3):
        eng.step()
    eng.submit(Request(rid=1, prompt=list(prompt), max_new_tokens=4))
    out = eng.run()
    assert eng.metrics.prefill_skips == 0
    assert eng.metrics.prefills == 2
    assert out[0][:4] == out[1][:4]                 # same prompt, same start


# --------------------------------------------------------------------------
# admission-accounting regressions
# --------------------------------------------------------------------------
def test_token_budget_accounting_matches_scheduler_cost():
    """Regression: the engine's per-tick active-token charge must be the
    scheduler's request_cost (min(prompt, bucket) + max_new), not
    bucket + max_new — otherwise a short prompt in a large bucket inflates
    the budget between submit-time checks and per-tick accounting, blocking
    admissions the scheduler already proved feasible."""
    cfg, model, params = _model("qwen3-1.7b")
    # cost per request = min(3, 16) + 6 = 9; two fit in budget 18. The
    # drifted charge (16 + 6 = 22) would block the second request forever.
    eng = PagedServingEngine(cfg, params, PagedEngineConfig(
        batch_slots=2, max_seq=64, page_tokens=8, prefill_buckets=(16,),
        max_active_tokens=18))
    r0 = Request(rid=0, prompt=[3, 1, 4], max_new_tokens=6)
    r1 = Request(rid=1, prompt=[1, 5, 9], max_new_tokens=6)
    eng.submit(r0)
    eng.step()
    assert eng._active_tokens() == 9
    eng.submit(r1)
    eng.step()
    assert r1.admit_tick == 1           # admitted immediately, not serialized
    assert eng._active_tokens() == 18
    eng.run()
    assert len(r0.out_tokens) == 6 and len(r1.out_tokens) == 6


def test_dense_run_returns_preadmitted_requests():
    """Regression: ServingEngine.run() must return requests that were
    already admitted into slots before run() was called (the old queue-only
    snapshot silently dropped their outputs)."""
    cfg, model, params = _model("qwen3-1.7b")
    from repro.serving import EngineConfig, ServingEngine
    eng = ServingEngine(cfg, params, EngineConfig(
        batch_slots=2, max_seq=64, prefill_bucket=16))
    eng.submit(Request(rid=0, prompt=[3, 1, 4], max_new_tokens=4))
    eng.step()                           # rid 0 leaves the queue for a slot
    eng.submit(Request(rid=1, prompt=[2, 7], max_new_tokens=4))
    done = eng.run()
    assert set(done) == {0, 1}
    assert len(done[0]) == 4 and len(done[1]) == 4


def test_pool_alloc_preserves_step_working_set():
    """Regression: alloc() must not evict pages the current step still
    needs (stale LRU order made the working set the victim, forcing a
    same-step fault/restore round-trip that polluted the latency-hidden
    metric)."""
    from repro.serving.kv_pages import KVPagePool, PageConfig
    pool = KVPagePool(PageConfig(page_tokens=8, hot_frames=5), features=4)
    assert pool.capacity == 3
    p1, p2, p3 = pool.alloc(), pool.alloc(), pool.alloc()
    pool.ensure_hot([p2, p3])            # p1 becomes the strict LRU entry
    pool.alloc(needed=(p1,))             # full pool: someone must spill...
    assert pool.pages[p1].frame is not None   # ...but never the working set
    assert pool.metrics.evictions == 1
    assert pool.metrics.page_faults == 0      # no same-step churn


def test_write_rows_validates_before_scatter():
    """Regression: the zero-frame invariant is checked BEFORE the row
    write — a commit routed to the reserved all-zeros frame stops the step
    before the sweep launches, so the frame stays all-zeros."""
    from repro.serving.kv_pages import ZERO_FRAME
    cfg, _, params = _model("qwen3-1.7b")
    eng = PagedServingEngine(cfg, params, PagedEngineConfig(
        batch_slots=2, max_seq=32, page_tokens=8, prefill_buckets=(16,)))
    launches, sweep = [], eng._sweep_decode

    def counted(*args):
        launches.append(1)
        return sweep(*args)

    eng._sweep_decode = counted
    eng.submit(Request(rid=0, prompt=[3, 1, 4, 1, 5], max_new_tokens=4))
    eng.step()                           # prefill, then one sweep
    assert len(launches) == 1
    (pid,) = eng.slot_pages[0]           # the tail page of the next commit
    eng.pool.pages[pid].frame = ZERO_FRAME
    with pytest.raises(AssertionError, match="zero frame"):
        eng.step()
    assert len(launches) == 1            # no launch behind the bad commit
    for plane in eng.pool.planes.values():
        assert not np.asarray(plane[:, ZERO_FRAME]).any()   # still zeros


def test_sampling_differential_across_engines():
    """greedy=False with one shared sample_seed: the dense and paged
    engines draw identical streams (prompt length == bucket keeps the two
    prefill paddings — left vs right — bitwise equivalent)."""
    cfg, model, params = _model("qwen3-1.7b")
    from repro.serving import EngineConfig, ServingEngine
    prompt = list(range(3, 19))                    # 16 tokens == the bucket
    outs = []
    for seed in (0, 7):
        dense = ServingEngine(cfg, params, EngineConfig(
            batch_slots=2, max_seq=64, prefill_bucket=16, greedy=False,
            sample_seed=seed))
        paged = PagedServingEngine(cfg, params, PagedEngineConfig(
            batch_slots=2, max_seq=64, page_tokens=8, prefill_buckets=(16,),
            greedy=False, sample_seed=seed))
        for eng in (dense, paged):
            eng.submit(Request(rid=0, prompt=list(prompt), max_new_tokens=8))
        a, b = dense.run()[0], paged.run()[0]
        assert a == b, f"seed {seed}: {a} != {b}"
        outs.append(a)
    assert outs[0] != outs[1]                      # seed actually matters


# --------------------------------------------------------------------------
# one decode step in flight: greedy tokens stay on the device
# --------------------------------------------------------------------------
def _inflight_case(case, vocab):
    """(engine knobs, [(rid, prompt, max_new, priority, submit tick)], a
    check of the engine after the run) for one in-flight scenario."""
    draw = lambda seed, n: np.random.default_rng(seed).integers(
        1, vocab, size=n).tolist()
    if case == "admissions":
        # rid 1 shares rid 0's three prompt pages; rid 2 is rid 0's prompt
        # again, admitted mid-run with no prefill at all; rid 3 arrives late
        shared = draw(60, 24)
        reqs = [(0, shared, 4, 0, 0), (1, shared + draw(61, 5), 10, 0, 0),
                (2, list(shared), 5, 0, 2), (3, draw(62, 3), 6, 0, 4)]

        def check(eng):
            # pages cannot rebuild recurrent state: such a model never
            # skips a prefill
            assert (eng.metrics.prefill_skips >= 1) != eng._has_recurrent
            assert eng.pool.metrics.shared_hits > 0
        return dict(max_seq=64), reqs, check
    if case == "max_seq":
        # rid 0 fills its slot to max_seq; rid 1 stops at max_new_tokens
        reqs = [(0, draw(63, 10), 22, 0, 0), (1, draw(64, 5), 4, 0, 0)]

        def check(eng):
            assert eng.metrics.decode_steps == 21     # no step past the end
            assert eng.metrics.tokens_emitted == 26
        return dict(max_seq=32), reqs, check
    if case == "preempt":
        reqs = [(0, draw(65, 9), 12, 0, 0), (1, draw(66, 7), 12, 0, 0),
                (2, draw(67, 5), 4, 5, 3)]

        def check(eng):
            assert eng.metrics.preemptions >= 1
            assert eng.metrics.readmissions >= 1
            assert eng.pool.metrics.page_faults >= 1
            assert eng.metrics.settles >= 2     # the preemption, the drain
        return dict(max_seq=64, policy="priority"), reqs, check
    assert case == "chunked"
    reqs = [(0, draw(68, 40), 4, 0, 0)] + [
        (rid, draw(68 + rid, 5), 6, 0, 0) for rid in (1, 2, 3)]

    def check(eng):
        assert eng.metrics.chunk_passes == 5          # ceil(40 / 8)
    return dict(max_seq=64, prefill_chunk_tokens=8), reqs, check


# the sweep alone, and the sweep beside recurrent state in `resident` (the
# MLA one comes with MoE, whose capacity dispatch makes a stream depend on
# its batch-mates)
@pytest.mark.parametrize("arch", ["qwen3-1.7b", "zamba2-7b"])
@pytest.mark.parametrize("case", ["admissions", "max_seq", "preempt",
                                  "chunked"])
def test_inflight_greedy_matches_dense_reference(case, arch):
    """With one decode step in flight (the host dispatches step t+1 before
    it reads step t's tokens), greedy streams equal the dense reference
    token for token: admissions mid-run (shared-prefix and fully shared ones
    included), finishes at max_new_tokens and at max_seq, a policy
    preemption through the cold tier and back, and chunked prefill."""
    cfg, model, params = _model(arch)
    buckets = (8, 16, 32)
    knobs, reqs, check = _inflight_case(case, cfg.vocab_size)
    eng = PagedServingEngine(cfg, params, PagedEngineConfig(
        batch_slots=2, page_tokens=8, prefill_buckets=buckets, **knobs))
    tick = 0
    for when in sorted({r[4] for r in reqs}):
        while tick < when:
            eng.step()
            tick += 1
        for rid, prompt, new, prio, at in reqs:
            if at == when:
                eng.submit(Request(rid=rid, prompt=list(prompt),
                                   max_new_tokens=new, priority=prio))
    got = eng.run()
    check(eng)
    assert eng.metrics.inflight_steps > 0
    assert eng.metrics.decode_steps == (eng.metrics.inflight_steps
                                        + eng.metrics.settles)
    assert all(r is None for r in eng.slot_req) and not eng.pool.pages
    for rid, prompt, new, _, _ in reqs:
        want = dense_reference(
            model, params, prompt, new,
            _pick_bucket(buckets, len(prompt), knobs["max_seq"]),
            B=2, max_seq=knobs["max_seq"])
        assert got[rid] == want, f"{case} rid {rid}: {got[rid]} != {want}"


def test_inflight_counters_and_settles():
    """`inflight_steps` counts the decode steps dispatched over the
    previous step's tokens, `settles` the steps read with none dispatched
    behind them; every step is one or the other once the engine is idle.
    A preemption, a pause or a resume that meets a step in flight settles
    it, one settle each."""
    cfg, _, params = _model("qwen3-1.7b")
    draw = lambda seed, n: np.random.default_rng(seed).integers(
        1, cfg.vocab_size, size=n).tolist()
    eng = PagedServingEngine(cfg, params, PagedEngineConfig(
        batch_slots=2, max_seq=64, page_tokens=8, prefill_buckets=(16,)))
    for rid in range(3):
        eng.submit(Request(rid=rid, prompt=draw(70 + rid, 9),
                           max_new_tokens=5))
    eng.run()
    m = eng.metrics
    # rids 0 and 1 finish on one step and rid 2 waits for their slots, so
    # the loop runs dry twice: there, and at the end
    assert m.settles == 2 and m.decode_steps == 8
    assert m.inflight_steps == m.decode_steps - m.settles
    snap = eng.snapshot()
    assert (snap["inflight_steps"], snap["settles"],
            snap["decode_steps"]) == (m.inflight_steps, m.settles,
                                      m.decode_steps)

    eng = PagedServingEngine(cfg, params, PagedEngineConfig(
        batch_slots=2, max_seq=64, page_tokens=8, prefill_buckets=(16,),
        policy="priority"))
    for rid in range(2):
        eng.submit(Request(rid=rid, prompt=draw(80 + rid, 9),
                           max_new_tokens=12))
    for _ in range(3):
        eng.step()
    assert eng.metrics.settles == 0 and eng._inflight is not None
    eng.submit(Request(rid=2, prompt=draw(82, 5), max_new_tokens=3,
                       priority=5))
    eng.step()                                # preempts: one settle
    assert eng.metrics.preemptions == 1 and eng.metrics.settles == 1
    eng.step()
    slot = next(i for i, r in enumerate(eng.slot_req) if r and r.rid < 2)
    eng.preempt(slot)                         # pause: one settle
    assert eng.metrics.settles == 2 and eng._inflight is None
    eng.step()
    assert eng._inflight is not None
    eng.resume(slot)                          # resume: one settle
    assert eng.metrics.settles == 3
    eng.preempt(slot)                         # nothing in flight: none
    eng.resume(slot)
    assert eng.metrics.settles == 3
    eng.run()
    m = eng.metrics
    assert m.inflight_steps == m.decode_steps - m.settles


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "rwkv6-7b"])
def test_greedy_decode_brings_no_logits_to_the_host(monkeypatch, arch):
    """On the greedy path every array the engine brings to the host during
    decode is a (B, 1) int32 token array; no (B, V) logits ever leave the
    device, whether the step is the sweep or (no pageable KV) the plain
    decode. The sampling path, which draws on the host, copies them."""
    from repro.serving import engine as engine_mod
    cfg, _, params = _model(arch)
    fetched = []

    class Recorder:
        """The engine module's numpy, noting each device array it reads."""

        def __getattr__(self, name):
            return getattr(np, name)

        def asarray(self, a, *args, **kwargs):
            if isinstance(a, jax.Array):
                fetched.append((a.shape, a.dtype))
            return np.asarray(a, *args, **kwargs)

    monkeypatch.setattr(engine_mod, "np", Recorder())
    for greedy in (True, False):
        fetched.clear()
        eng = PagedServingEngine(cfg, params, PagedEngineConfig(
            batch_slots=2, max_seq=32, page_tokens=8, prefill_buckets=(16,),
            greedy=greedy))
        eng.submit(Request(rid=0, prompt=[3, 1, 4, 1, 5], max_new_tokens=6))
        eng.submit(Request(rid=1, prompt=[2, 7, 1, 8], max_new_tokens=4))
        eng.run()
        assert eng.metrics.decode_steps == 5
        full = [f for f in fetched if f[0] == (2, cfg.vocab_size)]
        if greedy:
            assert not full
            assert fetched.count(((2, 1), jnp.int32)) == 5
        else:
            assert len(full) == 5


# the streams the sweep drew before greedy decode kept a step in flight
_SAMPLED = {
    "qwen3-1.7b": {0: [21, 205, 23, 121, 187, 100, 108, 188],
                   1: [56, 147, 110, 38, 27, 130, 149, 245],
                   2: [73, 168, 179, 78, 0]},
    "deepseek-v2-236b": {0: [20, 205, 23, 122, 185, 99, 109, 187],
                         1: [60, 149, 110, 41, 29, 131, 149, 244],
                         2: [70, 165, 176, 74, 0]},
}


@pytest.mark.parametrize("arch", sorted(_SAMPLED))
def test_sampled_decode_draws_as_before(arch):
    """greedy=False keeps the serial loop: a fixed seed draws the streams
    the engine drew before greedy decode kept a step in flight."""
    cfg, _, params = _model(arch)
    eng = PagedServingEngine(cfg, params, PagedEngineConfig(
        batch_slots=2, max_seq=32, page_tokens=8, prefill_buckets=(16,),
        greedy=False, sample_seed=3))
    eng.submit(Request(rid=0, prompt=[3, 1, 4, 1, 5], max_new_tokens=8))
    eng.submit(Request(rid=1, prompt=[2, 7, 1, 8], max_new_tokens=8))
    eng.step()
    eng.submit(Request(rid=2, prompt=[9, 9, 8], max_new_tokens=5))
    assert eng.run() == _SAMPLED[arch]
    assert eng.metrics.inflight_steps == eng.metrics.settles == 0
