"""SLO-aware scheduling: policies, preemption, chunked prefill, and the
admission-accounting regression sweep.

Three families:

  * scheduler/pool unit tests (no model): the admission-accounting bugfixes
    (true-length request_cost, never-admittable head detection, degenerate
    metrics guards), policy ordering, requeue position, deadline-aware
    eviction;
  * engine differentials (reduced zoo models): preempt -> swap-out ->
    resume mid-decode stays token-identical to the dense reference, across
    a dense arch (qwen3) and MLA (deepseek), including preemption while a
    chunked prefill is in flight;
  * chunked-prefill liveness: a long prompt never stalls short requests'
    decode ticks, and every token stream still matches the dense reference.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.serving import (
    AdmissionScheduler,
    EngineMetrics,
    KVPagePool,
    PageConfig,
    PagedEngineConfig,
    PagedServingEngine,
    Request,
    SchedulerConfig,
    mean,
    percentile,
)

pytestmark = pytest.mark.paged

_MODELS = {}


def _model(arch):
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
    for b in buckets:
        if n <= b:
            return b
    return max(max_seq, buckets[-1])


def dense_reference(model, params, prompt, max_new, bucket, *, B, max_seq):
    """Per-request greedy decode over a monolithic dense cache (same
    compiled shapes as the engine, so token streams must match exactly)."""
    prompt = prompt[-bucket:]
    toks = np.zeros((B, bucket), np.int32)
    toks[0, :len(prompt)] = prompt
    lengths = np.ones((B,), np.int32)
    lengths[0] = len(prompt)
    logits, caches = jax.jit(
        lambda p, b: model.prefill(p, b, max_seq=max_seq))(
        params, {"tokens": jnp.asarray(toks), "lengths": jnp.asarray(lengths)})
    out = [int(np.argmax(np.asarray(logits)[0]))]
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
        out.append(int(np.argmax(np.asarray(logits)[0])))
    return out


def _rand_prompt(seed, n, vocab):
    return np.random.default_rng(seed).integers(1, vocab, size=n).tolist()


# ========================================================================== #
# admission-accounting regression sweep (scheduler-only, no model)
# ========================================================================== #
def test_request_cost_charges_true_prompt_length():
    """Regression: a prompt longer than the largest prefill bucket must be
    charged at its TRUE length, not capped at the bucket — the pre-fix
    ``min(len(prompt), bucket)`` under-counted both the token budget and
    the page demand for exactly the requests served through the implicit
    max_seq top bucket."""
    sched = AdmissionScheduler(SchedulerConfig(
        prefill_buckets=(8, 16), page_tokens=8, max_seq=64))
    req = Request(rid=0, prompt=list(range(40)), max_new_tokens=10)
    assert sched.request_cost(req) == 50          # pre-fix: 16 + 10 = 26
    assert sched.request_pages(req) == 7          # ceil(50/8); pre-fix: 4
    # within-bucket requests are charged exactly as before
    short = Request(rid=1, prompt=list(range(5)), max_new_tokens=10)
    assert sched.request_cost(short) == 15


def test_pick_bucket_implicit_top_never_truncates():
    sched = AdmissionScheduler(SchedulerConfig(
        prefill_buckets=(8, 16), page_tokens=8, max_seq=64))
    assert sched.pick_bucket(5) == 8
    assert sched.pick_bucket(16) == 16
    assert sched.pick_bucket(40) == 64            # pre-fix: 16 (truncating)


def test_submit_rejects_request_that_can_never_fit():
    """Regression: prompt + max_new_tokens beyond max_seq is rejected at
    submit instead of being silently truncated into the largest bucket."""
    cfg, model, params = _model("qwen3-1.7b")
    eng = PagedServingEngine(cfg, params, PagedEngineConfig(
        batch_slots=2, max_seq=64, page_tokens=8,
        prefill_buckets=(8, 16, 32)))
    with pytest.raises(ValueError, match="never fit"):
        eng.submit(Request(rid=0, prompt=_rand_prompt(0, 60, cfg.vocab_size),
                           max_new_tokens=10))
    # a long-but-feasible prompt (above the largest bucket, within max_seq)
    # is accepted
    eng.submit(Request(rid=1, prompt=_rand_prompt(1, 40, cfg.vocab_size),
                       max_new_tokens=10))


def test_never_admittable_head_fails_instead_of_starving():
    """Regression: a head-of-queue request whose page demand exceeds the
    pool's TOTAL hot frames used to block admission forever, starving every
    feasible request behind it. It must fail visibly and let the queue
    drain."""
    sched = AdmissionScheduler(SchedulerConfig(
        prefill_buckets=(8, 16), page_tokens=8, max_active_tokens=200,
        max_seq=128))
    impossible = Request(rid=0, prompt=list(range(80)), max_new_tokens=8)
    feasible = Request(rid=1, prompt=list(range(8)), max_new_tokens=8)
    sched.submit(impossible, now=0)
    sched.submit(feasible, now=0)
    out = sched.admit([0, 1], active_tokens=0, free_hot_frames=10, now=0,
                      total_hot_frames=10)
    # pre-fix: out == [] every call, rid 1 starves behind rid 0
    assert [a.request.rid for a in out] == [1]
    assert impossible.failed and impossible.done
    assert "pages" in impossible.error
    assert sched.rejected == 1 and sched.failed == [impossible]
    assert len(sched) == 0


def test_head_over_whole_token_budget_fails_visibly():
    sched = AdmissionScheduler(SchedulerConfig(
        prefill_buckets=(8, 16), page_tokens=8, max_active_tokens=20,
        max_seq=128))
    too_big = Request(rid=0, prompt=list(range(22)), max_new_tokens=8)
    ok = Request(rid=1, prompt=list(range(4)), max_new_tokens=8)
    sched.submit(too_big, now=0)
    sched.submit(ok, now=0)
    out = sched.admit([0], active_tokens=0, free_hot_frames=50, now=0,
                      total_hot_frames=50)
    assert [a.request.rid for a in out] == [1]
    assert too_big.failed and "budget" in too_big.error


def test_temporarily_blocked_head_still_blocks_fcfs():
    """The starvation fix must NOT turn head-blocking off: a head that fits
    the pool but not the CURRENT budget keeps waiting (and keeps blocking),
    because time can make it feasible."""
    sched = AdmissionScheduler(SchedulerConfig(
        prefill_buckets=(8, 16), page_tokens=8, max_active_tokens=40,
        max_seq=128))
    head = Request(rid=0, prompt=list(range(16)), max_new_tokens=8)
    later = Request(rid=1, prompt=list(range(4)), max_new_tokens=4)
    sched.submit(head, now=0)
    sched.submit(later, now=0)
    out = sched.admit([0], active_tokens=30, free_hot_frames=50, now=0,
                      total_hot_frames=50)
    assert out == [] and len(sched) == 2 and not head.failed


def test_metrics_degenerate_inputs():
    """Regression: zero-duration / empty-sample metrics must yield clean
    zeros, not ZeroDivisionError / nan — tiny benchmark configs snapshot
    before any work has happened."""
    m = EngineMetrics()
    assert m.tokens_per_sec == 0.0
    m.tokens_emitted = 5
    m.wall_time = 0.0
    assert m.tokens_per_sec == 0.0                # pre-fix: ZeroDivisionError
    m.wall_time = 2.0
    assert m.tokens_per_sec == 2.5
    assert percentile([], 99) == 0.0              # pre-fix: np raises / nan
    assert mean([]) == 0.0                        # pre-fix: nan + warning
    assert percentile([3.0], 50) == 3.0
    assert percentile([0, 10], 50) == 5.0
    assert mean([1, 2, 3]) == 2.0


# ========================================================================== #
# policy ordering + requeue semantics (scheduler-only)
# ========================================================================== #
def test_priority_policy_orders_queue():
    sched = AdmissionScheduler(SchedulerConfig(
        prefill_buckets=(8,), page_tokens=8, policy="priority", max_seq=64))
    lo = Request(rid=0, prompt=[1], max_new_tokens=2, priority=0)
    hi = Request(rid=1, prompt=[2], max_new_tokens=2, priority=5)
    mid = Request(rid=2, prompt=[3], max_new_tokens=2, priority=3)
    for r in (lo, hi, mid):
        sched.submit(r, now=0)
    assert sched.head() is hi
    assert [r.rid for r in sched.queue] == [1, 2, 0]


def test_slo_edf_policy_orders_by_deadline():
    sched = AdmissionScheduler(SchedulerConfig(
        prefill_buckets=(8,), page_tokens=8, policy="slo-edf", max_seq=64))
    slack = Request(rid=0, prompt=[1], max_new_tokens=2, ttft_deadline=9)
    tight = Request(rid=1, prompt=[2], max_new_tokens=2, ttft_deadline=3)
    none = Request(rid=2, prompt=[3], max_new_tokens=2)       # no deadline
    for r in (slack, tight, none):
        sched.submit(r, now=0)
    assert sched.head() is tight
    assert [r.rid for r in sched.queue] == [1, 0, 2]
    # a deadline stops mattering once the first token is out: the request
    # must not preempt its way back after being served
    tight.first_token_tick = 1
    assert tight.deadline_tick() == math.inf
    assert sched.head() is slack


def test_fcfs_requeue_restores_arrival_position():
    """A preempted request readmits at its ORIGINAL arrival position, not
    the back of the queue — preemption must not double-penalize."""
    sched = AdmissionScheduler(SchedulerConfig(
        prefill_buckets=(8,), page_tokens=8, max_seq=64))
    first = Request(rid=0, prompt=[1], max_new_tokens=4)
    second = Request(rid=1, prompt=[2], max_new_tokens=4)
    sched.submit(first, now=0)
    sched.submit(second, now=0)
    out = sched.admit([0], active_tokens=0, free_hot_frames=8, now=0,
                      total_hot_frames=8)
    assert [a.request.rid for a in out] == [0]
    sched.requeue(first, now=3)
    assert [r.rid for r in sched.queue] == [0, 1]
    assert first.resuming and first.preemptions == 1
    # readmission must not record a second queue-latency sample
    n = len(sched.queue_latencies())
    sched.admit([0], active_tokens=0, free_hot_frames=8, now=5,
                total_hot_frames=8)
    assert len(sched.queue_latencies()) == n


def test_scheduler_rejects_unknown_policy():
    with pytest.raises(ValueError, match="policy"):
        SchedulerConfig(prefill_buckets=(8,), policy="sjf")


# ========================================================================== #
# deadline-aware eviction (pool-only)
# ========================================================================== #
def test_eviction_prefers_latest_deadline_then_lru():
    pool = KVPagePool(PageConfig(page_tokens=8, hot_frames=4), features=4)
    assert pool.capacity == 2
    tight = pool.alloc()
    slack = pool.alloc()
    pool.note_deadline([tight], 5.0)
    pool.note_deadline([slack], 50.0)
    pool.alloc()                     # needs a frame: someone must go cold
    assert pool.pages[slack].frame is None and slack in pool.cold
    assert pool.pages[tight].frame is not None

    # tie on deadline -> LRU (the original ordering) decides
    pool2 = KVPagePool(PageConfig(page_tokens=8, hot_frames=4), features=4)
    old = pool2.alloc()
    pool2.tick()
    young = pool2.alloc()
    pool2.note_deadline([old, young], 7.0)
    pool2.alloc()
    assert pool2.pages[old].frame is None
    assert pool2.pages[young].frame is not None


# ========================================================================== #
# engine differentials: preempt -> swap-out -> resume, chunked prefill
# ========================================================================== #
def test_priority_preemption_resumes_token_identical():
    """Two low-priority decoders fill both slots; a high-priority arrival
    preempts one (swap-out to the cold tier), runs to completion, and the
    victim resumes mid-decode — every stream matches the dense reference
    token-for-token."""
    cfg, model, params = _model("qwen3-1.7b")
    buckets = (8, 16, 32)
    eng = PagedServingEngine(cfg, params, PagedEngineConfig(
        batch_slots=2, max_seq=64, page_tokens=8, prefill_buckets=buckets,
        policy="priority"))
    specs = [(0, 9, 12, 0), (1, 7, 12, 0)]        # (rid, plen, new, prio)
    for rid, plen, new, prio in specs:
        eng.submit(Request(rid=rid, prompt=_rand_prompt(rid, plen,
                                                        cfg.vocab_size),
                           max_new_tokens=new, priority=prio))
    for _ in range(3):
        eng.step()
    specs.append((2, 5, 4, 5))
    eng.submit(Request(rid=2, prompt=_rand_prompt(2, 5, cfg.vocab_size),
                       max_new_tokens=4, priority=5))
    got = eng.run()
    assert eng.metrics.preemptions >= 1
    assert eng.metrics.readmissions >= 1
    assert eng.pool.metrics.page_faults >= 1      # resume restored from cold
    for rid, plen, new, _ in specs:
        want = dense_reference(model, params,
                               _rand_prompt(rid, plen, cfg.vocab_size), new,
                               _pick_bucket(buckets, plen), B=2, max_seq=64)
        assert got[rid] == want, f"rid {rid}: {got[rid]} != {want}"


def test_priority_preemption_mla_single_slot():
    """MLA (deepseek): preempt/resume over compressed-KV pages with a
    single slot (MoE capacity dispatch is batch-composition-sensitive, so
    the comparison keeps exactly one live request at all times)."""
    cfg, model, params = _model("deepseek-v2-236b")
    buckets = (8, 16, 32)
    eng = PagedServingEngine(cfg, params, PagedEngineConfig(
        batch_slots=1, max_seq=64, page_tokens=8, prefill_buckets=buckets,
        policy="priority"))
    low = _rand_prompt(0, 13, cfg.vocab_size)
    eng.submit(Request(rid=0, prompt=list(low), max_new_tokens=10,
                       priority=0))
    for _ in range(3):
        eng.step()
    hi = _rand_prompt(1, 5, cfg.vocab_size)
    eng.submit(Request(rid=1, prompt=list(hi), max_new_tokens=4, priority=1))
    got = eng.run()
    assert eng.metrics.preemptions == 1
    assert eng.metrics.readmissions == 1
    for rid, p, new in ((0, low, 10), (1, hi, 4)):
        want = dense_reference(model, params, p, new,
                               _pick_bucket(buckets, len(p)),
                               B=1, max_seq=64)
        assert got[rid] == want, f"rid {rid}: {got[rid]} != {want}"


def test_preemption_during_chunked_prefill():
    """Preempting a slot whose chunked prefill is still in flight must
    snapshot the chunk progress, swap out the banked pages, and resume the
    ladder exactly where it stopped — first token and the whole stream stay
    dense-reference-exact."""
    cfg, model, params = _model("qwen3-1.7b")
    buckets = (8, 16, 32)
    eng = PagedServingEngine(cfg, params, PagedEngineConfig(
        batch_slots=1, max_seq=64, page_tokens=8, prefill_buckets=buckets,
        policy="priority", prefill_chunk_tokens=8))
    long = _rand_prompt(10, 20, cfg.vocab_size)
    eng.submit(Request(rid=0, prompt=list(long), max_new_tokens=6,
                       priority=0))
    eng.step()                                    # one chunk pass banked
    assert 0 in eng._chunk and eng._chunk[0]["filled"] == 8
    hi = _rand_prompt(11, 4, cfg.vocab_size)
    eng.submit(Request(rid=1, prompt=list(hi), max_new_tokens=3, priority=2))
    got = eng.run()
    assert eng.metrics.preemptions == 1
    assert eng.metrics.readmissions == 1
    assert eng.metrics.chunk_passes == 3          # 8 + 8 + 4, no redo pass
    for rid, p, new in ((0, long, 6), (1, hi, 3)):
        want = dense_reference(model, params, p, new,
                               _pick_bucket(buckets, len(p)),
                               B=1, max_seq=64)
        assert got[rid] == want, f"rid {rid}: {got[rid]} != {want}"


def test_chunked_prefill_never_stalls_decode():
    """Acceptance: one long multi-page prompt chunk-prefills while three
    short requests stream through the other slot — every tick with the
    chunk in flight still emits decode tokens (no skipped decode tick), and
    all four streams match the dense reference."""
    cfg, model, params = _model("qwen3-1.7b")
    buckets = (8, 16, 32)
    eng = PagedServingEngine(cfg, params, PagedEngineConfig(
        batch_slots=2, max_seq=64, page_tokens=8, prefill_buckets=buckets,
        prefill_chunk_tokens=8))
    long = _rand_prompt(20, 40, cfg.vocab_size)
    eng.submit(Request(rid=0, prompt=list(long), max_new_tokens=4))
    shorts = {rid: _rand_prompt(20 + rid, 5, cfg.vocab_size)
              for rid in (1, 2, 3)}
    for rid, p in shorts.items():
        eng.submit(Request(rid=rid, prompt=list(p), max_new_tokens=6))
    pending = lambda: (len(eng.scheduler)
                       or any(r is not None for r in eng.slot_req))
    chunk_ticks = 0
    while pending():
        chunking = bool(eng._chunk)
        before = eng.metrics.tokens_emitted
        eng.step()
        if chunking:
            chunk_ticks += 1
            assert eng.metrics.tokens_emitted > before, \
                f"decode stalled at tick {eng._tick} during chunked prefill"
    got = eng.run()                               # drains completed requests
    assert eng.metrics.chunk_passes == 5          # ceil(40 / 8)
    assert chunk_ticks >= 4                       # passes after the first
    want_long = dense_reference(model, params, long, 4,
                                _pick_bucket(buckets, 40), B=2, max_seq=64)
    assert got[0] == want_long
    for rid, p in shorts.items():
        want = dense_reference(model, params, p, 6,
                               _pick_bucket(buckets, len(p)),
                               B=2, max_seq=64)
        assert got[rid] == want, f"rid {rid}: {got[rid]} != {want}"


def test_slo_edf_preempts_only_when_deadline_at_risk():
    """slo-edf with slack does nothing (no preemption churn); with a
    deadline that cannot be met by waiting it preempts, meets the SLO, and
    the victim resumes token-identically."""
    cfg, model, params = _model("qwen3-1.7b")
    buckets = (8, 16, 32)

    def build():
        return PagedServingEngine(cfg, params, PagedEngineConfig(
            batch_slots=1, max_seq=64, page_tokens=8,
            prefill_buckets=buckets, policy="slo-edf"))

    # (a) generous deadline: waiting meets it, so no preemption happens
    eng = build()
    eng.submit(Request(rid=0, prompt=_rand_prompt(30, 6, cfg.vocab_size),
                       max_new_tokens=4))
    eng.step()
    slack_req = Request(rid=1, prompt=_rand_prompt(31, 4, cfg.vocab_size),
                        max_new_tokens=2, ttft_deadline=10)
    eng.submit(slack_req)
    eng.run()
    assert eng.metrics.preemptions == 0
    assert eng.metrics.slo_violations == 0
    assert 0 <= slack_req.ttft <= 10

    # (b) tight deadline: the running request won't finish in time ->
    # preempt, serve, resume; zero violations and exact resumed stream
    eng = build()
    low = _rand_prompt(32, 6, cfg.vocab_size)
    eng.submit(Request(rid=0, prompt=list(low), max_new_tokens=20))
    eng.step()
    eng.step()
    hi = _rand_prompt(33, 4, cfg.vocab_size)
    req_hi = Request(rid=1, prompt=list(hi), max_new_tokens=2,
                     ttft_deadline=4)
    eng.submit(req_hi)
    got = eng.run()
    assert eng.metrics.preemptions == 1
    assert eng.metrics.readmissions == 1
    assert eng.metrics.slo_violations == 0
    assert 0 <= req_hi.ttft <= 4
    for rid, p, new in ((0, low, 20), (1, hi, 2)):
        want = dense_reference(model, params, p, new,
                               _pick_bucket(buckets, len(p)),
                               B=1, max_seq=64)
        assert got[rid] == want, f"rid {rid}: {got[rid]} != {want}"
