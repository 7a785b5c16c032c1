"""Page-lifecycle sanitizer, DMA-plan verifier, and PoolMetrics.validate.

Each violation class gets a deliberately broken driver — a real
``KVPagePool`` pushed through a buggy call sequence where the pool can
physically reach the bug, a synthetic trace (``TraceLog.emit``) where the
current pool implementation is already correct by construction and only a
hypothetical regression could emit the pattern. Every test asserts the
exact rule, the offending page id, and the event at which the break was
reported — provenance is the deliverable, not just a boolean.

The regression classes from PRs 1–3 are covered generically:

  * same-step evict/restore churn (PR 2's allocation-steals-fresh-restore
    bug) -> ``evict-restore-churn`` from a REAL pool driver;
  * decode scatter into the reserved zero frame (PR 1/2's page-table
    corruption class) -> ``write-to-non-hot-frame`` from a REAL pool driver;
  * shared-prefix refcount drift (PR 1's sharing bug class) ->
    ``refcount-underflow`` / ``refcount-leak``.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (
    EventKind,
    LifecycleChecker,
    LifecycleViolationError,
    PlanError,
    TraceLog,
    check_page_trace,
    format_violations,
    verify_kv_page_plan,
    verify_stream_plan,
)
from repro.core import (
    DMAEngine,
    IssueStrategy,
    PULConfig,
    TIERS,
    PES,
    plan_kv_page_stream,
)
from repro.serving import KVPagePool, PageConfig, PoolMetrics

pytestmark = [pytest.mark.paged, pytest.mark.analysis]

FEATURES = 32


def _pool(hot_frames=6, **kw) -> KVPagePool:
    """Traced pool, small enough to force real evictions (capacity =
    hot_frames - 2 reserved)."""
    kw.setdefault("page_tokens", 8)
    return KVPagePool(PageConfig(hot_frames=hot_frames, trace=True, **kw),
                      FEATURES)


def _rows(n=1):
    return jnp.ones((n, FEATURES), jnp.bfloat16)


def _only(violations, rule):
    """The single violation carrying `rule` (asserting there is exactly 1)."""
    hits = [v for v in violations if v.rule == rule]
    assert len(hits) == 1, format_violations(violations)
    return hits[0]


# ======================================================================== #
# clean traces: the real pool, driven correctly, produces zero violations
# ======================================================================== #

def test_clean_lifecycle_has_no_violations():
    pool = _pool()
    a = pool.alloc()
    b = pool.alloc(shared_key=("sys", 0))
    assert pool.lookup_shared(("sys", 0)) == b      # REF via prefix sharing
    pool.note_deadline([a, b], 40.0)
    pool.evict(a)                                   # explicit spill
    pool.ensure_hot([a, b])                         # restore a
    pool.write_page(a, _rows(8), n_valid=8)
    pool.frames_of([a, b])                          # READ events
    pool.unref(a)
    pool.unref(b)
    pool.unref(b)                                   # shared ref drops to 0
    violations = check_page_trace(pool.trace, final=True)
    assert violations == [], format_violations(violations)


def test_trace_off_by_default_means_no_trace_object():
    """Zero-overhead contract: an untraced pool never builds events."""
    pool = KVPagePool(PageConfig(page_tokens=8, hot_frames=6), FEATURES)
    assert pool.trace is None
    pid = pool.alloc()
    pool.ensure_hot([pid])
    assert pool.trace is None


# ======================================================================== #
# violation classes, one broken driver each
# ======================================================================== #

def test_refcount_underflow_detected():
    log = TraceLog()
    log.emit(0, EventKind.ALLOC, pid=7, frame=2, refcount=1)
    log.emit(1, EventKind.UNREF, pid=7, refcount=0)
    log.emit(1, EventKind.UNREF, pid=7, refcount=-1)    # the bug
    v = _only(check_page_trace(log), "refcount-underflow")
    assert v.pid == 7
    assert v.event.kind is EventKind.UNREF and v.event.seq == 2
    assert [e.kind for e in v.history] == [
        EventKind.ALLOC, EventKind.UNREF, EventKind.UNREF]


def test_unref_after_free_is_underflow():
    """Shared-prefix drift (PR 1 class): one more unref than refs."""
    pool = _pool()
    pid = pool.alloc(shared_key=("p", 1))
    pool.unref(pid)                                 # freed here
    pool.trace.emit(1, EventKind.UNREF, pid=pid, refcount=-1)  # the drift
    v = _only(check_page_trace(pool.trace), "refcount-underflow")
    assert v.pid == pid and v.event.kind is EventKind.UNREF


def test_refcount_leak_detected_at_finalize():
    pool = _pool()
    kept = pool.alloc()
    freed = pool.alloc()
    pool.unref(freed)
    violations = check_page_trace(pool.trace, final=True)
    v = _only(violations, "refcount-leak")
    assert v.pid == kept
    # without finalize the live page is not (yet) a violation
    assert check_page_trace(pool.trace) == []


def test_use_after_evict_detected_on_gather():
    pool = _pool()
    pid = pool.alloc()
    pool.evict(pid)
    with pytest.raises(AssertionError, match="cold at gather"):
        pool.frames_of([pid])           # READ event lands before the assert
    v = _only(check_page_trace(pool.trace), "use-after-evict")
    assert v.pid == pid
    assert v.event.kind is EventKind.READ
    assert [e.kind for e in v.history][-2:] == [EventKind.EVICT,
                                                EventKind.READ]


def test_write_to_zero_frame_detected():
    """PR 1/2 regression class: a decode scatter routed to the reserved
    zero frame corrupts every unallocated page-table slot."""
    pool = _pool()
    pool.alloc()
    with pytest.raises(AssertionError, match="zero frame"):
        pool.note_fused_commit(np.array([0]), np.array([0]))
    v = _only(check_page_trace(pool.trace), "write-to-non-hot-frame")
    assert v.event.kind is EventKind.WRITE_ROWS
    assert v.event.frames == (0,)
    assert "zero frame" in v.message


def test_write_to_unowned_frame_detected():
    """The pool's own assert only guards the zero frame; the sanitizer
    catches scatters into ANY frame that backs no hot page."""
    pool = _pool()
    pool.alloc()                                    # occupies one frame
    free = pool.free_frames[0]                      # backs no hot page
    pool.note_fused_commit(np.array([free]), np.array([0]))   # pool accepts!
    v = _only(check_page_trace(pool.trace), "write-to-non-hot-frame")
    assert v.event.kind is EventKind.WRITE_ROWS
    assert f"frame {free}" in v.message


def test_trash_frame_writes_are_legal():
    pool = _pool()
    pid = pool.alloc()
    frame = int(pool.frames_of([pid])[0])
    pool.note_fused_commit(np.array([1, frame]), np.array([0, 0]))
    assert check_page_trace(pool.trace) == []


def test_double_restore_detected():
    log = TraceLog()
    log.emit(0, EventKind.ALLOC, pid=3, frame=2, refcount=1)
    log.emit(1, EventKind.RESTORE, pid=3, frame=4)      # already hot
    v = _only(check_page_trace(log), "double-restore")
    assert v.pid == 3 and v.event.kind is EventKind.RESTORE
    assert v.event.seq == 1


def test_double_evict_detected():
    log = TraceLog()
    log.emit(0, EventKind.ALLOC, pid=3, frame=2, refcount=1)
    log.emit(1, EventKind.EVICT, pid=3, frame=2)
    log.emit(2, EventKind.EVICT, pid=3, frame=2)        # already cold
    v = _only(check_page_trace(log), "double-evict")
    assert v.pid == 3 and v.event.seq == 2


def test_same_step_churn_detected_from_real_pool():
    """PR 2 regression, reproduced with the REAL pool: an allocation that
    doesn't pin the current working set steals the frame of a page
    restored in the very same clock step."""
    pool = _pool(hot_frames=4)                      # capacity 2
    a = pool.alloc()
    b = pool.alloc(needed=[a])
    pool.note_deadline([a], 100.0)                  # a: most slack
    pool.note_deadline([b], 5.0)                    # b: urgent
    pool.evict(a)                                   # legitimate spill
    pool.ensure_hot([a, b])                         # restores a this step
    # BUG: alloc without needed=[a, b] — the steal victimizes a (latest
    # deadline), whose restore it just paid for, within the same step
    pool.alloc(needed=[b])
    violations = check_page_trace(pool.trace)
    v = _only(violations, "evict-restore-churn")
    assert v.pid == a
    assert v.event.kind is EventKind.EVICT and v.event.cause == "steal"
    kinds = [e.kind for e in v.history]
    assert kinds[-2:] == [EventKind.RESTORE, EventKind.EVICT]
    assert v.history[-1].clock == v.history[-2].clock   # same pool step


def test_correctly_pinned_alloc_produces_no_churn():
    pool = _pool(hot_frames=4)
    a = pool.alloc()
    b = pool.alloc(needed=[a])
    pool.evict(a)
    pool.ensure_hot([a, b])
    with pytest.raises(RuntimeError, match="hot tier exhausted"):
        pool.alloc(needed=[a, b])       # nothing stealable: fails loudly
    assert [v.rule for v in check_page_trace(pool.trace)] == []


def test_deadline_order_violation_detected():
    """A steal that spills the urgent page while a slack page sits hot."""
    log = TraceLog()
    log.emit(0, EventKind.ALLOC, pid=1, frame=2, refcount=1)
    log.emit(0, EventKind.ALLOC, pid=2, frame=3, refcount=1)
    log.emit(0, EventKind.DEADLINE, pid=1, deadline=50.0)
    log.emit(0, EventKind.DEADLINE, pid=2, deadline=10.0)
    log.emit(1, EventKind.EVICT, pid=2, frame=3, cause="steal")   # wrong!
    v = _only(check_page_trace(log), "deadline-order")
    assert v.pid == 2
    assert "page 1" in v.message and "50.0 > 10.0" in v.message


def test_deadline_order_respects_pinned_working_set():
    log = TraceLog()
    log.emit(0, EventKind.ALLOC, pid=1, frame=2, refcount=1)
    log.emit(0, EventKind.ALLOC, pid=2, frame=3, refcount=1)
    log.emit(0, EventKind.DEADLINE, pid=1, deadline=50.0)
    log.emit(0, EventKind.DEADLINE, pid=2, deadline=10.0)
    # pid 1 is pinned (in the step's working set): evicting 2 is correct
    log.emit(1, EventKind.EVICT, pid=2, frame=3, cause="steal", pinned=(1,))
    assert check_page_trace(log) == []


def test_explicit_evictions_exempt_from_victim_order():
    """Policy-driven spills (preemption) may evict any page."""
    pool = _pool()
    a = pool.alloc()
    b = pool.alloc()
    pool.note_deadline([a], 99.0)
    pool.note_deadline([b], 1.0)
    pool.evict(b)                       # urgent page, but explicit: legal
    assert check_page_trace(pool.trace) == []


def test_frame_collision_detected():
    log = TraceLog()
    log.emit(0, EventKind.ALLOC, pid=1, frame=2, refcount=1)
    log.emit(0, EventKind.ALLOC, pid=2, frame=2, refcount=1)    # same frame
    v = _only(check_page_trace(log), "frame-collision")
    assert v.pid == 2 and "already backs hot page 1" in v.message


def test_restore_into_reserved_frame_is_collision():
    log = TraceLog()
    log.emit(0, EventKind.ALLOC, pid=1, frame=2, refcount=1)
    log.emit(0, EventKind.EVICT, pid=1, frame=2)
    log.emit(1, EventKind.RESTORE, pid=1, frame=0)      # the zero frame
    v = _only(check_page_trace(log), "frame-collision")
    assert v.pid == 1 and "reserved frame 0" in v.message


def test_cross_layer_frame_claims_do_not_collide():
    """Per-layer plane identity: with the zero-copy layout a frame index
    names a DIFFERENT buffer row per layer plane, so two pages claiming
    frame 2 in layers 0 and 1 is legal — only a same-(layer, frame) claim
    is a collision. A sanitizer keyed on frame alone would false-positive
    on every fused-sweep serving trace."""
    log = TraceLog()
    log.emit(0, EventKind.ALLOC, pid=1, frame=2, refcount=1, layer=0)
    log.emit(0, EventKind.ALLOC, pid=2, frame=2, refcount=1, layer=1)
    assert check_page_trace(log) == []
    # ...while the SAME plane double-claimed is still a collision
    log.emit(1, EventKind.ALLOC, pid=3, frame=2, refcount=1, layer=1)
    v = _only(check_page_trace(log), "frame-collision")
    assert v.pid == 3 and "already backs hot page 2" in v.message


def test_layer_claim_collides_with_whole_frame_owner():
    """A layer=None claim owns the frame across every plane: a later
    layer-scoped claim of the same frame must still collide."""
    log = TraceLog()
    log.emit(0, EventKind.ALLOC, pid=1, frame=2, refcount=1)    # whole frame
    log.emit(0, EventKind.ALLOC, pid=2, frame=2, refcount=1, layer=3)
    v = _only(check_page_trace(log), "frame-collision")
    assert v.pid == 2


def test_per_layer_write_rows_against_whole_frame_owner_clean():
    """The fused sweep's commit shape: one whole-frame ALLOC, then a
    WRITE_ROWS per layer plane into that frame. Each per-layer write must
    resolve to the whole-frame owner, not flag write-to-non-hot-frame."""
    log = TraceLog()
    log.emit(0, EventKind.ALLOC, pid=1, frame=2, refcount=1)
    for layer in range(3):
        log.emit(1, EventKind.WRITE_ROWS, frames=(2,), layer=layer)
    assert check_page_trace(log) == []


def test_per_layer_write_rows_to_foreign_layer_flagged():
    """A layer-scoped write into a frame owned only by OTHER planes is a
    scatter into unbacked memory and must be flagged with its layer."""
    log = TraceLog()
    log.emit(0, EventKind.ALLOC, pid=1, frame=2, refcount=1, layer=0)
    log.emit(1, EventKind.WRITE_ROWS, frames=(2,), layer=5)
    v = _only(check_page_trace(log), "write-to-non-hot-frame")
    assert "(layer 5)" in v.message


def test_layer_scoped_release_keeps_other_planes_owned():
    """Evicting one plane's claim must not release sibling planes."""
    log = TraceLog()
    log.emit(0, EventKind.ALLOC, pid=1, frame=2, refcount=1, layer=0)
    log.emit(0, EventKind.ALLOC, pid=2, frame=2, refcount=1, layer=1)
    log.emit(1, EventKind.EVICT, pid=1, frame=2)
    log.emit(2, EventKind.WRITE_ROWS, frames=(2,), layer=1)     # still owned
    assert check_page_trace(log) == []
    log.emit(3, EventKind.WRITE_ROWS, frames=(2,), layer=0)     # released
    v = _only(check_page_trace(log), "write-to-non-hot-frame")
    assert "(layer 0)" in v.message


# ======================================================================== #
# incremental (shadow) checking
# ======================================================================== #

def test_feed_log_is_incremental():
    pool = _pool()
    checker = LifecycleChecker()
    a = pool.alloc()
    assert checker.feed_log(pool.trace) == []
    pool.evict(a)
    with pytest.raises(AssertionError):
        pool.frames_of([a])
    fresh = checker.feed_log(pool.trace)
    assert [v.rule for v in fresh] == ["use-after-evict"]
    # already-consumed events are not re-reported
    assert checker.feed_log(pool.trace) == []
    assert len(checker.violations) == 1


def test_lifecycle_violation_error_carries_provenance():
    log = TraceLog()
    log.emit(0, EventKind.UNREF, pid=9, refcount=-1)
    violations = check_page_trace(log)
    err = LifecycleViolationError(violations)
    assert err.violations == violations
    assert "refcount-underflow" in str(err) and "page=9" in str(err)


# ======================================================================== #
# PoolMetrics.validate
# ======================================================================== #

def test_pool_metrics_validate_passes_on_real_pool():
    pool = _pool(hot_frames=4)
    a = pool.alloc()
    pool.alloc(needed=[a])
    pool.evict(a)
    pool.ensure_hot([a])
    pool.metrics.validate()
    assert pool.metrics.page_faults == 1 and pool.metrics.evictions == 1


def test_pool_metrics_validate_rejects_negative_counter():
    m = PoolMetrics()
    m.page_faults = -1
    with pytest.raises(ValueError, match="page_faults is negative"):
        m.validate()


def test_pool_metrics_validate_rejects_unplanned_restore():
    """A restore without a PRELOAD descriptor means the preload plan was
    bypassed — the exact drift PUL exists to prevent."""
    pool = _pool(hot_frames=4)
    a = pool.alloc()
    pool.evict(a)
    pool.ensure_hot([a])
    pool.metrics.descriptors = [
        d for d in pool.metrics.descriptors if d.tag != a or
        d.direction.name != "PRELOAD"]
    with pytest.raises(ValueError, match="restores must be planned"):
        pool.metrics.validate()


def test_pool_metrics_validate_rejects_restore_without_spill():
    m = PoolMetrics()
    m.page_faults = 3
    m.evictions = 1
    with pytest.raises(ValueError, match="PRELOAD descriptors"):
        m.validate()


def test_pool_metrics_latency_hidden_bounds():
    m = PoolMetrics()
    m.modeled_restore_time = 1.0
    m.modeled_restore_stall = 2.0       # stall > total: impossible
    with pytest.raises(ValueError, match="out of"):
        m.validate()


# ======================================================================== #
# DMA-plan verifier
# ======================================================================== #

def _corrupt(cfg: PULConfig, **fields) -> PULConfig:
    """Bypass PULConfig.__post_init__ to build an invalid plan, the way a
    regression (not a user) would."""
    bad = dataclasses.replace(cfg)
    for k, v in fields.items():
        object.__setattr__(bad, k, v)
    return bad


def test_verify_stream_plan_accepts_both_strategies():
    for strat in IssueStrategy:
        cfg = PULConfig(distance=4, strategy=strat)
        report = verify_stream_plan(cfg, n_blocks=32, block_bytes=2048)
        assert report.distance == 4
        assert report.n_blocks == 32
        assert report.max_in_flight >= 1
        assert report.ok


def test_verify_planner_output_end_to_end():
    plan = plan_kv_page_stream(page_tokens=16, kv_features=128,
                               tier=TIERS["remote_hbm"],
                               pe=PES["tpu_v5e_vpu"], gqa_group=4)
    report = verify_kv_page_plan(plan, n_pages=64,
                                 page_bytes=16 * 128 * 2)
    assert report.distance == plan.cfg.distance
    assert report.max_in_flight <= plan.cfg.num_slots


def test_verify_rejects_zero_distance():
    cfg = _corrupt(PULConfig(distance=4), distance=0)
    with pytest.raises(PlanError, match="distance must be >= 1"):
        verify_stream_plan(cfg, n_blocks=8, block_bytes=512)


def test_verify_rejects_distance_beyond_fifo():
    cfg = _corrupt(PULConfig(distance=4, fifo_depth=64), distance=128)
    with pytest.raises(PlanError, match="FIFO"):
        verify_stream_plan(cfg, n_blocks=256, block_bytes=512)


def test_verify_rejects_starved_slot_ring():
    """Slots fewer than the warm-up window: the schedule would overwrite an
    unconsumed slot."""
    cfg = _corrupt(PULConfig(distance=8), slots=2)
    with pytest.raises(PlanError, match="slot"):
        verify_stream_plan(cfg, n_blocks=32, block_bytes=512)


def test_verify_rejects_nonsense_workload():
    cfg = PULConfig(distance=2)
    with pytest.raises(PlanError, match="n_blocks"):
        verify_stream_plan(cfg, n_blocks=-1, block_bytes=512)
    with pytest.raises(PlanError, match="block_bytes"):
        verify_stream_plan(cfg, n_blocks=8, block_bytes=0)


def test_verify_warns_on_fifo_backpressure_without_failing():
    """distance == fifo_depth under BATCH peaks at 2d in-flight; the twin
    models that as back-pressure stall, so it verifies with a warning."""
    cfg = PULConfig(distance=64, fifo_depth=64)
    report = verify_stream_plan(cfg, n_blocks=128, block_bytes=512)
    assert report.warnings and "FIFO" in report.warnings[0]


def test_run_stream_rejects_corrupted_plan_before_execution():
    eng = DMAEngine(TIERS["remote_hbm"], PES["tpu_v5e_vpu"])
    cfg = _corrupt(PULConfig(distance=4), distance=0)
    with pytest.raises(PlanError):
        eng.run_stream(cfg, n_blocks=16, block_bytes=1024,
                       compute_flops_per_block=1024.0)


def test_verify_kv_page_plan_rejects_inconsistent_prediction():
    plan = plan_kv_page_stream(page_tokens=16, kv_features=128,
                               tier=TIERS["remote_hbm"],
                               pe=PES["tpu_v5e_vpu"], gqa_group=4)
    bad = dataclasses.replace(plan, predicted_time_per_block=0.0)
    with pytest.raises(PlanError, match="predicts"):
        verify_kv_page_plan(bad, n_pages=64, page_bytes=16 * 128 * 2)


# ======================================================================== #
# engine shadow mode: the real serving engine, checked every tick
# ======================================================================== #

def test_engine_shadow_check_clean_under_preemption_pressure():
    """The full serving engine with ``shadow_check=True`` replays its own
    page trace through the sanitizer EVERY tick. Preemption forces real
    evict -> cold -> restore traffic, so the checker sees the hard paths
    (steal evictions, swap-out, resume restores) — and stays silent."""
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import PagedEngineConfig, PagedServingEngine, Request

    cfg = get_config("qwen3-1.7b").reduced()
    model = build_model(dataclasses.replace(cfg, paged_kv=True))
    params = model.init(jax.random.PRNGKey(0))
    eng = PagedServingEngine(cfg, params, PagedEngineConfig(
        batch_slots=2, max_seq=64, page_tokens=8,
        prefill_buckets=(8, 16, 32), policy="priority", shadow_check=True))
    assert eng.pool.trace is not None

    def prompt(seed, n):
        return np.random.default_rng(seed).integers(
            1, cfg.vocab_size, size=n).tolist()

    eng.submit(Request(rid=0, prompt=prompt(0, 9), max_new_tokens=12,
                       priority=0))
    eng.submit(Request(rid=1, prompt=prompt(1, 7), max_new_tokens=12,
                       priority=0))
    for _ in range(3):
        eng.step()
    eng.submit(Request(rid=2, prompt=prompt(2, 5), max_new_tokens=4,
                       priority=5))
    eng.run()                           # raises LifecycleViolationError on
                                        # any contract break, at the tick
    assert eng.metrics.preemptions >= 1
    assert eng.pool.metrics.page_faults >= 1
    assert len(eng.pool.trace) > 0
    assert eng._shadow_checker.violations == []


def test_engine_shadow_check_raises_on_injected_corruption():
    """Poisoning the trace makes the NEXT tick fail — the shadow checker
    is live, not decorative."""
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import PagedEngineConfig, PagedServingEngine, Request

    cfg = get_config("qwen3-1.7b").reduced()
    model = build_model(dataclasses.replace(cfg, paged_kv=True))
    params = model.init(jax.random.PRNGKey(0))
    eng = PagedServingEngine(cfg, params, PagedEngineConfig(
        batch_slots=1, max_seq=64, page_tokens=8, prefill_buckets=(8, 16),
        shadow_check=True))
    eng.submit(Request(
        rid=0,
        prompt=np.random.default_rng(0).integers(
            1, cfg.vocab_size, size=6).tolist(),
        max_new_tokens=8))
    eng.step()
    eng.pool.trace.emit(0, EventKind.UNREF, pid=999, refcount=-1)
    with pytest.raises(LifecycleViolationError, match="refcount-underflow"):
        eng.step()
