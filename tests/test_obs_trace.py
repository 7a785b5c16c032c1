"""Unified trace/metrics layer: round-trips, exporters, and the FIFO diff.

Six families:

  * tracer unit tests: Chrome export schema, the NullTracer's zero-cost
    contract, JSON-safety of args (tuples, non-finite floats), spans paired
    back onto ``perf_counter`` from a bounded buffer;
  * schema-validator fixtures: one hand-built broken trace per rule the
    validator enforces (unbalanced B/E, unknown phase, async e-before-b,
    negative X duration, counter without numerics, non-finite ts);
  * engine round-trip (reduced zoo model): a traced serving run with a
    preemption exports a valid trace; the page-lifecycle bridge
    reconstructs the pool's own ``TraceLog`` EXACTLY and replays clean
    through the sanitizer; tracing OFF records nothing and leaves the
    run's deterministic outputs bit-identical; a crashing ``metrics_hook``
    warns once, is disabled, and never kills the tick loop;
  * cache economics + registry: bytes-per-token arithmetic against
    hand-built PoolMetrics, Prometheus/JSON exporter shape, the engine's
    own ``metrics_registry()``;
  * DMA FIFO diff: the executed occupancy of a traced ``run_stream``
    matches the plan verifier's symbolic schedule (clean and
    back-pressure cases), and a corrupted occupancy log is caught;
  * spans in the profile: a span shows in a ``jax.profiler`` session; the
    default tracer records nothing without one, and under one every engine
    and pool span is in the profile on one offset from its
    ``perf_counter`` record, nested and closed; queue wait in seconds.
"""
import dataclasses
import math
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.analysis import LifecycleChecker, diff_fifo_occupancy
from repro.configs import get_config
from repro.core import (
    DMAEngine,
    KVPageWorkload,
    PES,
    PULConfig,
    TIERS,
    run_kv_page_workload,
)
from repro.models import build_model
from repro.obs import (
    DEFAULT_TRACER,
    MetricsRegistry,
    NULL_TRACER,
    Tracer,
    profiler_recording,
    cache_economics,
    economics_into_registry,
    page_events_from_chrome,
    validate_chrome_trace,
)
from repro.serving import PagedEngineConfig, PagedServingEngine, Request
from repro.serving.kv_pages import PoolMetrics
from repro.serving.scheduler import AdmissionScheduler, SchedulerConfig

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))
import trace_diff  # noqa: E402  (tools/ is not a package)

pytestmark = pytest.mark.obs


# ======================================================================== #
# tracer unit tests
# ======================================================================== #

def test_export_schema_and_phase_shapes(tmp_path):
    t = Tracer()
    t.set_tick(3)
    with t.span("engine", "tick"):
        t.counter("gauges", "live_slots", 2)
        t.decision("admit", rid=0, reason="capacity")
        t.async_begin("requests", "req0", 0, cat="request")
    t.async_end("requests", "req0", 0, cat="request")
    t.complete("dma/preload", "PRELOAD", ts=1.0, dur=2.5, cat="dma")

    path = tmp_path / "t.json"
    doc = t.to_chrome(str(path))
    assert path.exists()
    assert validate_chrome_trace(doc) == []

    by_ph = {}
    for ev in doc["traceEvents"]:
        by_ph.setdefault(ev["ph"], []).append(ev)
    # counters stay pure: no tick injected next to the value series
    assert all("tick" not in (ev.get("args") or {}) for ev in by_ph["C"])
    # non-counter events carry the tick
    assert all(ev["args"]["tick"] == 3 for ev in by_ph["B"])
    # instants carry thread scope (Perfetto renders them as arrows without)
    assert all(ev["s"] == "t" for ev in by_ph["i"])
    # the DMA track lives in its own process (model time != wall time)
    serving_pids = {ev["pid"] for ev in by_ph["B"]}
    dma_pids = {ev["pid"] for ev in by_ph["X"]}
    assert serving_pids.isdisjoint(dma_pids)


def test_null_tracer_records_and_allocates_nothing():
    assert NULL_TRACER.enabled is False
    assert NULL_TRACER.events == ()
    # the shared null context: no per-call allocation on the hot path
    assert NULL_TRACER.span("a", "b") is NULL_TRACER.span("c", "d")
    NULL_TRACER.decision("admit", rid=1)
    NULL_TRACER.counter("g", "x", 1)
    assert NULL_TRACER.events == ()
    with pytest.raises(RuntimeError):
        NULL_TRACER.to_chrome()


def test_args_are_json_safe_and_restored():
    t = Tracer()
    t.instant("pages", "deadline", cat="page",
              seq=0, clock=0, page=1, deadline=math.inf, pinned=(1, 2))
    doc = t.to_chrome()
    import json
    doc = json.loads(json.dumps(doc))       # must survive a real round-trip
    (ev,) = [e for e in doc["traceEvents"] if e.get("cat") == "page"]
    assert ev["args"]["deadline"] == "inf"
    assert ev["args"]["pinned"] == [1, 2]
    (pe,) = page_events_from_chrome(doc)
    assert pe.deadline == math.inf and pe.pinned == (1, 2)


def test_spans_pair_begin_and_end_on_perf_counter():
    t = Tracer()
    before = time.perf_counter()
    t.set_tick(4)
    with t.span("engine", "outer", rows=2):
        with t.span("engine", "inner"):
            pass
        t.instant("engine", "mark")
    after = time.perf_counter()
    outer, inner = t.spans()
    assert (outer.name, outer.parent, outer.tick, outer.args) == \
        ("outer", None, 4, {"rows": 2})
    assert (inner.name, inner.parent, inner.args) == ("inner", "outer", None)
    assert before <= outer.start <= inner.start <= inner.end <= outer.end \
        <= after
    # the bounded buffer drops the oldest events: a span whose begin went
    # is left out, the ones after it stay whole
    t.events = t.events[1:]
    assert [sp.name for sp in t.spans()] == ["inner"]
    assert Tracer(only_while_profiling=True).events.maxlen == \
        Tracer.MAX_PROFILED_EVENTS


# ======================================================================== #
# schema-validator fixtures
# ======================================================================== #

def _ev(**kw):
    base = {"ph": "i", "name": "x", "pid": 1, "tid": 1, "ts": 0.0, "s": "t"}
    base.update(kw)
    return base


def test_validator_catches_unbalanced_spans():
    doc = {"traceEvents": [_ev(ph="B"), _ev(ph="E"), _ev(ph="E")]}
    assert any("no open 'B'" in e for e in validate_chrome_trace(doc))
    doc = {"traceEvents": [_ev(ph="B")]}
    assert any("never closed" in e for e in validate_chrome_trace(doc))


def test_validator_catches_unknown_phase_and_bad_ts():
    assert any("unknown phase" in e for e in validate_chrome_trace(
        {"traceEvents": [_ev(ph="Q")]}))
    assert any("non-finite ts" in e for e in validate_chrome_trace(
        {"traceEvents": [_ev(ts=math.inf)]}))


def test_validator_catches_async_and_complete_misuse():
    assert any("async 'e' before 'b'" in e for e in validate_chrome_trace(
        {"traceEvents": [_ev(ph="e", cat="request", id=7)]}))
    assert any("missing 'id'" in e for e in validate_chrome_trace(
        {"traceEvents": [_ev(ph="b", cat="request")]}))
    assert any("dur >= 0" in e for e in validate_chrome_trace(
        {"traceEvents": [_ev(ph="X", dur=-1.0)]}))


def test_validator_catches_empty_counter():
    assert any("no numeric args" in e for e in validate_chrome_trace(
        {"traceEvents": [_ev(ph="C", args={"note": "text"})]}))


# ======================================================================== #
# engine round-trip (reduced model; one traced + one untraced run, cached)
# ======================================================================== #

_MODEL = {}


def _model():
    if not _MODEL:
        cfg = get_config("qwen3-1.7b").reduced()
        m = build_model(dataclasses.replace(cfg, paged_kv=True))
        _MODEL["cfg"] = cfg
        _MODEL["params"] = m.init(jax.random.PRNGKey(0))
    return _MODEL["cfg"], _MODEL["params"]


def _mixed_run(tracer=None, shadow=False, hook=None):
    """Two long low-priority decoders + one short high-priority request
    under the priority policy: forces a preemption, so the run exercises
    swap-out (evictions), resume (restores/faults), and the full request/
    slot async-span lifecycle."""
    cfg, params = _model()
    eng = PagedServingEngine(cfg, params, PagedEngineConfig(
        batch_slots=2, max_seq=64, page_tokens=8,
        prefill_buckets=(8, 16, 32), policy="priority",
        shadow_check=shadow), metrics_hook=hook, tracer=tracer)
    rng = np.random.default_rng(7)
    for i in range(2):
        eng.submit(Request(
            rid=i, prompt=rng.integers(1, cfg.vocab_size, size=12).tolist(),
            max_new_tokens=8, priority=0))
    for _ in range(2):
        eng.step()
    eng.submit(Request(
        rid=100, prompt=rng.integers(1, cfg.vocab_size, size=6).tolist(),
        max_new_tokens=3, priority=1, ttft_deadline=4))
    out = eng.run(max_ticks=64)
    return eng, out


_RUNS = {}


def _traced():
    if "traced" not in _RUNS:
        tracer = Tracer()
        eng, out = _mixed_run(tracer=tracer, shadow=True)
        _RUNS["traced"] = (eng, out, tracer.to_chrome())
    return _RUNS["traced"]


def _untraced():
    if "untraced" not in _RUNS:
        _RUNS["untraced"] = _mixed_run()
    return _RUNS["untraced"]


def test_traced_run_exports_valid_trace():
    eng, _, doc = _traced()
    assert validate_chrome_trace(doc) == []
    assert eng.metrics.preemptions >= 1, "workload must force a preemption"
    names = {ev.get("name") for ev in doc["traceEvents"]}
    assert {"serve.tick", "serve.admit", "serve.decode"} <= names
    assert "preempt" in names                            # reasoned decision
    # counters never carry tick in args (each key renders as a series)
    for ev in doc["traceEvents"]:
        if ev["ph"] == "C":
            assert "tick" not in (ev.get("args") or {})


def test_page_bridge_reconstructs_pool_trace_exactly():
    eng, _, doc = _traced()
    rebuilt = page_events_from_chrome(doc)
    assert rebuilt == list(eng.pool.trace.events)
    assert any(e.kind.value == "evict" for e in rebuilt)    # the preemption
    assert any(e.kind.value == "restore" for e in rebuilt)  # the resume


def test_reconstructed_trace_replays_clean_through_sanitizer():
    _, _, doc = _traced()
    violations = LifecycleChecker().feed(page_events_from_chrome(doc))
    assert violations == [], [v for v in violations]


def test_decision_stream_carries_reasons():
    _, _, doc = _traced()
    stream = trace_diff.decision_stream(doc)
    labels = [label for label, _, _ in stream]
    assert "decision:admit" in labels
    assert "decision:preempt" in labels and "decision:resume" in labels
    (preempt,) = [a for label, a, _ in stream if label == "decision:preempt"]
    assert preempt["reason"] == "priority"


def test_tracing_off_records_nothing_and_stays_deterministic():
    eng_t, out_t, _ = _traced()
    eng_u, out_u = _untraced()
    assert eng_u.tracer is DEFAULT_TRACER and len(eng_u.tracer.events) == 0
    assert out_u == out_t                       # token streams identical
    volatile = ("tokens_per_sec", "wall_time", "mean_queue_wait_s")
    snap_t = {k: v for k, v in eng_t.snapshot().items() if k not in volatile}
    snap_u = {k: v for k, v in eng_u.snapshot().items() if k not in volatile}
    assert snap_u == snap_t


def test_crashing_metrics_hook_warns_once_and_is_disabled():
    calls = []

    def hook(snap):
        calls.append(snap["tick"])
        raise ValueError("observer bug")

    with pytest.warns(RuntimeWarning, match="disabling the hook"):
        eng, out = _mixed_run(hook=hook)
    assert len(calls) == 1, "hook must be disabled after the first raise"
    assert eng.metrics_hook is None
    assert out == _untraced()[1], "a hook crash must not perturb the run"


# ======================================================================== #
# the default tracer: spans on the profiler's clock, only under a session
# ======================================================================== #

# every span the engine and pool open, with the span each opens inside
SPAN_PARENTS = {
    "serve.tick": None,
    "serve.admit": "serve.tick",
    "serve.prefill": "serve.admit",
    "pool.write_page": "serve.prefill",
    "pool.evict": "serve.admit",
    "serve.decode": "serve.tick",
    "serve.decode.prepare": "serve.decode",
    "pool.restore": "serve.decode.prepare",
    "serve.decode.fetch": "serve.decode",
    "serve.sample": "serve.decode",
}


def _profile_host_events(root, names):
    """[(name, start, end)] in seconds, by start: the host events of the
    one `.xplane.pb` profile under `root` whose names are in `names`."""
    from jax.profiler import ProfileData
    (pb,) = Path(root).rglob("*.xplane.pb")
    return sorted(((ev.name, ev.start_ns * 1e-9,
                    (ev.start_ns + ev.duration_ns) * 1e-9)
                   for plane in ProfileData.from_file(str(pb)).planes
                   if plane.name.startswith("/host:")
                   for line in plane.lines for ev in line.events
                   if ev.name in names), key=lambda e: e[1])


def _profiled(tmp_path_factory):
    """The mixed run inside a profiler session: (engine, outputs, the
    default tracer's spans, the profile's host events of those names, the
    default tracer's events)."""
    if "profiled" not in _RUNS:
        DEFAULT_TRACER.events.clear()
        tmp = tmp_path_factory.mktemp("profile")
        jax.profiler.start_trace(str(tmp))
        try:
            eng, out = _mixed_run()
        finally:
            jax.profiler.stop_trace()
        spans = DEFAULT_TRACER.spans()
        events = list(DEFAULT_TRACER.events)
        DEFAULT_TRACER.events.clear()
        _RUNS["profiled"] = (eng, out, spans,
                             _profile_host_events(tmp, SPAN_PARENTS), events)
    return _RUNS["profiled"]


def _one_offset(pairs):
    """Fit trace time = perf_counter + c over [((s, e), (a_s, a_e))], each
    recorded span (s, e) inside its profile event (a_s, a_e): c lies in
    [max(a_s - s), min(a_e - e)], and a pause of the host between the
    annotation and the clock reading only widens that room. Returns (c,
    by how much the bounds cross (<= 0 where they do not), the median
    distance of a mapped start from its event's)."""
    lo = max(a_s - s for (s, _), (a_s, _) in pairs)
    hi = min(a_e - e for (_, e), (_, a_e) in pairs)
    c = (lo + hi) / 2
    gap = float(np.median([abs(a_s - (s + c)) for (s, _), (a_s, _) in pairs]))
    return c, lo - hi, gap


def test_spans_show_in_a_profile_only_while_it_records(tmp_path):
    explicit = Tracer()
    gated = Tracer(only_while_profiling=True)
    assert not gated.enabled and explicit.enabled
    with explicit.span("engine", "before"), gated.span("engine", "before"):
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert profiler_recording()
        with explicit.span("engine", "during.explicit", slot=1):
            with gated.span("engine", "during.default"):
                time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
    with gated.span("engine", "after"):
        pass
    assert [sp.name for sp in explicit.spans()] == ["before",
                                                    "during.explicit"]
    assert [sp.name for sp in gated.spans()] == ["during.default"]
    host = _profile_host_events(tmp_path, {"before", "during.explicit",
                                           "during.default", "after"})
    assert [n for n, _, _ in host] == ["during.explicit", "during.default"]
    (mine,) = [sp for sp in explicit.spans() if sp.name != "before"]
    _, crossed, _ = _one_offset([((mine.start, mine.end), host[0][1:])])
    assert crossed <= 1e-6                  # the span lies inside the event


def test_default_tracer_records_nothing_without_a_profiler_session():
    DEFAULT_TRACER.events.clear()
    eng_d, out_d = _untraced()
    assert eng_d.tracer is DEFAULT_TRACER
    assert eng_d.pool.tracer is DEFAULT_TRACER
    assert eng_d.scheduler.tracer is DEFAULT_TRACER
    assert not profiler_recording()
    eng_n, out_n = _mixed_run(tracer=NULL_TRACER)
    assert len(DEFAULT_TRACER.events) == 0 and DEFAULT_TRACER.spans() == []
    assert DEFAULT_TRACER.events.maxlen is not None      # bounded
    assert out_d == out_n                       # token for token
    # the check is the only cost: spans off hand back the shared null ctx
    assert DEFAULT_TRACER.span("engine", "serve.tick") is \
        DEFAULT_TRACER.span("engine", "serve.sample", rows=3)


def test_profiled_spans_are_in_the_profile_on_one_offset(tmp_path_factory):
    eng, out, spans, events, _ = _profiled(tmp_path_factory)
    assert out == _untraced()[1]
    assert eng.metrics.preemptions >= 1
    names = {s.name for s in spans}
    assert names == set(SPAN_PARENTS)           # the run reaches every one
    assert {n for n, _, _ in events} == names
    # the k-th span of a name is the k-th profile event of that name
    by_name = {}
    for n, s, e in events:
        by_name.setdefault(n, []).append((s, e))
    pairs = []
    for name in names:
        mine = sorted((s.start, s.end) for s in spans if s.name == name)
        theirs = by_name[name]
        assert len(mine) == len(theirs), name
        pairs += zip(mine, theirs)
    _, crossed, gap = _one_offset(pairs)
    assert crossed <= 50e-6 and gap <= 50e-6
    assert all(set(s.args) == {"bucket", "rows"}
               for s in spans if s.name == "serve.prefill")
    assert all(set(s.args) == {"n_valid"}
               for s in spans if s.name == "pool.write_page")


def test_profiled_spans_nest_and_close(tmp_path_factory):
    eng, _, spans, _, events = _profiled(tmp_path_factory)
    phases = [ev.ph for ev in events]
    # only spans (no page-lifecycle instants), and every one closed
    assert set(phases) == {"B", "E"}
    assert phases.count("B") == phases.count("E") == len(spans)
    assert not DEFAULT_TRACER.enabled and eng.pool.trace is None
    for s in spans:
        assert s.end >= s.start
        assert s.parent == SPAN_PARENTS[s.name], s
        if s.parent is not None:                # inside a span of that name
            assert any(p.name == s.parent and p.start <= s.start
                       and s.end <= p.end for p in spans), s
    ticks = [s for s in spans if s.name == "serve.tick"]
    assert len(ticks) == eng.metrics.ticks
    assert [s.tick for s in ticks] == list(range(len(ticks)))


def test_queue_wait_in_seconds_beside_ticks():
    sched = AdmissionScheduler(SchedulerConfig(prefill_buckets=(8,)))
    reqs = [Request(rid=i, prompt=[1, 2, 3]) for i in range(2)]
    for r in reqs:
        sched.submit(r, now=0)
    time.sleep(0.01)
    (a,) = sched.admit([0], active_tokens=0, free_hot_frames=8, now=3)
    assert a.request is reqs[0] and reqs[0].queue_latency == 3
    assert reqs[0].admit_t - reqs[0].submit_t >= 0.01
    assert reqs[1].admit_t == -1.0                    # still queued
    assert sched.queue_waits_s() == [reqs[0].admit_t - reqs[0].submit_t]
    eng, _ = _untraced()
    assert eng.snapshot()["mean_queue_wait_s"] >= 0.0


# ======================================================================== #
# cache economics + metrics registry
# ======================================================================== #

def test_cache_economics_arithmetic():
    pm = PoolMetrics(page_faults=3, evictions=2, bytes_hot_written=1000,
                     planned_preloads=3, useful_preloads=2,
                     wasted_preloads=1)
    econ = cache_economics(page_bytes=100, tokens_emitted=10,
                           pool_metrics=pm)
    hot, cold = econ["tiers"]["hot"], econ["tiers"]["cold"]
    assert hot["bytes_in"] == 3 * 100 + 1000    # restores + scatter fills
    assert hot["bytes_out"] == 2 * 100
    assert hot["bytes_per_token"] == (300 + 1000 + 200) / 10
    assert cold == {"bytes_in": 200, "bytes_out": 300, "bytes_moved": 500,
                    "bytes_per_token": 50.0}
    pf = econ["prefetch"]
    assert pf["accuracy"] == pytest.approx(2 / 3)
    assert pf["coverage"] == 1.0                # all restores were planned


def test_registry_exporters():
    reg = MetricsRegistry()
    reg.set("pul_x", 1.5, help="an x", tier="hot")
    reg.set("pul_x", 2.5, tier="cold")
    reg.inc("pul_y", 2)
    reg.inc("pul_y", 3)
    assert reg.get("pul_x", tier="cold") == 2.5
    assert reg.get("pul_y") == 5.0
    prom = reg.to_prometheus()
    assert "# HELP pul_x an x" in prom
    assert '# TYPE pul_x gauge' in prom
    assert 'pul_x{tier="hot"} 1.5' in prom
    assert prom.endswith("\n")
    js = reg.to_json()
    assert js["pul_y"] == [{"labels": {}, "value": 5.0}]


def test_engine_metrics_registry_has_economics():
    eng, _, _ = _traced()
    reg = eng.metrics_registry()
    econ = eng.economics()
    assert (reg.get("pul_cache_bytes_per_token", tier="hot",
                    policy="priority")
            == econ["tiers"]["hot"]["bytes_per_token"])
    assert reg.get("pul_engine_tokens_emitted", policy="priority") \
        == eng.metrics.tokens_emitted
    # every policy report must expose prefetch quality
    for k in ("accuracy", "timeliness", "coverage"):
        assert reg.get(f"pul_prefetch_{k}", policy="priority") is not None
    economics_into_registry(reg, econ, run="again")
    assert reg.get("pul_tokens_emitted", run="again") is not None


# ======================================================================== #
# DMA FIFO occupancy: executed trace vs symbolic schedule
# ======================================================================== #

_WL = KVPageWorkload(page_bytes=16 * 128 * 2,
                     flops_per_page=4.0 * 16 * 128 * 4,
                     pages_per_step=4, steps=16)


def _traced_dma(distance, fifo_depth=64):
    tracer = Tracer()
    eng = DMAEngine(TIERS["remote_hbm"], PES["tpu_v5e_vpu"],
                    fifo_depth=fifo_depth, tracer=tracer)
    run_kv_page_workload(eng, _WL, distance=distance)
    cfg = PULConfig(distance=min(distance, fifo_depth),
                    fifo_depth=fifo_depth, unload_distance=1)
    return eng, cfg, tracer


def test_fifo_diff_empty_on_clean_run():
    eng, cfg, tracer = _traced_dma(distance=8)
    pre, _ = eng.last_channels
    assert diff_fifo_occupancy(cfg, n_blocks=_WL.n_pages, channel=pre,
                               engine_fifo_depth=eng.fifo_depth) == []
    doc = tracer.to_chrome()
    assert validate_chrome_trace(doc) == []
    # the high-water instant rides along in the export
    assert any(ev.get("name") == "fifo-high-water"
               for ev in doc["traceEvents"])


def test_fifo_diff_empty_under_back_pressure():
    eng, cfg, _ = _traced_dma(distance=8, fifo_depth=4)
    pre, _ = eng.last_channels
    assert pre.stalls, "shallow FIFO must produce back-pressure stalls"
    assert diff_fifo_occupancy(cfg, n_blocks=_WL.n_pages, channel=pre,
                               engine_fifo_depth=eng.fifo_depth) == []


def test_fifo_diff_catches_corrupted_occupancy():
    eng, cfg, _ = _traced_dma(distance=8)
    pre, _ = eng.last_channels
    t, _occ = pre.occupancy_log[0]
    pre.occupancy_log[0] = (t, 99)
    diff = diff_fifo_occupancy(cfg, n_blocks=_WL.n_pages, channel=pre,
                               engine_fifo_depth=eng.fifo_depth)
    assert any("exceeds the symbolic in-flight window" in d for d in diff)


# ======================================================================== #
# trace_diff tool semantics
# ======================================================================== #

def _decision_doc(policy, n=3):
    t = Tracer()
    for i in range(n):
        t.set_tick(i)                       # volatile: ignored by the diff
        t.decision("admit", rid=i, policy=policy, reason="capacity")
    return t.to_chrome()


def test_trace_diff_ignores_volatile_keys():
    a = trace_diff.decision_stream(_decision_doc("fcfs"))
    b = trace_diff.decision_stream(_decision_doc("fcfs"))
    assert trace_diff.diff_decisions(a, b) is None


def test_trace_diff_reports_first_divergence_with_reason():
    a = trace_diff.decision_stream(_decision_doc("fcfs"))
    b = trace_diff.decision_stream(_decision_doc("slo-edf"))
    idx, why = trace_diff.diff_decisions(a, b)
    assert idx == 0
    assert "policy" in why and "'fcfs'" in why and "reason" in why


def test_trace_diff_reports_length_mismatch():
    a = trace_diff.decision_stream(_decision_doc("fcfs", n=2))
    b = trace_diff.decision_stream(_decision_doc("fcfs", n=4))
    idx, why = trace_diff.diff_decisions(a, b)
    assert idx == 2 and "continues alone" in why
