"""The program's host spans set against a device trace: the offset fit, the
innermost-span attribution and the four engine-host idle metrics, on a
synthetic trace worked by hand; the fit and the span source on a real
profile."""
import time
import types

import pytest

from chipbench import discover, program_spans as PS, trace as T

OFFSET = 100.0     # trace clock = perf_counter + OFFSET


def _ctx(jitter=1e-6, shift=0.0):
    """Five harness steps on perf_counter, the middle two traced, each
    traced span `jitter` inside its step and the second `shift` later; the
    program's spans of those two; the device busy four times."""
    steps = [(0.0, 0.5, 1), (0.6, 1.0, 1), (2.0, 4.0, 2), (5.0, 6.0, 2),
             (7.0, 7.5, 1)]
    host = [("engine.submit", 101.5, 102.0),
            ("engine.step", 102.0 + jitter, 104.0 - jitter),
            ("generator.wait", 104.0, 105.0),
            ("engine.step", 105.0 + 2 * jitter + shift,
             106.0 - jitter + shift),
            ("engine.submit", 106.0 - jitter, 106.0)]
    dev = T.Device(ops=[("a", 101.5, 102.3), ("b", 102.55, 102.8),
                        ("c", 103.1, 103.6), ("d", 105.1, 105.8)],
                   programs=[])
    tr = T.Trace([dev], host)
    return types.SimpleNamespace(trace=tr, steps=steps, tw=tr.window())


SPANS = [  # (name, start, end) on perf_counter
    ("serve.tick", 2.1, 3.9),
    ("serve.admit", 2.1, 2.9),
    ("serve.prefill", 2.2, 2.8),
    ("pool.write_page", 2.5, 2.6),
    ("serve.decode", 3.0, 3.9),
    ("serve.decode.prepare", 3.0, 3.2),
    ("pool.restore", 3.05, 3.1),
    ("serve.decode.fetch", 3.2, 3.7),
    ("serve.sample", 3.7, 3.9),
    ("serve.tick", 5.0, 6.0),
    ("serve.decode", 5.0, 6.0),
    ("serve.decode.prepare", 5.0, 5.2),
    ("serve.decode.fetch", 5.2, 5.9),
    ("serve.sample", 5.9, 6.0),
]

# idle (102.3, 102.55) (102.8, 103.1) (103.6, 105.1) (105.8, 106.0), each
# instant to the innermost span open then, by hand
IDLE = {"serve.prefill": 0.2, "pool.write_page": 0.05, "serve.admit": 0.1,
        "serve.tick": 0.1, "serve.decode.prepare": 0.15,
        "pool.restore": 0.05, "serve.decode.fetch": 0.2,
        "serve.sample": 0.3, PS.NO_SPAN: 1.1}


def test_offset_fit_and_its_refusal():
    ctx = _ctx()
    offset, residual = PS.fit_offset(ctx.trace.host, ctx.steps)
    # bounds: largest (trace end - end) 100 - 1 us, smallest (trace start
    # - start) 100 + 1 us
    assert offset == pytest.approx(OFFSET, abs=1e-9)
    assert residual == pytest.approx(1e-6, abs=1e-9)
    # a pause between a clock reading and its span moves neither bound
    slack = _ctx(jitter=30e-6)
    assert PS.fit_offset(slack.trace.host, slack.steps)[0] == \
        pytest.approx(OFFSET, abs=1e-9)
    # one step 150 us later than the other: the bounds cross by 148 us
    wide = _ctx(shift=150e-6)
    assert PS.fit_offset(wide.trace.host, wide.steps) is None
    assert PS.fit_offset([], ctx.steps) is None
    assert PS.fit_offset(ctx.trace.host, ctx.steps[:1]) is None


def test_innermost_span_attribution(monkeypatch):
    own = PS.innermost([(n, s + OFFSET, e + OFFSET) for n, s, e in SPANS])
    assert own["serve.admit"] == [pytest.approx(iv) for iv in
                                  ((102.1, 102.2), (102.8, 102.9))]
    assert own["serve.tick"] == [pytest.approx((102.9, 103.0))]
    assert [pytest.approx(iv) for iv in own["serve.decode.prepare"]] == [
        (103.0, 103.05), (103.1, 103.2), (105.0, 105.2)]
    assert "serve.decode" not in own        # wholly covered by its children
    monkeypatch.setattr(PS, "program_spans", lambda: SPANS)
    by = PS.idle_by_span(_ctx())
    assert by == pytest.approx(IDLE)
    assert sum(by.values()) == pytest.approx(4.5 - T.busy(
        _ctx().trace.devices[0], 101.5, 106.0))


@pytest.mark.parametrize("metric,names", [
    ("admit_idle_share.chat",
     ("serve.admit", "serve.prefill", "pool.write_page")),
    ("prepare_idle_share.chat", ("serve.decode.prepare",)),
    ("fetch_idle_share.chat", ("serve.decode.fetch",)),
    ("sample_idle_share.chat", ("serve.sample",)),
])
def test_engine_host_metrics(monkeypatch, metric, names):
    hand = {"admit_idle_share.chat": 100 * 0.35 / 4.5,
            "prepare_idle_share.chat": 100 * 0.15 / 4.5,
            "fetch_idle_share.chat": 100 * 0.2 / 4.5,
            "sample_idle_share.chat": 100 * 0.3 / 4.5}[metric]
    assert hand == pytest.approx(100 * sum(IDLE[n] for n in names) / 4.5)
    read = discover.metric(metric).read
    monkeypatch.setattr(PS, "program_spans", lambda: SPANS)
    assert read(_ctx()) == pytest.approx(hand)
    # a program without the spans, or steps that do not fit: no reading
    monkeypatch.setattr(PS, "program_spans", lambda: [])
    assert read(_ctx()) is None
    monkeypatch.setattr(PS, "program_spans", lambda: SPANS)
    assert read(_ctx(shift=150e-6)) is None
    assert read(types.SimpleNamespace(trace=None)) is None


def test_spans_and_fit_on_a_real_profile(tmp_path):
    import jax
    from repro.obs import DEFAULT_TRACER
    DEFAULT_TRACER.events.clear()
    steps = []
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(5):
        a = time.perf_counter()
        with jax.profiler.TraceAnnotation("engine.step"):
            with DEFAULT_TRACER.span("engine", "serve.tick"):
                time.sleep(0.002)
        steps.append((a, time.perf_counter(), 0))
    jax.profiler.stop_trace()
    spans = PS.program_spans()
    assert [n for n, _, _ in spans] == ["serve.tick"] * 5
    tr = T.load(tmp_path)
    offset, residual = PS.fit_offset(tr.host, steps)
    assert residual <= PS.MAX_RESIDUAL_S
    traced = [(s, e) for n, s, e in tr.host if n == "engine.step"]
    for (_, s, e), (ts, te) in zip(spans, traced):   # each inside its step
        assert ts - PS.MAX_RESIDUAL_S <= s + offset < e + offset \
            <= te + PS.MAX_RESIDUAL_S
    DEFAULT_TRACER.events.clear()
