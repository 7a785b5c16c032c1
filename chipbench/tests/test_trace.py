"""The trace reduction: interval arithmetic, a trace recorded on a TPU v5e
by chipbench/run.py (0.6 s of a qwen3-1.7b.chat window), reading
a profile, and the table of peaks."""
import pytest

from conftest import BENCH
from chipbench import programs, trace as T

RECORDED = BENCH / "tests" / "data" / "trace_small.json"


def test_union_gaps_overlap():
    u = T.union([(0, 2), (1, 3), (5, 6), (5.5, 5.7), (9, 12)], 0, 10)
    assert u == [(0, 3), (5, 6), (9, 10)]
    assert T.length(u) == 5
    assert T.gaps(u, 0, 10) == [(3, 5), (6, 9)]
    assert T.gaps([], 1, 2) == [(1, 2)]
    assert T.overlap(u, [(2, 5.5), (9.5, 20)]) == pytest.approx(1.5 + 0.5)


def _synthetic():
    dev = T.Device(
        ops=[("fusion.1", 0.0, 1.0), ("while", 1.0, 3.5),
             ("k tpu_custom_call", 1.0, 3.0), ("fusion.2", 3.0, 3.5),
             ("copy", 6.0, 7.0)],
        programs=[("jit__lambda_(1)", 0.0, 1.0),
                  ("jit_paged_decode_fn(2)", 1.0, 3.6),
                  ("jit_scatter(3)", 6.0, 7.0)])
    host = [("engine.step", 0.0, 4.0), ("generator.wait", 4.0, 6.0),
            ("engine.step", 6.0, 8.0), ("engine.submit", 8.0, 10.0)]
    return T.Trace([dev], host)


def test_reductions_on_a_synthetic_trace():
    tr = _synthetic()
    lo, hi = tr.window()
    assert (lo, hi) == (0.0, 10.0)
    dev = tr.devices[0]
    assert T.busy(dev, lo, hi) == pytest.approx(4.5)
    by = T.busy_by_program(dev, lo, hi)
    assert by == {"jit__lambda_": 1.0, "jit_paged_decode_fn": 2.5,
                  "jit_scatter": 1.0}
    assert programs.by_kind(by) == {"prefill": 1.0, "decode": 2.5,
                                    "eager": 1.0}
    assert T.op_time(dev, lo, hi, programs.SWEEP_KERNEL.search) == 2.0
    assert T.op_time(dev, lo, hi, lambda n: n == "while") == 0.0  # no leaf
    assert T.top_ops(dev, lo, hi, 2) == [
        ["jit_paged_decode_fn/k tpu_custom_call", 2.0],
        ["jit__lambda_/fusion.1", 1.0]]
    idle = dict(T.idle_by_host(tr, dev, lo, hi))
    assert idle == {"generator.wait": 2.0, "engine.submit": 2.0,
                    "engine.step": pytest.approx(0.5 + 1.0)}
    assert sum(idle.values()) == pytest.approx(hi - lo - 4.5)


def test_reductions_on_a_recorded_tpu_trace():
    tr = T.undump(RECORDED.read_text())
    lo, hi = tr.window()
    dev = tr.devices[0]
    busy = T.busy(dev, lo, hi)
    assert 0 < busy < hi - lo
    by = T.busy_by_program(dev, lo, hi)
    assert sum(by.values()) == pytest.approx(busy)
    kinds = programs.by_kind(by)
    assert kinds["decode"] > 0 and kinds["eager"] > 0
    assert T.op_time(dev, lo, hi, programs.SWEEP_KERNEL.search) > 0
    idle = T.idle_by_host(tr, dev, lo, hi)
    assert sum(v for _, v in idle) == pytest.approx(hi - lo - busy)
    ops = T.top_ops(dev, lo, hi)
    assert [v for _, v in ops] == sorted((v for _, v in ops), reverse=True)


def test_host_spans_are_read_from_a_profile(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for name in ("engine.step", "generator.wait", "other.span"):
        with jax.profiler.TraceAnnotation(name):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = T.load(tmp_path)
    assert [n for n, _, _ in tr.host] == ["engine.step", "generator.wait"]
    assert all(e > s for _, s, e in tr.host)
    assert tr.devices == []             # the CPU has no TPU plane


def test_peaks_are_keyed_by_device_kind():
    p = T.load_peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        T.load_peaks("TPU v9 imaginary")
