"""The open-loop generator: determinism, the seed's effect, and lengths
that follow the traffic files."""
import json

import numpy as np
import pytest

from conftest import BENCH
from chipbench import discover

BIG_SEED = 2**40 + 12345


def _mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def _schedule(name, seed, rate=2.0, seconds=30.0, max_total=2047):
    gen = discover.cell(f"qwen3-1.7b.{name}")["generator"]
    return gen.schedule(_mix(name), rate=rate, seconds=seconds, seed=seed,
                        vocab=151936, max_total=max_total)


@pytest.mark.parametrize("name", ["chat", "swap"])
def test_same_seed_same_schedule_other_seed_other(name):
    a, b = _schedule(name, BIG_SEED), _schedule(name, BIG_SEED)
    assert a == b
    c = _schedule(name, BIG_SEED + 1)
    assert [r["prompt"] for r in c] != [r["prompt"] for r in a]


@pytest.mark.parametrize("name", ["chat", "swap"])
def test_every_seed_gets_the_same_work_at_the_same_times(name):
    a, b = _schedule(name, 1), _schedule(name, 2)
    strip = lambda s: [(r["due"], r["cls"], len(r["prompt"]), r["max_new"],
                        r["priority"]) for r in s]
    assert strip(a) == strip(b)


def test_the_order_comes_from_the_mix():
    mix = _mix("chat")
    gen = discover.cell("qwen3-1.7b.chat")["generator"]
    other = dict(mix, order_seed=mix["order_seed"] + 1)
    kw = dict(rate=2.0, seconds=30.0, seed=1, vocab=151936, max_total=2047)
    assert [r["due"] for r in gen.schedule(mix, **kw)] != [
        r["due"] for r in gen.schedule(other, **kw)]


@pytest.mark.parametrize("name", ["chat", "swap"])
def test_drawn_lengths_match_the_traffic_file(name):
    mix = _mix(name)
    sched = _schedule(name, 7, rate=40.0, seconds=100.0, max_total=10**6)
    for c in mix["classes"]:
        rs = [r for r in sched if r["cls"] == c["name"]]
        assert abs(len(rs) / len(sched) - c["share"]) < 0.01
        for key, got in (("prompt", [len(r["prompt"]) for r in rs]),
                         ("output", [r["max_new"] for r in rs])):
            spec = c[key]
            assert min(got) >= spec["min"] and max(got) <= spec["max"]
            assert abs(np.median(got) / spec["median"] - 1) < 0.02
            # lognormal spread: the quartiles sit at median * exp(+-0.674 s)
            q1, q3 = np.percentile(got, [25, 75])
            want = spec["median"] * np.exp(0.674 * spec["sigma"])
            assert abs(q3 / want - 1) < 0.03 or q3 >= spec["max"]
            want = spec["median"] * np.exp(-0.674 * spec["sigma"])
            assert abs(q1 / want - 1) < 0.03 or q1 <= spec["min"]


def test_arrivals_have_the_rate_and_burstiness_of_the_file():
    for name, cv in (("chat", 1.0), ("swap", 2.0)):
        sched = _schedule(name, 3, rate=20.0, seconds=500.0)
        gaps = np.diff([r["due"] for r in sched])
        assert abs(gaps.mean() * 20.0 - 1) < 0.02
        assert abs(gaps.std() / gaps.mean() / cv - 1) < 0.1


def test_chat_prompts_open_with_a_shared_system_prompt():
    mix = _mix("chat")
    sched = _schedule("chat", 11)
    n = mix["system_prompts"]["tokens"]
    heads = {tuple(r["prompt"][:n]) for r in sched
             if len(r["prompt"]) >= n + mix["system_prompts"]
             ["min_user_tokens"]}
    assert len(heads) == mix["system_prompts"]["count"]


def test_priorities_follow_the_classes():
    sched = _schedule("swap", 5)
    pri = {c["name"]: c["priority"] for c in _mix("swap")["classes"]}
    assert all(r["priority"] == pri[r["cls"]] for r in sched)
