"""Open-loop request schedule from a traffic file.

Arrivals are a renewal process: inter-arrival gaps are gamma with the mix's
coefficient of variation (CV 1 is Poisson, CV 2 bursty) and mean 1/rate.
Each request belongs to one class of the mix, which fixes its priority and
its lognormal prompt and output lengths (median, sigma, clipped to
[min, max]). A prompt may open with one of the mix's shared system prompts.
Token ids are uniform over the vocabulary; decoding is greedy and each
request stops at its drawn output length.

Every seed gets the same work in the same order: the gaps, the classes and
each class's lengths are stratified quantiles of their distributions, put
in an order drawn from the mix's own `order_seed`. The run's seed draws the
token ids (system prompts included) and, in the harness, the weights. Runs
with different seeds then differ in what they compute, not in how much or
when: with a seed-drawn order, the median TTFT of 51 s chat windows spread
by a quarter from seed to seed.
"""
from __future__ import annotations

import numpy as np
from scipy import stats


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    z = stats.norm.ppf(_quantiles(n))
    x = np.rint(spec["median"] * np.exp(spec["sigma"] * z)).astype(np.int64)
    return rng.permutation(np.clip(x, spec["min"], spec["max"]))


def _gaps(arrivals: dict, rate: float, n: int,
          rng: np.random.Generator) -> np.ndarray:
    k = 1.0 / arrivals["cv"] ** 2
    g = stats.gamma.ppf(_quantiles(n), k)
    g = g / g.mean() / rate              # mean exactly 1/rate
    return rng.permutation(g)


def schedule(mix: dict, *, rate: float, seconds: float, seed: int,
             vocab: int, max_total: int) -> list[dict]:
    """Requests due in [0, warm_s + seconds), in due order.

    Each is {"due": s, "prompt": [ids], "max_new": n, "priority": p,
    "cls": name}. `max_total` caps prompt + output tokens per request."""
    rng = np.random.default_rng(mix["order_seed"])     # sizes and arrivals
    ids = np.random.default_rng(seed)                   # token ids
    duration = mix["warm_s"] + seconds
    n = max(1, int(round(rate * duration)))
    due = np.cumsum(_gaps(mix["arrivals"], rate, n, rng))
    due = due - due[0] * rng.random()       # first arrival inside [0, gap)

    classes = mix["classes"]
    counts = [int(round(c["share"] * n)) for c in classes]
    counts[0] += n - sum(counts)
    cls = rng.permutation(np.repeat(np.arange(len(classes)), counts))

    sp = mix["system_prompts"]
    systems = [ids.integers(0, vocab, sp["tokens"]).tolist()
               for _ in range(sp["count"])]
    sys_pick = rng.permutation(np.arange(n) % max(sp["count"], 1))

    lengths = {}
    for i, c in enumerate(classes):
        m = counts[i]
        lengths[i] = (list(_lengths(c["prompt"], m, rng)),
                      list(_lengths(c["output"], m, rng)))
    out = []
    for j in range(n):
        c = int(cls[j])
        prompt_len = int(lengths[c][0].pop())
        max_new = int(min(lengths[c][1].pop(), max_total - prompt_len))
        head = []
        if systems:
            cut = min(sp["tokens"], prompt_len - sp["min_user_tokens"])
            head = systems[int(sys_pick[j])][:max(cut, 0)]
        user = ids.integers(0, vocab, prompt_len - len(head)).tolist()
        out.append({"due": float(due[j]), "prompt": head + user,
                    "max_new": max_new, "priority": classes[c]["priority"],
                    "cls": classes[c]["name"]})
    return [r for r in out if r["due"] < duration]
