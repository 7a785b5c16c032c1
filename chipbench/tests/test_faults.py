"""The harness, driven past its look for a chip at the small size, comes
out not correct when the timed path is broken underneath it, and correct
when it is not."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from small import run_small
from repro.serving import engine as E


def test_sound_run_is_correct():
    res = run_small("qwen3-1.7b.chat", 5)
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0


def test_a_token_altered_where_it_is_produced_fails(monkeypatch):
    emit, count = E.PagedServingEngine._emit_token, [0]

    def altered(self, slot, logits):
        count[0] += 1
        if count[0] % 5 == 0:          # every fifth token: the least likely
            logits = np.array(logits, copy=True)
            logits[int(np.argmin(logits))] = logits.max() + 1.0
        return emit(self, slot, logits)

    monkeypatch.setattr(E.PagedServingEngine, "_emit_token", altered)
    res = run_small("qwen3-1.7b.chat", 5)
    assert not res["correct"], res["check"]


@pytest.mark.parametrize("workload", ["qwen3-1.7b.chat", "qwen3-1.7b.swap"])
def test_a_decode_step_that_leaves_the_kv_unchanged_fails(monkeypatch,
                                                          workload):
    init = E.PagedServingEngine.__init__

    def patched(self, *a, **k):
        init(self, *a, **k)
        step = self._sweep_decode

        def stale(params, batch, tree, planes):
            keep = jax.tree.map(jnp.copy, planes)
            logits, new_tree, _ = step(params, batch, tree, planes)
            return logits, new_tree, keep     # the new token's K/V dropped

        self._sweep_decode = stale

    monkeypatch.setattr(E.PagedServingEngine, "__init__", patched)
    res = run_small(workload, 5)
    assert not res["correct"], res["check"]
