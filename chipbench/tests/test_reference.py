"""The float32 reference of each configuration's family against the
program's own model on logits."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import reduced_arch
from chipbench import adapter, discover, weights


@pytest.mark.parametrize("name", ["qwen3-1.7b", "qwen2.5-32b"])
def test_reference_matches_program_logits(name):
    arch = reduced_arch(name)
    ref = discover.family(arch).reference
    cfg = adapter.model_config(name, arch)
    w = weights.make(arch, 1234567890123, adapter.vocab_rows(cfg))
    tree = adapter.params(arch, cfg, w)
    T = 256
    tokens = np.random.default_rng(0).integers(0, arch["vocab_size"], T)
    model = adapter.build_model(cfg)
    logits, _ = jax.jit(model.prefill)(
        tree, {"tokens": jnp.asarray(tokens[None], jnp.int32)})
    h = ref.hidden(arch, w, tokens)
    top, at, best = ref.head_stats(arch, w, h, tokens)
    got = np.asarray(logits[0], np.float32)
    # the program computes in bfloat16: its last-position logits sit within
    # a few bf16 steps of the float32 reference (random logits have std ~1)
    full = np.asarray(jnp.dot(h[-1], w["embed" if arch["tie_word_embeddings"]
                                        else "lm_head"][:256].astype(
                                            jnp.float32).T))
    assert np.max(np.abs(got - full)) < 0.1
    assert float(top[-1]) == pytest.approx(float(full.max()), abs=1e-4)
    assert int(best[-1]) == int(full.argmax())
    assert float(at[0]) == pytest.approx(
        float(np.asarray(jnp.dot(h[0], (w["embed"] if arch[
            "tie_word_embeddings"] else w["lm_head"])[tokens[0]].astype(
                jnp.float32)))), abs=1e-4)
