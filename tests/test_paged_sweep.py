"""The fused paged sweep decode: parity, page bounds, epilogue commits and
zero-copy properties.

  * the sweep's token streams are the dense reference's (up to ties in
    its logits) across the zoo subset AND deepseek MLA, including a
    preempt/resume landing mid-chunked-prefill;
  * both sweeps stream only the pages the decode mask can keep (NaN
    frames everywhere else change no output), and the engine counts the
    pages they stream;
  * the fused epilogue writes the current token's rows at (layer, frame,
    offset) and nowhere else but the trash frame;
  * ``layer_view`` never copies: its jaxpr contains no data-movement
    primitive, and a jitted plane commit with donated planes aliases the
    input buffers in place (CPU buffer donation — the same mechanism the
    engine's ``_sweep_decode`` uses via ``donate_argnums``).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.serving import (
    PackedKVLayout,
    PagedEngineConfig,
    PagedServingEngine,
    Request,
)
from repro.serving.kv_pages import TRASH_FRAME
from test_paged_serving import (
    _model, _pick_bucket, assert_reference_stream, dense_reference)

pytestmark = pytest.mark.paged

# dense archs: MoE capacity dispatch is batch-composition-sensitive, so
# deepseek compares single requests in its own test
ZOO_SUBSET = ("qwen3-1.7b", "gemma2-27b", "qwen2.5-32b")
BUCKETS = (8, 16, 32)

def _engine(arch, **cfg_kw):
    cfg, _, params = _model(arch)
    kw = dict(batch_slots=2, max_seq=64, page_tokens=8,
              prefill_buckets=BUCKETS)
    kw.update(cfg_kw)
    return PagedServingEngine(cfg, params, PagedEngineConfig(**kw))


def _want(arch, prompt, max_new, *, B=2):
    """The dense reference's greedy stream of one request."""
    _, model, params = _model(arch)
    return dense_reference(model, params, prompt, max_new,
                           _pick_bucket(BUCKETS, len(prompt)), B=B,
                           max_seq=64)


# ======================================================================== #
# sweep vs the dense reference
# ======================================================================== #

@pytest.mark.parametrize("arch", ZOO_SUBSET)
def test_sweep_matches_dense_reference(arch):
    """Mixed prompt lengths with a mid-stream slot refill: the fused sweep
    over the page planes emits the dense reference's streams (up to ties),
    and the pool accounts the rows its epilogue committed."""
    cfg, _, _ = _model(arch)
    eng = _engine(arch)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
               for n in (3, 17, 8)]
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=list(p), max_new_tokens=6))
    got = eng.run()
    assert eng.metrics.prefills >= 2
    _, model, params = _model(arch)
    for i, p in enumerate(prompts):
        assert len(got[i]) == 6
        assert_reference_stream(model, params, p, got[i],
                                _pick_bucket(BUCKETS, len(p)), B=2,
                                max_seq=64)
    # the prompt pages and every decode step's fused commit
    decoded = sum(len(t) - 1 for t in got.values())
    assert eng.pool.metrics.bytes_hot_written >= (
        decoded * eng.pool.row_bytes)


def test_sweep_matches_dense_reference_mla():
    """deepseek MLA: absorbed decode over compressed-KV planes —
    multi-page, sub-page, and partial-tail lengths."""
    cfg, _, _ = _model("deepseek-v2-236b")
    for seed, plen in ((2, 19), (3, 5)):
        p = np.random.default_rng(seed).integers(
            1, cfg.vocab_size, size=plen).tolist()
        eng = _engine("deepseek-v2-236b")
        eng.submit(Request(rid=0, prompt=list(p), max_new_tokens=8))
        assert eng.run()[0] == _want("deepseek-v2-236b", p, 8), f"len {plen}"


def test_sweep_parity_mid_chunk_preempt_resume():
    """A high-priority arrival preempts a slot whose chunked prefill is
    still in flight; the victim later resumes from the cold tier and
    finishes its ladder. Both streams equal the dense reference."""
    cfg, _, _ = _model("qwen3-1.7b")
    long = np.random.default_rng(10).integers(
        1, cfg.vocab_size, size=20).tolist()
    hi = np.random.default_rng(11).integers(
        1, cfg.vocab_size, size=4).tolist()
    eng = _engine("qwen3-1.7b", batch_slots=1, policy="priority",
                  prefill_chunk_tokens=8)
    eng.submit(Request(rid=0, prompt=list(long), max_new_tokens=6,
                       priority=0))
    eng.step()                              # one 8-token chunk banked
    assert 0 in eng._chunk and eng._chunk[0]["filled"] == 8
    eng.submit(Request(rid=1, prompt=list(hi), max_new_tokens=3,
                       priority=2))
    got = eng.run()
    assert eng.metrics.preemptions == 1 and eng.metrics.readmissions == 1
    assert eng.pool.metrics.page_faults >= 1    # resumed through cold
    assert got[0] == _want("qwen3-1.7b", long, 6, B=1)
    assert got[1] == _want("qwen3-1.7b", hi, 3, B=1)


# ======================================================================== #
# the sweep streams only the pages the mask can keep
# ======================================================================== #

_P, _N_PAGES, _WINDOW = 8, 8, 19
_MAX_SEQ = _P * _N_PAGES
_LENGTHS = (0, 1, _P - 1, _P, _P + 1, _MAX_SEQ - 1)
_B = len(_LENGTHS)
# frames: 0 zeros, 1 NaN, then each slot's commit frame, then data pages
_ZERO, _NAN = 0, 1


def _page_tables(window):
    """(clean, poisoned) page tables over the frames above: slot b's page
    t < ceil(len/P) sits in data frame 2 + B + b * n_pages + t. Past the
    length the clean table points at the zero frame, the poisoned one at
    the NaN frame, which it also gives every page before the window's
    first."""
    clean = np.full((_B, _N_PAGES), _ZERO, np.int32)
    poisoned = np.full((_B, _N_PAGES), _NAN, np.int32)
    for b, n in enumerate(_LENGTHS):
        first = 0 if window is None else max(0, n - window + 1) // _P
        for t in range(-(-n // _P)):
            clean[b, t] = 2 + _B + b * _N_PAGES + t
            if t >= first:
                poisoned[b, t] = clean[b, t]
    return jnp.asarray(clean), jnp.asarray(poisoned)


def _frames(key, shape):
    """(L, NF, ...) bf16 planes: random, the zero frame zero, the NaN frame
    NaN."""
    x = jax.random.normal(key, shape, jnp.float32).astype(jnp.bfloat16)
    return x.at[:, _ZERO].set(0).at[:, _NAN].set(jnp.nan)


@pytest.mark.parametrize("kind,window", [("gqa", None), ("gqa", _WINDOW),
                                         ("mla", None)])
def test_sweep_never_reads_pages_past_the_mask(kind, window):
    """Every page-table entry the decode mask cannot keep (past ceil(len/P),
    before a window's first page) points at a NaN frame. A sweep that
    streamed it would poison its accumulator (0 * NaN); the sweep that
    skips it gives outputs bitwise equal to the same call on a clean table,
    and equal to the jnp reference there."""
    from repro.core import PULConfig
    from repro.kernels import (
        pul_paged_sweep_decode_attention,
        pul_paged_sweep_mla_decode_attention, ref)
    L, layer, NF = 2, 1, 2 + _B + _B * _N_PAGES
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    lengths = jnp.asarray(_LENGTHS, jnp.int32)
    frames = jnp.arange(2, 2 + _B, dtype=jnp.int32)
    offsets = lengths % _P
    clean, poisoned = _page_tables(window)
    cfg = PULConfig(distance=2)
    if kind == "gqa":
        H, K, W = 4, 2, 32
        q = jax.random.normal(ks[0], (_B, H, W), jnp.float32)
        kp = _frames(ks[1], (L, NF, K, _P, W))
        vp = _frames(ks[2], (L, NF, K, _P, W))
        kn = jax.random.normal(ks[3], (_B, K, W)).astype(jnp.bfloat16)
        vn = jax.random.normal(ks[4], (_B, K, W)).astype(jnp.bfloat16)

        def sweep(pt):
            return pul_paged_sweep_decode_attention(
                q, kp, vp, layer, pt, lengths, kn, vn, frames, offsets,
                cfg=cfg, window=window)[0]
        want = ref.paged_decode_ref(q, kp[layer], vp[layer], clean, lengths,
                                    kn, vn, window=window)
    else:
        H, C, R, scale = 4, 32, 16, 0.2
        qa = jax.random.normal(ks[0], (_B, H, C), jnp.float32)
        qr = jax.random.normal(ks[1], (_B, H, R), jnp.float32)
        cp = _frames(ks[2], (L, NF, _P, C))
        rp = _frames(ks[3], (L, NF, _P, R))
        cn = jax.random.normal(ks[4], (_B, C)).astype(jnp.bfloat16)
        rn = jax.random.normal(ks[5], (_B, R)).astype(jnp.bfloat16)

        def sweep(pt):
            return pul_paged_sweep_mla_decode_attention(
                qa, qr, cp, rp, layer, pt, lengths, cn, rn, frames, offsets,
                scale=scale, cfg=cfg)[0]
        want = ref.paged_mla_decode_ref(qa, qr, cp[layer], rp[layer], clean,
                                        lengths, cn, rn, scale=scale)
    got = np.asarray(sweep(poisoned))
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, np.asarray(sweep(clean)))
    np.testing.assert_allclose(got, np.asarray(want), rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("layer", ["first", "last"])
@pytest.mark.parametrize("kind", ["gqa", "mla"])
def test_sweep_epilogue_commits_only_the_tail_rows(kind, layer):
    """The fused epilogue of a sweep over a multi-layer plane writes each
    live slot's current row exactly at (layer, frame, offset) — lane
    padding included — and an idle slot's only at the trash frame: every
    other layer, frame and row of the planes stays bitwise as it was."""
    from repro.core import PULConfig
    from repro.kernels import (
        pul_paged_sweep_decode_attention,
        pul_paged_sweep_mla_decode_attention)
    L, P, NF, n_pages = 3, 8, 9, 2
    g = 0 if layer == "first" else L - 1
    ks = jax.random.split(jax.random.PRNGKey(9), 6)
    # slots 0 and 1 decode at lengths 5 and 11 (tail pages in frames 4
    # and 6); slot 2 is idle and commits to the trash frame
    lengths = jnp.asarray([5, 11, 0], jnp.int32)
    pt = jnp.asarray([[4, 0], [5, 6], [0, 0]], jnp.int32)
    frames = jnp.asarray([4, 6, TRASH_FRAME], jnp.int32)
    offsets = lengths % P
    live = ((0, 4, 5), (1, 6, 3))              # (slot, frame, offset)
    cfg = PULConfig(distance=2)

    def planes(key, shape):
        return jax.random.normal(key, shape, jnp.float32).astype(
            jnp.bfloat16)

    if kind == "gqa":
        H, K, hd, W = 4, 2, 16, 32
        q = jax.random.normal(ks[0], (3, H, hd), jnp.float32)
        before = (planes(ks[1], (L, NF, K, P, W)),
                  planes(ks[2], (L, NF, K, P, W)))
        new = (planes(ks[3], (3, K, hd)), planes(ks[4], (3, K, hd)))
        _, *after = pul_paged_sweep_decode_attention(
            q, *before, g, pt, lengths, *new, frames, offsets, cfg=cfg)
        row = lambda x: jnp.pad(x, ((0, 0), (0, W - hd)))   # (K, W)
        at = lambda f, o: (g, f, slice(None), o)
    else:
        H, kvr, dr, C, R = 4, 32, 8, 32, 16
        qa = jax.random.normal(ks[0], (3, H, kvr), jnp.float32)
        qr = jax.random.normal(ks[5], (3, H, dr), jnp.float32)
        before = (planes(ks[1], (L, NF, P, C)), planes(ks[2], (L, NF, P, R)))
        new = (planes(ks[3], (3, kvr)), planes(ks[4], (3, dr)))
        _, *after = pul_paged_sweep_mla_decode_attention(
            qa, qr, *before, g, pt, lengths, *new, frames, offsets,
            scale=0.2, cfg=cfg)
        at = lambda f, o: (g, f, o)
    for old, got, rows in zip(before, after, new):
        want = old
        for slot, f, o in live:
            r = rows[slot]
            if kind == "gqa":
                r = row(r)
            else:
                r = jnp.pad(r, (0, old.shape[-1] - r.shape[-1]))
            want = want.at[at(f, o)].set(r)
        got = np.asarray(got.astype(jnp.float32))
        want = np.asarray(want.astype(jnp.float32))
        # the trash frame of this layer may hold anything; nothing else
        keep = np.ones(got.shape[:2], bool)
        keep[g, TRASH_FRAME] = False
        np.testing.assert_array_equal(got[keep], want[keep])


def test_sweep_page_counter_matches_hand_count():
    """Two live slots of known lengths and one empty: prompts of 7 and 17
    tokens decode 3 steps each at lengths 7, 8, 9 and 17, 18, 19, so with
    pages of 8 the sweep streams 1 + 3, 1 + 3 and 2 + 3 pages a step (the
    empty slot none), out of 3 slots x 8 pages a step. A fourth tick
    decodes nothing and streams no page: it reads the tokens of the third
    step, which was still in flight."""
    from repro.obs import Tracer
    cfg, _, params = _model("qwen3-1.7b")
    tracer = Tracer()
    eng = PagedServingEngine(cfg, params, PagedEngineConfig(
        batch_slots=3, max_seq=64, page_tokens=8, prefill_buckets=(8, 16, 32)),
        tracer=tracer)
    rng = np.random.default_rng(3)
    for rid, n in enumerate((7, 17)):
        eng.submit(Request(
            rid=rid, prompt=rng.integers(1, cfg.vocab_size, size=n).tolist(),
            max_new_tokens=4))
    eng.run()
    assert [sp.args["pages"] for sp in tracer.spans()
            if sp.name == "serve.decode"] == [4, 4, 5, 0]
    assert eng.metrics.decode_steps == 3
    assert eng.metrics.sweep_pages == 13
    assert eng.snapshot()["sweep_page_share"] == 13 / (3 * 3 * 8)


# ======================================================================== #
# zero-copy properties of the v2 layout
# ======================================================================== #

# jaxpr primitives that move or rearrange data: none may appear in a
# layer_view trace — a true view is static leading-axis indexing only
_COPYING_PRIMS = {"gather", "concatenate", "transpose", "dynamic_slice",
                  "scatter", "reshape", "copy", "convert_element_type"}


@settings(max_examples=12, deadline=None)
@given(arch=st.sampled_from(ZOO_SUBSET + ("deepseek-v2-236b",)),
       layer=st.integers(0, 63), n_frames=st.integers(2, 9))
def test_layer_view_never_copies(arch, layer, n_frames):
    """Property: for any arch, layer, and frame count, layer_view's jaxpr
    is pure static slicing — no gather, concat, transpose, or reshape: a
    layer's pages are the pool's own buffer, never a per-step repack."""
    cfg = get_config(arch).reduced()
    layout = PackedKVLayout(cfg, 1, 8)
    layers = max(e.layers for e in layout.entries)
    g = layer % layers
    planes = layout.init_planes(n_frames, 8, jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda p: layout.layer_view(p, g))(planes)
    prims = {str(eqn.primitive) for eqn in jaxpr.jaxpr.eqns}
    assert not prims & _COPYING_PRIMS, prims


def test_donated_plane_commit_aliases_in_place():
    """The engine's sweep entry point donates the planes
    (``donate_argnums``): a jitted commit must reuse the input buffers —
    the donated arrays die and the outputs sit at the same addresses. This
    is the runtime half of the zero-copy claim (and what lint rule PUL107
    enforces statically)."""
    cfg = get_config("qwen3-1.7b").reduced()
    layout = PackedKVLayout(cfg, 1, 8)
    planes = layout.init_planes(4, 8, jnp.bfloat16)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def commit(pl):
        return {k: v.at[(0,) * v.ndim].set(1.0) for k, v in pl.items()}

    ptrs = {k: v.unsafe_buffer_pointer() for k, v in planes.items()}
    out = commit(planes)
    assert all(v.is_deleted() for v in planes.values())
    assert {k: v.unsafe_buffer_pointer() for k, v in out.items()} == ptrs
