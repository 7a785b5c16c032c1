"""Fused single-sweep paged decode: parity + zero-copy properties.

The KVStoreLayout redesign's satellite contract:

  * the fused sweep (``sweep_decode=True``, the default) produces BITWISE
    identical token streams to the per-layer kernel path
    (``sweep_decode=False``) across the zoo subset AND deepseek MLA,
    including a preempt/resume landing mid-chunked-prefill;
  * ``layer_view`` never copies: its jaxpr contains no data-movement
    primitive, and a jitted plane commit with donated planes aliases the
    input buffers in place (CPU buffer donation — the same mechanism the
    engine's ``_sweep_decode`` uses via ``donate_argnums``);
  * both sweeps stream only the pages the decode mask can keep (NaN
    frames everywhere else change no output), and the engine counts the
    pages they stream;
  * the deprecated v1 surface (``page_views`` / ``pack_new_rows``) warns
    ``PendingDeprecationWarning`` and no in-repo caller reaches it.
"""
import dataclasses
import functools
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.models import build_model
from repro.serving import (
    PackedKVLayout,
    PagedEngineConfig,
    PagedServingEngine,
    Request,
)

pytestmark = pytest.mark.paged

# dense archs (MoE capacity dispatch is batch-composition-sensitive, but
# both paths below run IDENTICAL schedules, so deepseek still compares
# bitwise in its own test)
ZOO_SUBSET = ("qwen3-1.7b", "gemma2-27b", "qwen2.5-32b")

_MODELS = {}


def _model(arch):
    if arch not in _MODELS:
        cfg = get_config(arch).reduced()
        m = build_model(dataclasses.replace(cfg, paged_kv=True))
        params = m.init(jax.random.PRNGKey(0))
        _MODELS[arch] = (cfg, m, params)
    return _MODELS[arch]


def _engine(arch, sweep, **cfg_kw):
    cfg, _, params = _model(arch)
    kw = dict(batch_slots=2, max_seq=64, page_tokens=8,
              prefill_buckets=(8, 16, 32), use_paged_kernel=True,
              sweep_decode=sweep)
    kw.update(cfg_kw)
    return PagedServingEngine(cfg, params, PagedEngineConfig(**kw))


def _run_both(arch, drive, **cfg_kw):
    """Run the same driver on a fused-sweep and a per-layer engine; return
    both engines and their token streams."""
    outs, engines = [], []
    for sweep in (True, False):
        eng = _engine(arch, sweep, **cfg_kw)
        outs.append(drive(eng))
        engines.append(eng)
    return engines, outs


# ======================================================================== #
# sweep vs per-layer path: bitwise stream parity
# ======================================================================== #

@pytest.mark.parametrize("arch", ZOO_SUBSET)
def test_sweep_matches_per_layer_path(arch):
    """Mixed prompt lengths with a mid-stream slot refill: the single-sweep
    fused decode and the per-layer launch loop are the same math over the
    same planes, so the streams must match token for token."""
    cfg, _, _ = _model(arch)

    def drive(eng):
        rng = np.random.default_rng(7)
        for i, n in enumerate((3, 17, 8)):
            eng.submit(Request(
                rid=i, prompt=rng.integers(1, cfg.vocab_size, size=n).tolist(),
                max_new_tokens=6))
        return eng.run()

    (sweep_eng, ref_eng), (got_sweep, got_ref) = _run_both(arch, drive)
    assert got_sweep == got_ref
    assert sweep_eng.metrics.prefills == ref_eng.metrics.prefills
    # and the sweep really took the fused path: the eager scatter is off,
    # yet the pool accounted the same committed row bytes
    assert sweep_eng.pool.metrics.bytes_hot_written \
        == ref_eng.pool.metrics.bytes_hot_written > 0


def test_sweep_matches_per_layer_path_mla():
    """deepseek MLA: absorbed decode over compressed-KV planes, fused sweep
    vs per-layer — multi-page, sub-page, and partial-tail lengths."""
    cfg, _, _ = _model("deepseek-v2-236b")
    for seed, plen in ((2, 19), (3, 5)):
        p = np.random.default_rng(seed).integers(
            1, cfg.vocab_size, size=plen).tolist()

        def drive(eng):
            eng.submit(Request(rid=0, prompt=list(p), max_new_tokens=8))
            return eng.run()

        _, (got_sweep, got_ref) = _run_both("deepseek-v2-236b", drive)
        assert got_sweep == got_ref, f"len {plen}"


def test_sweep_parity_mid_chunk_preempt_resume():
    """A high-priority arrival preempts a slot whose chunked prefill is
    still in flight; the victim later resumes from the cold tier and
    finishes its ladder. Both decode paths must walk the identical
    schedule and emit identical streams."""
    cfg, _, _ = _model("qwen3-1.7b")
    long = np.random.default_rng(10).integers(
        1, cfg.vocab_size, size=20).tolist()
    hi = np.random.default_rng(11).integers(
        1, cfg.vocab_size, size=4).tolist()

    def drive(eng):
        eng.submit(Request(rid=0, prompt=list(long), max_new_tokens=6,
                           priority=0))
        eng.step()                          # one 8-token chunk banked
        assert 0 in eng._chunk and eng._chunk[0]["filled"] == 8
        eng.submit(Request(rid=1, prompt=list(hi), max_new_tokens=3,
                           priority=2))
        return eng.run()

    engines, (got_sweep, got_ref) = _run_both(
        "qwen3-1.7b", drive, batch_slots=1, policy="priority",
        prefill_chunk_tokens=8)
    assert got_sweep == got_ref
    for eng in engines:
        assert eng.metrics.preemptions == 1 and eng.metrics.readmissions == 1
        assert eng.pool.metrics.page_faults >= 1    # resumed through cold


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
    skips it gives outputs bitwise equal to the same call on a clean table
    and to the per-layer kernel there."""
    from repro.core import PULConfig
    from repro.kernels import (
        pul_paged_decode_attention, pul_paged_mla_decode_attention,
        pul_paged_sweep_decode_attention,
        pul_paged_sweep_mla_decode_attention)
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
        per_layer = pul_paged_decode_attention(
            q, kp[layer], vp[layer], clean, lengths, cfg=cfg, window=window,
            k_new=kn, v_new=vn)
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
        per_layer = pul_paged_mla_decode_attention(
            qa, qr, cp[layer], rp[layer], clean, lengths, cn, rn,
            scale=scale, cfg=cfg)
    got = np.asarray(sweep(poisoned))
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, np.asarray(sweep(clean)))
    np.testing.assert_array_equal(got, np.asarray(per_layer))


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
        batch_slots=3, max_seq=64, page_tokens=8, prefill_buckets=(8, 16, 32),
        use_paged_kernel=True), tracer=tracer)
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
    is pure static slicing — no gather, concat, transpose, or reshape. This
    is the structural guarantee that the per-layer kernel path launches on
    the pool's own buffers rather than per-step repacks."""
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


# ======================================================================== #
# deprecated v1 surface
# ======================================================================== #

def test_deprecated_v1_api_warns():
    cfg = get_config("qwen3-1.7b").reduced()
    layout = PackedKVLayout(cfg, 1, 16)
    store = jnp.zeros((3, 16, layout.features), jnp.bfloat16)
    # the shims warn before touching the tree, so a None tree suffices
    with pytest.warns(PendingDeprecationWarning, match="page_views"):
        try:
            layout.page_views(None, store)
        except (KeyError, TypeError, AttributeError):
            pass
    with pytest.warns(PendingDeprecationWarning, match="pack_new_rows"):
        try:
            layout.pack_new_rows(None)
        except (KeyError, TypeError, AttributeError):
            pass


def test_no_in_repo_caller_uses_deprecated_v1_api():
    """Static closure of the migration: outside kv_pages.py itself (the
    definitions + their deprecation tests' fixtures), nothing in the repo
    calls .page_views( or .pack_new_rows(."""
    root = Path(__file__).resolve().parent.parent
    offenders = []
    for sub in ("src", "benchmarks", "examples", "tools"):
        for f in sorted((root / sub).rglob("*.py")):
            if f.name == "kv_pages.py":
                continue
            text = f.read_text()
            for needle in (".page_views(", ".pack_new_rows("):
                if needle in text:
                    offenders.append(f"{f.relative_to(root)}: {needle}")
    assert offenders == [], offenders
