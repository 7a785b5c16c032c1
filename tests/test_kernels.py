"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import IssueStrategy, PULConfig
from repro.kernels import (
    pul_attention,
    pul_filter,
    pul_gather,
    pul_matmul,
    pul_paged_sweep_decode_attention,
    pul_paged_sweep_mla_decode_attention,
    pul_sum,
    ref,
)
from repro.serving.kv_pages import RESERVED_FRAMES, TRASH_FRAME

pytestmark = pytest.mark.kernels

KEY = jax.random.PRNGKey(0)


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


# --------------------------------------------------------------------- sum
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("distance,strategy", [
    (1, IssueStrategy.BATCH), (4, IssueStrategy.BATCH),
    (3, IssueStrategy.SEQUENTIAL), (16, IssueStrategy.BATCH)])
@pytest.mark.parametrize("rows_per_req", [1, 4])
def test_pul_sum(dtype, distance, strategy, rows_per_req):
    R, W, n = 32, 128, 18
    data = _rand(KEY, (R * rows_per_req, W), dtype)
    trace = jax.random.randint(jax.random.PRNGKey(1), (n,), 0, R, jnp.int32)
    cfg = PULConfig(distance=distance, strategy=strategy)
    got = pul_sum(data, trace, cfg=cfg, rows_per_req=rows_per_req)
    rows = jnp.concatenate([jnp.arange(rows_per_req) + t * rows_per_req
                            for t in trace])
    want = ref.sum_ref(data, rows)
    np.testing.assert_allclose(got, want, rtol=3e-2 if dtype == jnp.bfloat16 else 1e-5)


# ------------------------------------------------------------------ gather
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
@pytest.mark.parametrize("distance", [1, 2, 8])
def test_pul_gather(dtype, distance):
    R, W, n = 64, 256, 40
    if dtype == jnp.int32:
        table = jax.random.randint(KEY, (R, W), -100, 100, jnp.int32)
    else:
        table = _rand(KEY, (R, W), dtype)
    trace = jax.random.randint(jax.random.PRNGKey(2), (n,), 0, R, jnp.int32)
    got = pul_gather(table, trace, cfg=PULConfig(distance=distance))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref.gather_ref(table, trace)))


# ------------------------------------------------------------------ matmul
@pytest.mark.parametrize("dtype,rtol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("shape,blocks", [
    ((128, 128, 128), (64, 64, 64)),
    ((128, 256, 384), (64, 128, 128)),
    ((64, 512, 128), (64, 64, 128)),
])
@pytest.mark.parametrize("distance", [1, 3])
def test_pul_matmul(dtype, rtol, shape, blocks, distance):
    M, K, N = shape
    bm, bk, bn = blocks
    a = _rand(KEY, (M, K), dtype)
    b = _rand(jax.random.PRNGKey(3), (K, N), dtype)
    got = pul_matmul(a, b, cfg=PULConfig(distance=distance), bm=bm, bk=bk, bn=bn)
    want = ref.matmul_ref(a, b)
    np.testing.assert_allclose(got, want, rtol=rtol, atol=rtol * 10)


# --------------------------------------------------------------- attention
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-4), (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("gqa", [1, 4])
@pytest.mark.parametrize("softcap,window", [(None, None), (8.0, None), (None, 24)])
def test_pul_attention(dtype, tol, gqa, softcap, window):
    B, K, T, S, hd = 2, 2, 64, 64, 32
    H = K * gqa
    q = _rand(KEY, (B, H, T, hd), dtype) * 0.3
    k = _rand(jax.random.PRNGKey(4), (B, K, S, hd), dtype) * 0.3
    v = _rand(jax.random.PRNGKey(5), (B, K, S, hd), dtype)
    got = pul_attention(q, k, v, cfg=PULConfig(distance=2), bt=32, bs=16,
                        softcap=softcap, window=window)
    want = ref.attention_ref(q, k, v, softcap=softcap, window=window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


def test_pul_attention_uneven_kv_tail():
    """S not a multiple of bs exercises the in-kernel tail mask."""
    B, H, T, S, hd = 1, 2, 32, 48, 16
    q = _rand(KEY, (B, H, T, hd), jnp.float32) * 0.5
    k = _rand(jax.random.PRNGKey(6), (B, H, S, hd), jnp.float32) * 0.5
    v = _rand(jax.random.PRNGKey(7), (B, H, S, hd), jnp.float32)
    got = pul_attention(q, k, v, bt=32, bs=32)
    want = ref.attention_ref(q, k, v)
    np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)


# ------------------------------------------------------------------ filter
@pytest.mark.parametrize("materialize", [False, True])
@pytest.mark.parametrize("distance", [2, 8])
def test_pul_filter(materialize, distance):
    N, W = 512, 64
    data = _rand(KEY, (N, W), jnp.float32)
    got = pul_filter(data, 0.25, cfg=PULConfig(distance=distance),
                     rows_per_block=128, materialize=materialize)
    if materialize:
        want = ref.filter_materialize_ref(data, 0.25)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        want = ref.filter_ref(data, 0.25)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------- property sweep
@settings(max_examples=20, deadline=None)
@given(
    n=st.integers(2, 40),
    rows=st.integers(8, 64),
    d=st.integers(1, 8),
    seq=st.booleans(),
)
def test_gather_roundtrip_property(n, rows, d, seq):
    """gather(table, trace) == table[trace] for arbitrary traces/knobs."""
    table = jax.random.normal(jax.random.PRNGKey(n), (rows, 128), jnp.float32)
    trace = jax.random.randint(jax.random.PRNGKey(n + 1), (n,), 0, rows, jnp.int32)
    cfg = PULConfig(distance=d, strategy=(IssueStrategy.SEQUENTIAL if seq
                                          else IssueStrategy.BATCH))
    got = pul_gather(table, trace, cfg=cfg)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(table[trace]))


# --------------------------------------------------------------- paged paths
def _one_layer(*pages):
    """Each (NF, ...) frame array as a one-layer plane (1, 2 + NF, ...)
    behind the pool's reserved zero and trash frames."""
    return [jnp.concatenate([jnp.zeros((RESERVED_FRAMES, *p.shape[1:]),
                                       p.dtype), p])[None] for p in pages]


def _trash_commits(B):
    """Commit coordinates that send every slot's row to the trash frame."""
    return (jnp.full((B,), TRASH_FRAME, jnp.int32),
            jnp.zeros((B,), jnp.int32))


def _gqa_sweep(q, kp, vp, pt, lengths, kn, vn, **kw):
    """The GQA sweep over frames kp/vp of a one-layer plane."""
    kpl, vpl = _one_layer(kp, vp)
    return pul_paged_sweep_decode_attention(
        q, kpl, vpl, 0, pt + RESERVED_FRAMES, lengths, kn, vn,
        *_trash_commits(q.shape[0]), **kw)[0]


@pytest.mark.parametrize("gqa", [1, 4])
@pytest.mark.parametrize("distance", [1, 3])
def test_pul_paged_sweep_decode_attention(gqa, distance):
    """Decode attention straight over scattered pages == dense oracle over
    the assembled contiguous cache, the current token at its position
    (mixed fill levels incl. partial pages)."""
    B, K, P, npg, hd = 2, 2, 8, 4, 16
    H, S, NP = K * gqa, P * npg, 11
    kp = _rand(jax.random.PRNGKey(1), (NP, K, P, hd), jnp.float32) * 0.4
    vp = _rand(jax.random.PRNGKey(2), (NP, K, P, hd), jnp.float32)
    kn = _rand(jax.random.PRNGKey(4), (B, K, hd), jnp.float32) * 0.4
    vn = _rand(jax.random.PRNGKey(6), (B, K, hd), jnp.float32)
    pt = jnp.asarray(np.random.default_rng(0).permutation(NP)[:B * npg]
                     .reshape(B, npg), jnp.int32)
    q = _rand(jax.random.PRNGKey(3), (B, H, hd), jnp.float32) * 0.4
    lengths = jnp.asarray([S, S // 2 + 3], jnp.int32)
    got = _gqa_sweep(q, kp, vp, pt, lengths, kn, vn,
                     cfg=PULConfig(distance=distance))

    def dense(pages, new):              # (B, K, S + 1, hd), new at length
        d = pages[pt].transpose(0, 2, 1, 3, 4).reshape(B, K, S, hd)
        d = jnp.pad(d, ((0, 0), (0, 0), (0, 1), (0, 0)))
        return d.at[jnp.arange(B), :, lengths].set(new)
    want = ref.decode_attention_ref(q, dense(kp, kn), dense(vp, vn),
                                    lengths + 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("window", [None, 11, 24])
@pytest.mark.parametrize("softcap", [None, 8.0])
def test_pul_paged_sweep_decode_attention_window_and_self_merge(window,
                                                                softcap):
    """Sliding-window masking + current-token (k_new, v_new) merge: the
    kernel over scattered pages == dense oracle over [assembled cache ;
    current token], with the window anchored at the query position."""
    B, K, P, npg, hd, gqa = 2, 2, 8, 4, 16, 2
    H, S, NP = K * gqa, P * npg, 9
    kp = _rand(jax.random.PRNGKey(1), (NP, K, P, hd), jnp.float32) * 0.4
    vp = _rand(jax.random.PRNGKey(2), (NP, K, P, hd), jnp.float32)
    kn = _rand(jax.random.PRNGKey(3), (B, K, hd), jnp.float32) * 0.4
    vn = _rand(jax.random.PRNGKey(4), (B, K, hd), jnp.float32)
    q = _rand(jax.random.PRNGKey(5), (B, H, hd), jnp.float32) * 0.4
    pt = jnp.asarray(np.random.default_rng(0).permutation(NP)[:B * npg]
                     .reshape(B, npg) % NP, jnp.int32)
    lengths = jnp.asarray([S - 2, 13], jnp.int32)
    got = _gqa_sweep(q, kp, vp, pt, lengths, kn, vn,
                     cfg=PULConfig(distance=2), softcap=softcap,
                     window=window)
    want = ref.paged_decode_ref(q, kp, vp, pt, lengths, kn, vn,
                                softcap=softcap, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("distance", [1, 3])
def test_pul_paged_sweep_mla_decode_attention(distance):
    """Absorbed MLA decode over compressed-KV pages == dense oracle (the
    compressed cache doubles as the value stream), mixed fill levels."""
    B, H, kvr, dr, P, npg, NP = 2, 4, 32, 8, 8, 4, 11
    S = P * npg
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    qa = _rand(ks[0], (B, H, kvr), jnp.float32) * 0.4
    qr = _rand(ks[1], (B, H, dr), jnp.float32) * 0.4
    cp = _rand(ks[2], (NP, P, kvr), jnp.float32) * 0.4
    rp = _rand(ks[3], (NP, P, dr), jnp.float32) * 0.4
    cn = _rand(ks[4], (B, kvr), jnp.float32) * 0.4
    rn = _rand(ks[5], (B, dr), jnp.float32) * 0.4
    pt = jnp.asarray(np.random.default_rng(1).permutation(NP)[:B * npg]
                     .reshape(B, npg), jnp.int32)
    lengths = jnp.asarray([S, 11], jnp.int32)
    scale = 1.0 / (kvr + dr) ** 0.5
    got = pul_paged_sweep_mla_decode_attention(
        qa, qr, *_one_layer(cp, rp), 0, pt + RESERVED_FRAMES, lengths, cn,
        rn, *_trash_commits(B), scale=scale,
        cfg=PULConfig(distance=distance))[0]
    want = ref.paged_mla_decode_ref(qa, qr, cp, rp, pt, lengths, cn, rn,
                                    scale=scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=3e-4)


def test_pul_paged_sweep_decode_attention_empty_cache():
    """length 0: only the current token is visible (empty-prompt decode)."""
    B, K, P, npg, hd = 1, 2, 8, 2, 16
    kp = _rand(jax.random.PRNGKey(1), (3, K, P, hd), jnp.float32)
    vp = _rand(jax.random.PRNGKey(2), (3, K, P, hd), jnp.float32)
    kn = _rand(jax.random.PRNGKey(3), (B, K, hd), jnp.float32)
    vn = _rand(jax.random.PRNGKey(4), (B, K, hd), jnp.float32)
    q = _rand(jax.random.PRNGKey(5), (B, K, hd), jnp.float32)
    pt = jnp.zeros((B, npg), jnp.int32)
    got = _gqa_sweep(q, kp, vp, pt, jnp.zeros((B,), jnp.int32), kn, vn)
    # softmax over a single visible position == v_new itself
    np.testing.assert_allclose(np.asarray(got), np.asarray(vn),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------- decode attention
@pytest.mark.parametrize("gqa", [1, 4])
@pytest.mark.parametrize("distance", [1, 4])
@pytest.mark.parametrize("softcap", [None, 10.0])
def test_pul_decode_attention(gqa, distance, softcap):
    from repro.kernels import pul_decode_attention
    B, K, S, hd = 2, 2, 96, 32
    H = K * gqa
    q = _rand(KEY, (B, H, hd), jnp.float32) * 0.4
    k = _rand(jax.random.PRNGKey(8), (B, K, S, hd), jnp.float32) * 0.4
    v = _rand(jax.random.PRNGKey(9), (B, K, S, hd), jnp.float32)
    length = jnp.asarray([S, S // 2], jnp.int32)     # one full, one partial
    got = pul_decode_attention(q, k, v, length, cfg=PULConfig(distance=distance),
                               bs=32, softcap=softcap)
    want = ref.decode_attention_ref(q, k, v, length, softcap=softcap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=3e-4)
