"""Flash attention with PUL-streamed KV (causal, GQA, window, softcap).

The TPU-native adaptation of the paper's idea applied to the dominant
memory-bound op of LM serving/training: query tiles live in VMEM (delivered
by the standard Pallas pipeline), while the long KV stream — the paper's
"dataset in slow memory" — is pulled through a distance-d preload ring with
online-softmax compute interleaved against in-flight DMAs. Sliding-window
layers simply bound the streamed range (gemma2/3).

Layout: q (B, H, T, hd); k/v (B, K, S, hd); GQA mapping h -> h // (H/K) is
done by the kv index_map inside the kernel (no host-side repeat).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import (
    PULConfig, PreloadStream, pul_loop, ring_scratch, interpret_mode)

NEG_INF = -2.0e38


def _kernel(q_vmem, k_hbm, v_hbm, o_vmem, kbuf, ksems, vbuf, vsems,
            m_scr, l_scr, acc_scr, *, cfg: PULConfig, bt: int, bs: int,
            ns: int, S: int, T: int, group: int, scale: float,
            softcap: Optional[float], window: Optional[int], causal: bool):
    b = pl.program_id(0)
    h = pl.program_id(1)
    tq = pl.program_id(2)
    kv_h = h // group

    k_st = PreloadStream(k_hbm, kbuf, ksems,
                         index_map=lambda t: (b, kv_h, t * bs, 0),
                         cfg=cfg, n_blocks=ns)
    v_st = PreloadStream(v_hbm, vbuf, vsems,
                         index_map=lambda t: (b, kv_h, t * bs, 0),
                         cfg=cfg, n_blocks=ns)

    m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
    l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    q = q_vmem[0, 0].astype(jnp.float32)                 # (bt, hd)
    # absolute query positions (queries end-aligned with keys: offset S - T)
    iq = tq * bt + jax.lax.iota(jnp.int32, bt) + (S - T)

    def body(t, views, carry):
        kt = views[0][0, 0].astype(jnp.float32)          # (bs, hd)
        vt = views[1][0, 0].astype(jnp.float32)
        logits = jnp.dot(q, kt.T, preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            logits = softcap * jnp.tanh(logits / softcap)
        jk = t * bs + jax.lax.iota(jnp.int32, bs)
        msk = jk[None, :] < S
        if causal:
            msk &= jk[None, :] <= iq[:, None]
        if window is not None:
            msk &= jk[None, :] > iq[:, None] - window
        logits = jnp.where(msk, logits, NEG_INF)
        bmax = jnp.max(logits, axis=-1, keepdims=True)   # (bt,1)
        new_m = jnp.maximum(m_scr[...], bmax)
        corr = jnp.exp(m_scr[...] - new_m)
        p = jnp.exp(logits - new_m)
        m_scr[...] = new_m
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jnp.dot(
            p, vt, preferred_element_type=jnp.float32)
        return carry

    pul_loop(ns, [k_st, v_st], body, 0, cfg)
    out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
    o_vmem[0, 0] = out.astype(o_vmem.dtype)


def _pad_lanes(x: jax.Array, width: int) -> jax.Array:
    """Zero-pad the minor dim of `x` to `width`. Page planes store their
    features lane-padded (`serving.kv_pages.KVStoreLayout`); the zero lanes
    add exact zeros to every dot product, so padding changes no result."""
    pad = width - x.shape[-1]
    if pad == 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def _merge_row(page_ref, row, offset):
    """Overwrite row `offset` of a (P, W) VMEM page with `row` ((1, W)
    float32). A whole-tile select: Mosaic neither slices one row out of a
    packed bf16 tile nor DMAs one, so the fused commit rewrites the page."""
    page = page_ref[...]
    at = jax.lax.broadcasted_iota(jnp.int32, page.shape, 0) == offset
    page_ref[...] = jnp.where(at, row, page.astype(jnp.float32)).astype(
        page_ref.dtype)


def _paged_decode_kernel(pt_smem, len_smem, q_vmem, *rest, cfg: PULConfig,
                         P: int, n_pages: int, scale: float,
                         softcap: Optional[float], window: Optional[int],
                         has_new: bool):
    if has_new:
        knew_vmem, vnew_vmem, k_hbm, v_hbm, o_vmem, \
            kbuf, ksems, vbuf, vsems = rest
    else:
        k_hbm, v_hbm, o_vmem, kbuf, ksems, vbuf, vsems = rest
    b = pl.program_id(0)
    kv_h = pl.program_id(1)
    length = len_smem[b]

    # the page table IS the preload trace: block t of the stream is whatever
    # physical page the slot's logical page t maps to (random access in slow
    # memory, sequential consumption in the ring — the paper's Exp. 2 trace)
    k_st = PreloadStream(k_hbm, kbuf, ksems,
                         index_map=lambda t: (pt_smem[b, t], kv_h, 0, 0),
                         cfg=cfg, n_blocks=n_pages)
    v_st = PreloadStream(v_hbm, vbuf, vsems,
                         index_map=lambda t: (pt_smem[b, t], kv_h, 0, 0),
                         cfg=cfg, n_blocks=n_pages)

    q = q_vmem[0, 0].astype(jnp.float32)                 # (G, hd)

    def _cap(logits):
        if softcap is not None:
            return softcap * jnp.tanh(logits / softcap)
        return logits

    def body(t, views, carry):
        m, l, acc = carry
        kt = views[0][0, 0].astype(jnp.float32)          # (P, hd)
        vt = views[1][0, 0].astype(jnp.float32)
        logits = _cap(
            jnp.dot(q, kt.T, preferred_element_type=jnp.float32) * scale)
        jk = t * P + jax.lax.iota(jnp.int32, P)
        msk = jk < length
        if window is not None:
            # the incoming query sits at absolute position `length`; cached
            # token jk is visible iff jk > length - window
            msk &= jk > length - window
        logits = jnp.where(msk[None, :], logits, NEG_INF)
        bmax = jnp.max(logits, axis=-1, keepdims=True)
        new_m = jnp.maximum(m, bmax)
        corr = jnp.exp(m - new_m)
        p = jnp.exp(logits - new_m)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jnp.dot(p, vt, preferred_element_type=jnp.float32)
        return new_m, l, acc

    G, hd = q.shape
    init = (jnp.full((G, 1), NEG_INF, jnp.float32),
            jnp.zeros((G, 1), jnp.float32),
            jnp.zeros((G, hd), jnp.float32))
    m, l, acc = pul_loop(n_pages, [k_st, v_st], body, init, cfg)
    if has_new:
        # fold in the current token's K/V (not yet written to any page);
        # it is always causally visible and always inside the window
        kn = knew_vmem[0, 0].astype(jnp.float32)         # (1, hd)
        vn = vnew_vmem[0, 0].astype(jnp.float32)
        ls = _cap(jnp.dot(q, kn.T, preferred_element_type=jnp.float32)
                  * scale)                               # (G, 1)
        new_m = jnp.maximum(m, ls)
        corr = jnp.exp(m - new_m)
        p = jnp.exp(ls - new_m)
        l = l * corr + p
        acc = acc * corr + jnp.dot(p, vn, preferred_element_type=jnp.float32)
    o_vmem[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_vmem.dtype)


def pul_paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                               v_pages: jax.Array, page_tables: jax.Array,
                               lengths, *, cfg: PULConfig = PULConfig(),
                               scale: Optional[float] = None,
                               softcap: Optional[float] = None,
                               window: Optional[int] = None,
                               k_new: Optional[jax.Array] = None,
                               v_new: Optional[jax.Array] = None,
                               interpret: Optional[bool] = None) -> jax.Array:
    """Decode attention straight over a paged KV store (serving hot path).

    q: (B, H, hd) one query token per slot; k_pages/v_pages: (NP, K, P, W)
    physical page frames (P tokens per page, W >= hd lanes, zero beyond
    hd); page_tables: (B, n_pages) int32 physical page id of each slot's
    logical page; lengths: (B,) valid tokens per slot. Returns (B, H, hd).

    `window` bounds the visible range to the last `window` tokens relative to
    the incoming query at position `lengths[b]` (sliding-window layers).
    `k_new`/`v_new` ((B, K, hd)) carry the CURRENT token's K/V — not yet
    written to any page — and are folded into the online softmax after the
    page stream, so the engine can run attention before the page write-back.

    The kernel never materializes a contiguous KV view: pages stream from
    slow memory through a distance-d preload ring, addressed by the SMEM
    page table — software paging *is* the trace-driven preload of the paper.
    """
    B, H, hd = q.shape
    NP, K, P, W = k_pages.shape
    _, n_pages = page_tables.shape
    assert H % K == 0
    G = H // K
    has_new = k_new is not None
    assert (v_new is not None) == has_new, "k_new/v_new come as a pair"
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    lengths = jnp.asarray(lengths, jnp.int32).reshape(B)
    qg = _pad_lanes(q, W).reshape(B, K, G, W)
    kern = functools.partial(_paged_decode_kernel, cfg=cfg, P=P,
                             n_pages=n_pages, scale=scale, softcap=softcap,
                             window=window, has_new=has_new)
    new_specs, new_args = [], []
    if has_new:
        new_specs = [pl.BlockSpec((1, 1, 1, W), lambda b, h: (b, h, 0, 0)),
                     pl.BlockSpec((1, 1, 1, W), lambda b, h: (b, h, 0, 0))]
        new_args = [_pad_lanes(k_new, W).reshape(B, K, 1, W),
                    _pad_lanes(v_new, W).reshape(B, K, 1, W)]
    out = pl.pallas_call(
        kern,
        grid=(B, K),
        out_shape=jax.ShapeDtypeStruct((B, K, G, W), q.dtype),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, G, W), lambda b, h: (b, h, 0, 0)),
            *new_specs,
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, 1, G, W), lambda b, h: (b, h, 0, 0)),
        scratch_shapes=[
            *ring_scratch(cfg, (1, 1, P, W), k_pages.dtype),
            *ring_scratch(cfg, (1, 1, P, W), v_pages.dtype),
        ],
        interpret=interpret_mode(interpret),
    )(page_tables.astype(jnp.int32), lengths, qg, *new_args,
      k_pages, v_pages)
    return out.reshape(B, H, W)[..., :hd]


def _paged_mla_decode_kernel(pt_smem, len_smem, qa_vmem, qr_vmem, cnew_vmem,
                             rnew_vmem, ckv_hbm, kr_hbm, o_vmem,
                             cbuf, csems, rbuf, rsems, *, cfg: PULConfig,
                             P: int, n_pages: int, scale: float):
    b = pl.program_id(0)
    length = len_smem[b]

    c_st = PreloadStream(ckv_hbm, cbuf, csems,
                         index_map=lambda t: (pt_smem[b, t], 0, 0),
                         cfg=cfg, n_blocks=n_pages)
    r_st = PreloadStream(kr_hbm, rbuf, rsems,
                         index_map=lambda t: (pt_smem[b, t], 0, 0),
                         cfg=cfg, n_blocks=n_pages)

    qa = qa_vmem[0].astype(jnp.float32)                  # (H, C)
    qr = qr_vmem[0].astype(jnp.float32)                  # (H, R)

    def body(t, views, carry):
        m, l, acc = carry
        ct = views[0][0].astype(jnp.float32)             # (P, C)
        rt = views[1][0].astype(jnp.float32)             # (P, R)
        logits = (jnp.dot(qa, ct.T, preferred_element_type=jnp.float32)
                  + jnp.dot(qr, rt.T, preferred_element_type=jnp.float32)
                  ) * scale                              # (H, P)
        jk = t * P + jax.lax.iota(jnp.int32, P)
        logits = jnp.where((jk < length)[None, :], logits, NEG_INF)
        bmax = jnp.max(logits, axis=-1, keepdims=True)
        new_m = jnp.maximum(m, bmax)
        corr = jnp.exp(m - new_m)
        p = jnp.exp(logits - new_m)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        # MLA: the compressed cache IS the value stream (absorbed decode)
        acc = acc * corr + jnp.dot(p, ct, preferred_element_type=jnp.float32)
        return new_m, l, acc

    H, C = qa.shape
    init = (jnp.full((H, 1), NEG_INF, jnp.float32),
            jnp.zeros((H, 1), jnp.float32),
            jnp.zeros((H, C), jnp.float32))
    m, l, acc = pul_loop(n_pages, [c_st, r_st], body, init, cfg)
    # current token's compressed KV, not yet paged
    cn = cnew_vmem[0].astype(jnp.float32)                # (1, C)
    rn = rnew_vmem[0].astype(jnp.float32)                # (1, R)
    ls = (jnp.dot(qa, cn.T, preferred_element_type=jnp.float32)
          + jnp.dot(qr, rn.T, preferred_element_type=jnp.float32)) * scale
    new_m = jnp.maximum(m, ls)
    corr = jnp.exp(m - new_m)
    p = jnp.exp(ls - new_m)
    l = l * corr + p
    acc = acc * corr + jnp.dot(p, cn, preferred_element_type=jnp.float32)
    o_vmem[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_vmem.dtype)


def pul_paged_mla_decode_attention(q_abs: jax.Array, q_rope: jax.Array,
                                   ckv_pages: jax.Array, kr_pages: jax.Array,
                                   page_tables: jax.Array, lengths,
                                   c_new: jax.Array, r_new: jax.Array, *,
                                   scale: float,
                                   cfg: PULConfig = PULConfig(),
                                   interpret: Optional[bool] = None) -> jax.Array:
    """Absorbed MLA decode attention straight over compressed-KV pages.

    q_abs: (B, H, kvr) queries absorbed into the compressed space; q_rope:
    (B, H, dr) rope-carrying queries; ckv_pages: (NP, P, C) and kr_pages:
    (NP, P, R) physical page frames (one row per token — MLA's cache is
    head-shared; C >= kvr and R >= dr lanes, zero beyond); page_tables:
    (B, n_pages); lengths: (B,) cached tokens per slot; c_new/r_new:
    (B, kvr)/(B, dr) the current token's compressed KV. Returns o_c
    (B, H, kvr) — the caller applies the absorbed v up-projection.
    """
    B, H, kvr = q_abs.shape
    NP, P, C = ckv_pages.shape
    R = kr_pages.shape[-1]
    _, n_pages = page_tables.shape
    lengths = jnp.asarray(lengths, jnp.int32).reshape(B)
    kern = functools.partial(_paged_mla_decode_kernel, cfg=cfg, P=P,
                             n_pages=n_pages, scale=scale)
    out = pl.pallas_call(
        kern,
        grid=(B,),
        out_shape=jax.ShapeDtypeStruct((B, H, C), q_abs.dtype),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, H, C), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, H, R), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, C), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, R), lambda b: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, C), lambda b: (b, 0, 0)),
        scratch_shapes=[
            *ring_scratch(cfg, (1, P, C), ckv_pages.dtype),
            *ring_scratch(cfg, (1, P, R), kr_pages.dtype),
        ],
        interpret=interpret_mode(interpret),
    )(page_tables.astype(jnp.int32), lengths, _pad_lanes(q_abs, C),
      _pad_lanes(q_rope, R), _pad_lanes(c_new, C).reshape(B, 1, C),
      _pad_lanes(r_new, R).reshape(B, 1, R), ckv_pages, kr_pages)
    return out[..., :kvr]


def _paged_sweep_decode_kernel(pt_smem, len_smem, frames_smem, offs_smem,
                               layer_smem, q_vmem, knew_vmem, vnew_vmem,
                               k_hbm, v_hbm, o_vmem, kp_out, vp_out,
                               kbuf, ksems, vbuf, vsems, ktail, vtail, tsems,
                               *, cfg: PULConfig, P: int, n_pages: int,
                               scale: float, softcap: Optional[float],
                               window: Optional[int]):
    b = pl.program_id(0)
    kv_h = pl.program_id(1)
    g = layer_smem[0]
    length = len_smem[b]

    # fused commit, part 1: fetch the tail page (layer, frames[b], kv_h)
    # the current token lands in; the fetch flies under the page stream.
    # The epilogue merges the new row into it and writes the page back
    # whole. Only this program writes that page (inactive slots all point
    # at the pool's TRASH sink, which nothing reads).
    def tail(ref):
        return ref.at[g, frames_smem[b], kv_h]
    k_fetch = pltpu.make_async_copy(tail(k_hbm), ktail, tsems.at[0])
    v_fetch = pltpu.make_async_copy(tail(v_hbm), vtail, tsems.at[1])
    k_fetch.start()
    v_fetch.start()

    # same page-table-driven stream as the per-layer kernel, with the layer
    # scalar prepended: block t is plane row (g, pt[b, t], kv_h) — the sweep
    # reads the SAME bytes the per-layer launch would, just without the
    # host-side layer slice
    k_st = PreloadStream(k_hbm, kbuf, ksems,
                         index_map=lambda t: (g, pt_smem[b, t], kv_h, 0, 0),
                         cfg=cfg, n_blocks=n_pages)
    v_st = PreloadStream(v_hbm, vbuf, vsems,
                         index_map=lambda t: (g, pt_smem[b, t], kv_h, 0, 0),
                         cfg=cfg, n_blocks=n_pages)

    q = q_vmem[0, 0].astype(jnp.float32)                 # (G, W)

    def _cap(logits):
        if softcap is not None:
            return softcap * jnp.tanh(logits / softcap)
        return logits

    def body(t, views, carry):
        m, l, acc = carry
        kt = views[0][0, 0, 0].astype(jnp.float32)       # (P, W)
        vt = views[1][0, 0, 0].astype(jnp.float32)
        logits = _cap(
            jnp.dot(q, kt.T, preferred_element_type=jnp.float32) * scale)
        jk = t * P + jax.lax.iota(jnp.int32, P)
        msk = jk < length
        if window is not None:
            msk &= jk > length - window
        logits = jnp.where(msk[None, :], logits, NEG_INF)
        bmax = jnp.max(logits, axis=-1, keepdims=True)
        new_m = jnp.maximum(m, bmax)
        corr = jnp.exp(m - new_m)
        p = jnp.exp(logits - new_m)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jnp.dot(p, vt, preferred_element_type=jnp.float32)
        return new_m, l, acc

    G, W = q.shape
    init = (jnp.full((G, 1), NEG_INF, jnp.float32),
            jnp.zeros((G, 1), jnp.float32),
            jnp.zeros((G, W), jnp.float32))
    m, l, acc = pul_loop(n_pages, [k_st, v_st], body, init, cfg)
    # the current token (position `length`, not yet paged) is always
    # causally visible and always inside the window
    kn = knew_vmem[0, 0].astype(jnp.float32)             # (1, W)
    vn = vnew_vmem[0, 0].astype(jnp.float32)
    ls = _cap(jnp.dot(q, kn.T, preferred_element_type=jnp.float32) * scale)
    new_m = jnp.maximum(m, ls)
    corr = jnp.exp(m - new_m)
    p = jnp.exp(ls - new_m)
    l = l * corr + p
    acc = acc * corr + jnp.dot(p, vn, preferred_element_type=jnp.float32)
    o_vmem[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_vmem.dtype)

    # fused commit, part 2: the current token's K/V row lands at in-page
    # row offsets[b] of the fetched tail page, which goes back whole. The
    # stream above only read positions < length and this row IS position
    # length, so the write can never race a read of itself. The host side
    # accounts/validates this commit via KVPagePool.note_fused_commit
    # BEFORE the launch.
    k_fetch.wait()
    v_fetch.wait()
    _merge_row(ktail, kn, offs_smem[b])
    _merge_row(vtail, vn, offs_smem[b])
    k_put = pltpu.make_async_copy(ktail, tail(kp_out), tsems.at[0])
    v_put = pltpu.make_async_copy(vtail, tail(vp_out), tsems.at[1])
    k_put.start()
    v_put.start()
    k_put.wait()
    v_put.wait()


def pul_paged_sweep_decode_attention(
        q: jax.Array, k_planes: jax.Array, v_planes: jax.Array, layer,
        page_tables: jax.Array, lengths, k_new: jax.Array, v_new: jax.Array,
        frames, offsets, *, cfg: PULConfig = PULConfig(),
        scale: Optional[float] = None, softcap: Optional[float] = None,
        window: Optional[int] = None, interpret: Optional[bool] = None):
    """One layer step of the single-sweep paged decode over per-layer planes.

    Reads layer `layer` of the full stacked planes and fuses the commit of
    the current token's K/V into the kernel epilogue — the in-kernel half of
    the `KVStoreLayout` commit contract.

    q: (B, H, hd); k_planes/v_planes: (L, NF, K, P, W) the ENTIRE per-layer
    page store (never sliced on the host — the zero-copy point; W >= hd
    lanes, zero beyond hd); layer: () int32 scalar (prefetched to SMEM; a
    scan-carried layer index); k_new / v_new: (B, K, hd) the current
    token's K/V, merged into the online softmax AND written to plane
    position (layer, frames[b], kv_h, offsets[b]); frames/offsets: (B,)
    int32 tail-page frame and in-page row per slot (TRASH frame for
    inactive slots — never the zero frame).

    Returns (out (B, H, hd), k_planes, v_planes) where the plane outputs are
    input/output-aliased: XLA updates the store in place, the caller threads
    them forward (the engine donates them through the jitted step).
    """
    B, H, hd = q.shape
    L, NF, K, P, W = k_planes.shape
    _, n_pages = page_tables.shape
    assert H % K == 0
    G = H // K
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    lengths = jnp.asarray(lengths, jnp.int32).reshape(B)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    frames = jnp.asarray(frames, jnp.int32).reshape(B)
    offsets = jnp.asarray(offsets, jnp.int32).reshape(B)
    qg = _pad_lanes(q, W).reshape(B, K, G, W)
    kern = functools.partial(_paged_sweep_decode_kernel, cfg=cfg, P=P,
                             n_pages=n_pages, scale=scale, softcap=softcap,
                             window=window)
    out, kp, vp = pl.pallas_call(
        kern,
        grid=(B, K),
        out_shape=[
            jax.ShapeDtypeStruct((B, K, G, W), q.dtype),
            jax.ShapeDtypeStruct(k_planes.shape, k_planes.dtype),
            jax.ShapeDtypeStruct(v_planes.shape, v_planes.dtype),
        ],
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # page tables
            pl.BlockSpec(memory_space=pltpu.SMEM),   # lengths
            pl.BlockSpec(memory_space=pltpu.SMEM),   # commit frames
            pl.BlockSpec(memory_space=pltpu.SMEM),   # commit offsets
            pl.BlockSpec(memory_space=pltpu.SMEM),   # layer scalar
            pl.BlockSpec((1, 1, G, W), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, W), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, W), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, G, W), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        # flattened inputs: pt, len, frames, offs, layer, q, k_new, v_new,
        # k_planes (8), v_planes (9) -> aliased to outputs 1 and 2
        input_output_aliases={8: 1, 9: 2},
        scratch_shapes=[
            *ring_scratch(cfg, (1, 1, 1, P, W), k_planes.dtype),
            *ring_scratch(cfg, (1, 1, 1, P, W), v_planes.dtype),
            pltpu.VMEM((P, W), k_planes.dtype),     # tail pages of the
            pltpu.VMEM((P, W), v_planes.dtype),     # fused commit
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret_mode(interpret),
    )(page_tables.astype(jnp.int32), lengths, frames, offsets, layer, qg,
      _pad_lanes(k_new.astype(k_planes.dtype), W).reshape(B, K, 1, W),
      _pad_lanes(v_new.astype(v_planes.dtype), W).reshape(B, K, 1, W),
      k_planes, v_planes)
    return out.reshape(B, H, W)[..., :hd], kp, vp


def _paged_sweep_mla_decode_kernel(pt_smem, len_smem, frames_smem, offs_smem,
                                   layer_smem, qa_vmem, qr_vmem, cnew_vmem,
                                   rnew_vmem, ckv_hbm, kr_hbm, o_vmem,
                                   cp_out, rp_out, cbuf, csems, rbuf, rsems,
                                   ctail, rtail, tsems, *, cfg: PULConfig,
                                   P: int, n_pages: int, scale: float):
    b = pl.program_id(0)
    g = layer_smem[0]
    length = len_smem[b]

    # fused commit, part 1 (see _paged_sweep_decode_kernel): fetch the
    # tail page (layer, frames[b]) of both planes under the page stream
    def tail(ref):
        return ref.at[g, frames_smem[b]]
    c_fetch = pltpu.make_async_copy(tail(ckv_hbm), ctail, tsems.at[0])
    r_fetch = pltpu.make_async_copy(tail(kr_hbm), rtail, tsems.at[1])
    c_fetch.start()
    r_fetch.start()

    c_st = PreloadStream(ckv_hbm, cbuf, csems,
                         index_map=lambda t: (g, pt_smem[b, t], 0, 0),
                         cfg=cfg, n_blocks=n_pages)
    r_st = PreloadStream(kr_hbm, rbuf, rsems,
                         index_map=lambda t: (g, pt_smem[b, t], 0, 0),
                         cfg=cfg, n_blocks=n_pages)

    qa = qa_vmem[0].astype(jnp.float32)                  # (H, C)
    qr = qr_vmem[0].astype(jnp.float32)                  # (H, R)

    def body(t, views, carry):
        m, l, acc = carry
        ct = views[0][0, 0].astype(jnp.float32)          # (P, C)
        rt = views[1][0, 0].astype(jnp.float32)          # (P, R)
        logits = (jnp.dot(qa, ct.T, preferred_element_type=jnp.float32)
                  + jnp.dot(qr, rt.T, preferred_element_type=jnp.float32)
                  ) * scale
        jk = t * P + jax.lax.iota(jnp.int32, P)
        logits = jnp.where((jk < length)[None, :], logits, NEG_INF)
        bmax = jnp.max(logits, axis=-1, keepdims=True)
        new_m = jnp.maximum(m, bmax)
        corr = jnp.exp(m - new_m)
        p = jnp.exp(logits - new_m)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jnp.dot(p, ct, preferred_element_type=jnp.float32)
        return new_m, l, acc

    H, C = qa.shape
    init = (jnp.full((H, 1), NEG_INF, jnp.float32),
            jnp.zeros((H, 1), jnp.float32),
            jnp.zeros((H, C), jnp.float32))
    m, l, acc = pul_loop(n_pages, [c_st, r_st], body, init, cfg)
    cn = cnew_vmem[0].astype(jnp.float32)                # (1, C)
    rn = rnew_vmem[0].astype(jnp.float32)                # (1, R)
    ls = (jnp.dot(qa, cn.T, preferred_element_type=jnp.float32)
          + jnp.dot(qr, rn.T, preferred_element_type=jnp.float32)) * scale
    new_m = jnp.maximum(m, ls)
    corr = jnp.exp(m - new_m)
    p = jnp.exp(ls - new_m)
    l = l * corr + p
    acc = acc * corr + jnp.dot(p, cn, preferred_element_type=jnp.float32)
    o_vmem[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_vmem.dtype)

    # fused commit, part 2: the current token's compressed KV lands at row
    # offsets[b] of both tail pages, which go back whole
    c_fetch.wait()
    r_fetch.wait()
    _merge_row(ctail, cn, offs_smem[b])
    _merge_row(rtail, rn, offs_smem[b])
    c_put = pltpu.make_async_copy(ctail, tail(cp_out), tsems.at[0])
    r_put = pltpu.make_async_copy(rtail, tail(rp_out), tsems.at[1])
    c_put.start()
    r_put.start()
    c_put.wait()
    r_put.wait()


def pul_paged_sweep_mla_decode_attention(
        q_abs: jax.Array, q_rope: jax.Array, ckv_planes: jax.Array,
        kr_planes: jax.Array, layer, page_tables: jax.Array, lengths,
        c_new: jax.Array, r_new: jax.Array, frames, offsets, *, scale: float,
        cfg: PULConfig = PULConfig(), interpret: Optional[bool] = None):
    """Absorbed-MLA layer step of the single-sweep paged decode.

    ckv_planes: (L, NF, P, C), kr_planes: (L, NF, P, R) — the entire
    per-layer compressed page store (C >= kvr and R >= dr lanes, zero
    beyond); `layer` selects the plane row in-kernel via the prefetched
    SMEM scalar. c_new/r_new ((B, kvr)/(B, dr)) are merged into the online
    softmax AND committed to (layer, frames[b], offsets[b]) in the fused
    epilogue. Returns (o_c (B, H, kvr), ckv_planes, kr_planes) with the
    planes input/output-aliased for in-place update.
    """
    B, H, kvr = q_abs.shape
    L, NF, P, C = ckv_planes.shape
    R = kr_planes.shape[-1]
    _, n_pages = page_tables.shape
    lengths = jnp.asarray(lengths, jnp.int32).reshape(B)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    frames = jnp.asarray(frames, jnp.int32).reshape(B)
    offsets = jnp.asarray(offsets, jnp.int32).reshape(B)
    kern = functools.partial(_paged_sweep_mla_decode_kernel, cfg=cfg, P=P,
                             n_pages=n_pages, scale=scale)
    out, cp, rp = pl.pallas_call(
        kern,
        grid=(B,),
        out_shape=[
            jax.ShapeDtypeStruct((B, H, C), q_abs.dtype),
            jax.ShapeDtypeStruct(ckv_planes.shape, ckv_planes.dtype),
            jax.ShapeDtypeStruct(kr_planes.shape, kr_planes.dtype),
        ],
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, H, C), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, H, R), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, C), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, R), lambda b: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, H, C), lambda b: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        # flattened inputs: pt, len, frames, offs, layer, qa, qr, c_new,
        # r_new, ckv_planes (9), kr_planes (10) -> aliased to outputs 1, 2
        input_output_aliases={9: 1, 10: 2},
        scratch_shapes=[
            *ring_scratch(cfg, (1, 1, P, C), ckv_planes.dtype),
            *ring_scratch(cfg, (1, 1, P, R), kr_planes.dtype),
            pltpu.VMEM((P, C), ckv_planes.dtype),   # tail pages of the
            pltpu.VMEM((P, R), kr_planes.dtype),    # fused commit
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret_mode(interpret),
    )(page_tables.astype(jnp.int32), lengths, frames, offsets, layer,
      _pad_lanes(q_abs, C), _pad_lanes(q_rope, R),
      _pad_lanes(c_new.astype(ckv_planes.dtype), C).reshape(B, 1, C),
      _pad_lanes(r_new.astype(kr_planes.dtype), R).reshape(B, 1, R),
      ckv_planes, kr_planes)
    return out[..., :kvr], cp, rp


def pul_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  cfg: PULConfig = PULConfig(), bt: int = 128, bs: int = 128,
                  causal: bool = True, scale: Optional[float] = None,
                  softcap: Optional[float] = None,
                  window: Optional[int] = None,
                  interpret: Optional[bool] = None) -> jax.Array:
    B, H, T, hd = q.shape
    _, K, S, _ = k.shape
    assert H % K == 0
    bt = min(bt, T)
    bs = min(bs, S)
    assert T % bt == 0
    ns = -(-S // bs)
    if ns * bs != S:
        # pad the KV stream to whole preload blocks; the in-kernel jk < S
        # mask discards the tail (DMA may not read out of bounds)
        pad = ((0, 0), (0, 0), (0, ns * bs - S), (0, 0))
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    kern = functools.partial(
        _kernel, cfg=cfg, bt=bt, bs=bs, ns=ns, S=S, T=T, group=H // K,
        scale=scale, softcap=softcap, window=window, causal=causal)
    return pl.pallas_call(
        kern,
        grid=(B, H, T // bt),
        out_shape=jax.ShapeDtypeStruct((B, H, T, hd), q.dtype),
        in_specs=[
            pl.BlockSpec((1, 1, bt, hd), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, 1, bt, hd), lambda b, h, t: (b, h, t, 0)),
        scratch_shapes=[
            *ring_scratch(cfg, (1, 1, bs, hd), k.dtype),
            *ring_scratch(cfg, (1, 1, bs, hd), v.dtype),
            pltpu.VMEM((bt, 1), jnp.float32),
            pltpu.VMEM((bt, 1), jnp.float32),
            pltpu.VMEM((bt, hd), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(q, k, v)
