"""Tiled matmul with PUL-pipelined operand streaming.

C[i,j] = sum_k A[i,k] B[k,j]. The grid parallelizes output tiles (the "PE
array"); inside each grid step the K-dimension reduction streams A and B
tiles through distance-d preload rings while the MXU consumes the previous
tiles, and finished C tiles leave through an unload ring — compute/IO
interleaving at MXU granularity (the paper's Fig. 1 roofline argument: low
arithmetic-intensity tiles are latency-bound without PUL).

Block shapes are PULConfig knobs; defaults are MXU-aligned (128 multiples).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import (
    PULConfig, PreloadStream, UnloadStream, pul_loop, ring_scratch,
    interpret_mode)


def _kernel(a_hbm, b_hbm, c_hbm, abuf, asems, bbuf, bsems, cacc, ubuf, usems,
            *, cfg: PULConfig, bm: int, bk: int, bn: int, nk: int, ni: int,
            nj: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    a_st = PreloadStream(a_hbm, abuf, asems,
                         index_map=lambda t: (i * bm, t * bk),
                         cfg=cfg, n_blocks=nk)
    b_st = PreloadStream(b_hbm, bbuf, bsems,
                         index_map=lambda t: (t * bk, j * bn),
                         cfg=cfg, n_blocks=nk)
    tile = i * nj + j
    ucfg = PULConfig(distance=1, slots=2, unload_distance=cfg.unload_distance)
    unl = UnloadStream(c_hbm, ubuf, usems,
                       index_map=lambda t: ((t // nj) * bm, (t % nj) * bn),
                       cfg=ucfg, n_blocks=ni * nj)  # double-buffered C ring

    cacc[...] = jnp.zeros(cacc.shape, cacc.dtype)

    def body(t, views, carry):
        at = views[0][...]
        bt = views[1][...]
        cacc[...] += jnp.dot(at, bt, preferred_element_type=jnp.float32)
        return carry

    pul_loop(nk, [a_st, b_st], body, 0, cfg)

    slot = unl.slot(tile)
    slot[...] = cacc[...].astype(ubuf.dtype)
    unl.issue(tile)
    # intermediate grid steps overlap the C flush with the next tile's
    # compute (slot() enforces ring reuse); the last step drains the ring
    @pl.when((i == ni - 1) & (j == nj - 1))
    def _():
        unl.drain()


def pul_matmul(a: jax.Array, b: jax.Array, *, cfg: PULConfig = PULConfig(),
               bm: int = 128, bk: int = 128, bn: int = 128,
               out_dtype=jnp.float32, interpret: Optional[bool] = None) -> jax.Array:
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    assert M % bm == 0 and K % bk == 0 and N % bn == 0, (M, K, N, bm, bk, bn)
    nk, ni, nj = K // bk, M // bm, N // bn
    kern = functools.partial(_kernel, cfg=cfg, bm=bm, bk=bk, bn=bn, nk=nk,
                             ni=ni, nj=nj)
    ucfg = PULConfig(distance=1, slots=2, unload_distance=cfg.unload_distance)
    return pl.pallas_call(
        kern,
        grid=(ni, nj),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            *ring_scratch(cfg, (bm, bk), a.dtype),
            *ring_scratch(cfg, (bk, bn), b.dtype),
            pltpu.VMEM((bm, bn), jnp.float32),
            *ring_scratch(ucfg, (bm, bn), out_dtype),
        ],
        interpret=interpret_mode(interpret),
    )(a, b)
