"""Filter with bit-vector unloading (paper Experiment 5, Fig. 7).

Streams table rows through the preload ring, evaluates the predicate, and
materializes the result either as

  * a positional BIT-VECTOR (one bit per row, packed into int32 words) —
    the paper's bandwidth-saving encoding: extra interleavable compute,
    64x less unload traffic for 64B rows; or
  * the FULL rows (zero-masked), the baseline materialization whose unload
    traffic competes with the already bandwidth-bound scan.

Predicate: row[0] > threshold.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import (
    PULConfig, PreloadStream, UnloadStream, pul_loop, ring_scratch,
    interpret_mode)


def _kernel_bitvec(thr_smem, data_hbm, out_hbm, pbuf, psems, ubuf, usems, *,
                   cfg: PULConfig, n_blocks: int, rows: int):
    # rows per block must be a multiple of 32 (bit-packing word width)
    words = rows // 32
    pre = PreloadStream(data_hbm, pbuf, psems,
                        index_map=lambda i: (i * rows, 0),
                        cfg=cfg, n_blocks=n_blocks)
    unl = UnloadStream(out_hbm, ubuf, usems,
                       index_map=lambda i: (i * words, 0),
                       cfg=cfg, n_blocks=n_blocks)
    thr = thr_smem[0]

    def body(i, views, carry):
        blk = views[0][...]                            # (rows, W)
        bits = (blk[:, 0] > thr).astype(jnp.uint32)    # (rows,)
        shifted = bits.reshape(words, 32) << jax.lax.broadcasted_iota(
            jnp.uint32, (words, 32), 1)
        packed = jnp.sum(shifted, axis=1, dtype=jnp.uint32)  # or of disjoint bits
        slot = unl.slot(i)
        slot[...] = packed.reshape(words, 1)
        unl.issue(i)
        return carry

    pul_loop(n_blocks, [pre], body, 0, cfg, unloads=[unl])


def _kernel_materialize(thr_smem, data_hbm, out_hbm, pbuf, psems, ubuf, usems,
                        *, cfg: PULConfig, n_blocks: int, rows: int):
    pre = PreloadStream(data_hbm, pbuf, psems,
                        index_map=lambda i: (i * rows, 0),
                        cfg=cfg, n_blocks=n_blocks)
    unl = UnloadStream(out_hbm, ubuf, usems,
                       index_map=lambda i: (i * rows, 0),
                       cfg=cfg, n_blocks=n_blocks)
    thr = thr_smem[0]

    def body(i, views, carry):
        blk = views[0][...]
        keep = blk[:, 0] > thr
        slot = unl.slot(i)
        slot[...] = jnp.where(keep[:, None], blk, 0)
        unl.issue(i)
        return carry

    pul_loop(n_blocks, [pre], body, 0, cfg, unloads=[unl])


def pul_filter(data: jax.Array, threshold: float, *,
               cfg: PULConfig = PULConfig(), rows_per_block: int = 128,
               materialize: bool = False, interpret: Optional[bool] = None) -> jax.Array:
    N, W = data.shape
    rows = rows_per_block
    assert N % rows == 0 and rows % 32 == 0
    n_blocks = N // rows
    thr = jnp.asarray([threshold], data.dtype)
    if materialize:
        kern = functools.partial(_kernel_materialize, cfg=cfg,
                                 n_blocks=n_blocks, rows=rows)
        out_shape = jax.ShapeDtypeStruct((N, W), data.dtype)
        ublock = (rows, W)
        udtype = data.dtype
    else:
        kern = functools.partial(_kernel_bitvec, cfg=cfg,
                                 n_blocks=n_blocks, rows=rows)
        out_shape = jax.ShapeDtypeStruct((N // 32, 1), jnp.uint32)
        ublock = (rows // 32, 1)
        udtype = jnp.uint32
    out = pl.pallas_call(
        kern,
        out_shape=out_shape,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[*ring_scratch(cfg, (rows, W), data.dtype),
                        *ring_scratch(cfg, ublock, udtype)],
        interpret=interpret_mode(interpret),
    )(thr, data)
    return out[:, 0] if not materialize else out
