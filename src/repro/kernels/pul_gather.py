"""Random row gather with preload + unload (embedding / KV-block fetch).

out[i] = table[trace[i]] — the data path of an embedding lookup or a paged
KV-cache fetch. Reads ride a distance-d preload ring; writes leave through an
unload ring (paper §2: preloading and unloading are independent FIFOs and
synchronize independently).
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


def _kernel(trace_smem, table_hbm, out_hbm, pbuf, psems, ubuf, usems, *,
            cfg: PULConfig, n_req: int, rows_per_req: int):
    pre = PreloadStream(
        table_hbm, pbuf, psems,
        index_map=lambda i: (trace_smem[i] * rows_per_req, 0),
        cfg=cfg, n_blocks=n_req)
    unl = UnloadStream(
        out_hbm, ubuf, usems,
        index_map=lambda i: (i * rows_per_req, 0),
        cfg=cfg, n_blocks=n_req)

    def body(i, views, carry):
        slot = unl.slot(i)
        slot[...] = views[0][...]
        unl.issue(i)
        return carry

    pul_loop(n_req, [pre], body, 0, cfg, unloads=[unl])


def pul_page_gather(store: jax.Array, page_table: jax.Array, *,
                    cfg: PULConfig = PULConfig(),
                    interpret: Optional[bool] = None) -> jax.Array:
    """Assemble sequences from a paged KV store (the serving gather path).

    store: (n_pages, page_tokens, feat) physical page frames.
    page_table: (n_seqs, pages_per_seq) int32 page ids (a serving slot's
      logical->physical page map; the SMEM-resident trace of the PUL gather).
    Returns (n_seqs, pages_per_seq * page_tokens, feat): each sequence's
    token-contiguous KV, pulled page-by-page through the preload ring and
    written back out through the unload ring.
    """
    n_pages, P, F = store.shape
    n_seqs, ppseq = page_table.shape
    flat = pul_gather(store.reshape(n_pages * P, F),
                      page_table.reshape(-1).astype(jnp.int32),
                      cfg=cfg, rows_per_req=P, interpret=interpret)
    return flat.reshape(n_seqs, ppseq * P, F)


def pul_gather(table: jax.Array, trace: jax.Array, *,
               cfg: PULConfig = PULConfig(), rows_per_req: int = 1,
               interpret: Optional[bool] = None) -> jax.Array:
    n_req = trace.shape[0]
    W = table.shape[1]
    block = (rows_per_req, W)
    kern = functools.partial(_kernel, cfg=cfg, n_req=n_req,
                             rows_per_req=rows_per_req)
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((n_req * rows_per_req, W), table.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[*ring_scratch(cfg, block, table.dtype),
                        *ring_scratch(cfg, block, table.dtype)],
        interpret=interpret_mode(interpret),
    )(trace, table)
