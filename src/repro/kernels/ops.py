"""Jit'd public wrappers for the PUL kernels.

`interpret=None` leaves the choice to `repro.core.interpret_mode`: the
Pallas interpreter on CPU (validation mode), Mosaic with the real DMA
engines on TPU.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import PULConfig
from repro.kernels.pul_sum import pul_sum
from repro.kernels.pul_gather import pul_gather
from repro.kernels.pul_matmul import pul_matmul
from repro.kernels.pul_attention import pul_attention
from repro.kernels.pul_filter import pul_filter


@functools.partial(jax.jit, static_argnames=("cfg", "rows_per_req", "interpret"))
def sum_op(data, trace, *, cfg: PULConfig = PULConfig(),
           rows_per_req: int = 1, interpret: Optional[bool] = None):
    return pul_sum(data, trace, cfg=cfg, rows_per_req=rows_per_req,
                   interpret=interpret)


@functools.partial(jax.jit, static_argnames=("cfg", "rows_per_req", "interpret"))
def gather_op(table, trace, *, cfg: PULConfig = PULConfig(),
              rows_per_req: int = 1, interpret: Optional[bool] = None):
    return pul_gather(table, trace, cfg=cfg, rows_per_req=rows_per_req,
                      interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "cfg", "bm", "bk", "bn", "out_dtype", "interpret"))
def matmul_op(a, b, *, cfg: PULConfig = PULConfig(), bm: int = 128,
              bk: int = 128, bn: int = 128, out_dtype=jnp.float32,
              interpret: Optional[bool] = None):
    return pul_matmul(a, b, cfg=cfg, bm=bm, bk=bk, bn=bn,
                      out_dtype=out_dtype, interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "cfg", "bt", "bs", "causal", "scale", "softcap", "window", "interpret"))
def attention_op(q, k, v, *, cfg: PULConfig = PULConfig(), bt: int = 128,
                 bs: int = 128, causal: bool = True,
                 scale: Optional[float] = None,
                 softcap: Optional[float] = None,
                 window: Optional[int] = None,
                 interpret: Optional[bool] = None):
    return pul_attention(q, k, v, cfg=cfg, bt=bt, bs=bs, causal=causal,
                         scale=scale, softcap=softcap, window=window,
                         interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "cfg", "rows_per_block", "materialize", "interpret"))
def filter_op(data, threshold: float, *, cfg: PULConfig = PULConfig(),
              rows_per_block: int = 128, materialize: bool = False,
              interpret: Optional[bool] = None):
    return pul_filter(data, threshold, cfg=cfg, rows_per_block=rows_per_block,
                      materialize=materialize, interpret=interpret)
