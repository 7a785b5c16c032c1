"""The paper's SUM microbenchmark as a PUL Pallas kernel (Exps. 1, 3, 4).

Trace-driven random row aggregation: rows of an HBM-resident table are
requested in trace order through a distance-d preload pipeline into VMEM ring
slots, and reduced while later requests are in flight — Listing 1 verbatim,
with the trace playing the paper's pre-generated random access pattern.

Knobs swept by benchmarks: preload distance (Exp. 3), rows-per-request =
transfer size (Exp. 4), BATCH vs SEQUENTIAL issue (Fig. 5-D).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import (
    PULConfig, PreloadStream, pul_loop, ring_scratch, interpret_mode)


def _kernel(trace_smem, data_hbm, out_smem, buf, sems, *, cfg: PULConfig,
            n_req: int, rows_per_req: int):
    stream = PreloadStream(
        data_hbm, buf, sems,
        # the paper's byte-addressable "arbitrary address" preload: the row
        # index for request i comes from the trace (SMEM scalar read)
        index_map=lambda i: (trace_smem[i] * rows_per_req, 0),
        cfg=cfg, n_blocks=n_req)

    def body(i, views, acc):
        blk = views[0][...]                       # (rows_per_req, W)
        return acc + jnp.sum(blk.astype(jnp.float32))

    acc = pul_loop(n_req, [stream], body, jnp.float32(0.0), cfg)
    out_smem[0] = acc


def pul_sum(data: jax.Array, trace: jax.Array, *, cfg: PULConfig = PULConfig(),
            rows_per_req: int = 1, interpret: Optional[bool] = None) -> jax.Array:
    """sum over data[trace[i]*rows_per_req : +rows_per_req] for all i.

    data: (R, W) float; trace: (n_req,) int32 of block indices.
    """
    n_req = trace.shape[0]
    W = data.shape[1]
    block = (rows_per_req, W)
    kern = functools.partial(_kernel, cfg=cfg, n_req=n_req,
                             rows_per_req=rows_per_req)
    out = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((1,), jnp.float32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        scratch_shapes=list(ring_scratch(cfg, block, data.dtype)),
        interpret=interpret_mode(interpret),
    )(trace, data)
    return out[0]
