"""TPU realization of PUL (paper Listing 1) as a Pallas pipeline emitter.

The paper's programming model:

    PRELOAD_SET_SIZE(64);
    PRELOAD(src[i], scratch[slot]);   // async, non-blocking enqueue
    PRELOAD_WAIT();                   // status-register sync
    ... compute on scratch[...] ...
    UNLOAD(scratch[slot], dst, n);    // async write-back

maps onto TPU Pallas as: refs living in HBM (`pl.ANY` memory space), ring
buffers of VMEM scratch slots, `pltpu.make_async_copy(...).start()` as the
FIFO enqueue, and DMA-semaphore `.wait()` as the status-register poll. The
classes below package that into *streams*:

  * :class:`PreloadStream` — distance-d read pipeline HBM -> VMEM ring.
  * :class:`UnloadStream`  — write-back pipeline VMEM ring -> HBM, waited
    `slots` blocks behind production (Exp. 5).
  * :func:`pul_loop`       — the steady-state driver: warm-up per the issue
    strategy, then wait(i) / body(i) / issue(i+d).

Kernels in `repro.kernels` build on these; nothing here is kernel-specific.
All of it runs under `interpret=True` on CPU (how this repo validates) and
lowers to real TPU DMA ops on hardware; :func:`interpret_mode` is the one
place that picks between the two.
"""
from __future__ import annotations

import math
from typing import Callable, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.pul import IssueStrategy, PULConfig

# Default VMEM budget we allow a kernel's PUL rings to claim. v5e VMEM is
# ~128 MiB; leave headroom for the compute body's operands and XLA spills.
VMEM_BUDGET_BYTES = 96 * 2**20


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Resolve a kernel's `interpret` flag: compiled Mosaic on a TPU
    backend, the Pallas interpreter on any other. An explicit bool wins, so
    an AOT compile for a described TPU from a CPU process passes False."""
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def ring_scratch(cfg: PULConfig, block_shape: Sequence[int], dtype) -> Tuple:
    """Scratch shapes for one stream: (VMEM ring, DMA semaphores).

    Pass the results inside `scratch_shapes=[...]` of `pl.pallas_call`; the
    kernel receives them as (buf, sems) positional scratch arguments.
    """
    slots = cfg.num_slots
    nbytes = slots * math.prod(block_shape) * jnp.dtype(dtype).itemsize
    if nbytes > VMEM_BUDGET_BYTES:
        raise ValueError(
            f"PUL ring of {slots} x {tuple(block_shape)} x {jnp.dtype(dtype).name} "
            f"= {nbytes/2**20:.1f} MiB exceeds the VMEM budget "
            f"({VMEM_BUDGET_BYTES/2**20:.0f} MiB); shrink block_shape or distance"
        )
    return (
        pltpu.VMEM((slots, *block_shape), dtype),
        pltpu.SemaphoreType.DMA((slots,)),
    )


def _block_slice(ref, offsets, block_shape):
    idx = tuple(pl.ds(o, s) for o, s in zip(offsets, block_shape))
    return ref.at[idx] if idx else ref


class PreloadStream:
    """Distance-d preload pipeline: HBM ref -> VMEM ring (paper PRELOAD).

    Args:
      src: source ref in `pl.ANY`/HBM memory space.
      buf: VMEM ring scratch, shape (slots, *block_shape).
      sems: DMA semaphore array, shape (slots,).
      index_map: fn(i) -> element offsets of block i in `src` (one offset per
        `src` axis, len == len(block_shape); traced, may read SMEM scalars —
        this is how trace-driven random preloads work).
      cfg: the PUL knobs.
      n_blocks: total number of logical blocks in the stream (static).
    """

    def __init__(self, src, buf, sems, *, index_map, cfg: PULConfig, n_blocks: int):
        self.src = src
        self.buf = buf
        self.sems = sems
        self.index_map = index_map
        self.cfg = cfg
        self.n_blocks = n_blocks
        self.slots = cfg.num_slots
        self.block_shape = tuple(buf.shape[1:])

    def _copy(self, i):
        slot = jax.lax.rem(i, self.slots)
        src_blk = _block_slice(self.src, self.index_map(i), self.block_shape)
        return pltpu.make_async_copy(src_blk, self.buf.at[slot], self.sems.at[slot])

    def issue(self, i):
        """Non-blocking FIFO enqueue of block i (PRELOAD)."""
        self._copy(i).start()

    def issue_if_in_range(self, i):
        @pl.when(i < self.n_blocks)
        def _():
            self.issue(i)

    def wait(self, i):
        """Status-register sync for block i (PRELOAD_WAIT); returns the VMEM
        slot view holding the block."""
        self._copy(i).wait()
        return self.buf.at[jax.lax.rem(i, self.slots)]


class UnloadStream:
    """Write-back pipeline: VMEM ring -> HBM ref (paper UNLOAD, Exp. 5).

    Production protocol for block i:
        view = stream.slot(i)     # waits for the flush that last used this
                                  # slot (i - slots) to retire, then hands
                                  # out the VMEM view to write results into
        ... body writes view ...
        stream.issue(i)           # async flush of block i
    and `drain()` at the end (the final PRELOAD_WAIT of Listing 1).
    """

    def __init__(self, dst, buf, sems, *, index_map, cfg: PULConfig, n_blocks: int):
        self.dst = dst
        self.buf = buf
        self.sems = sems
        self.index_map = index_map
        self.cfg = cfg
        self.n_blocks = n_blocks
        self.slots = cfg.num_slots
        self.block_shape = tuple(buf.shape[1:])

    def _copy(self, i):
        slot = jax.lax.rem(i, self.slots)
        dst_blk = _block_slice(self.dst, self.index_map(i), self.block_shape)
        return pltpu.make_async_copy(self.buf.at[slot], dst_blk, self.sems.at[slot])

    def slot(self, i):
        """VMEM view for producing block i; enforces single-owner slot reuse."""
        j = i - self.slots
        @pl.when(j >= 0)
        def _():
            self._copy(j).wait()
        return self.buf.at[jax.lax.rem(i, self.slots)]

    def issue(self, i):
        self._copy(i).start()
        if self.cfg.unload_distance == 0:       # synchronous-flush baseline
            self._copy(i).wait()

    def drain(self, produced: Optional[int] = None):
        """Wait for every in-flight flush. `produced` = number of blocks
        issued so far (defaults to the stream's static n_blocks)."""
        n = self.n_blocks if produced is None else produced
        if self.cfg.unload_distance == 0:
            return
        first = max(0, n - self.slots) if isinstance(n, int) else jnp.maximum(0, n - self.slots)
        if isinstance(n, int):
            for j in range(first, n):
                self._copy(jnp.int32(j)).wait()
        else:
            def body(j, _):
                @pl.when(j >= first)
                def _w():
                    self._copy(j).wait()
                return 0
            jax.lax.fori_loop(0, n, body, 0)


def pul_loop(
    n_blocks: int,
    preloads: Sequence[PreloadStream],
    body: Callable,                      # body(i, views: list[Ref], carry) -> carry
    carry,
    cfg: PULConfig,
    *,
    unloads: Sequence[UnloadStream] = (),
    drain: bool = True,
):
    """The steady-state PUL driver (paper Listing 1 around the compute).

    Warm-up: BATCH fires the full distance-d window up-front; SEQUENTIAL
    fires it too (Listing 1 lines 1-3) but in the steady state issues block
    i+d *before* computing block i (`PL[i+d] -> compute[i]`), whereas BATCH
    issues after the compute — with 2d slots the batches double-buffer.

    `n_blocks` must be static (Python int). Returns the final carry.
    """
    if n_blocks <= 0:
        return carry
    d = min(cfg.distance, n_blocks)

    for s in preloads:
        for i in range(d):
            s.issue(jnp.int32(i))

    seq = cfg.strategy is IssueStrategy.SEQUENTIAL

    def step(i, carry):
        if seq:
            for s in preloads:
                s.issue_if_in_range(i + d)
        views = [s.wait(i) for s in preloads]
        carry = body(i, views, carry)
        if not seq:
            for s in preloads:
                s.issue_if_in_range(i + d)
        return carry

    carry = jax.lax.fori_loop(0, n_blocks, step, carry)
    if drain:
        for u in unloads:
            u.drain()
    return carry


def pul_streams(
    refs_bufs_sems: Sequence[Tuple],
    index_maps: Sequence[Callable],
    cfg: PULConfig,
    n_blocks: int,
) -> List[PreloadStream]:
    """Convenience constructor for several parallel preload streams."""
    return [
        PreloadStream(r, b, s, index_map=m, cfg=cfg, n_blocks=n_blocks)
        for (r, b, s), m in zip(refs_bufs_sems, index_maps)
    ]
