"""PUL Pallas emitter invariants (interpret mode): stream correctness over
the (distance, slots, strategy) knob space, unload single-ownership."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import (
    IssueStrategy,
    PULConfig,
    PreloadStream,
    UnloadStream,
    pul_loop,
    ring_scratch,
)


def _copy_kernel(cfg, n, blk):
    def kernel(idx_smem, x_hbm, acc_ref, o_hbm, pbuf, psem, ubuf, usem):
        pre = PreloadStream(x_hbm, pbuf, psem,
                            index_map=lambda i: (idx_smem[i], 0),
                            cfg=cfg, n_blocks=n)
        unl = UnloadStream(o_hbm, ubuf, usem,
                           index_map=lambda i: (i, 0), cfg=cfg, n_blocks=n)

        def body(i, views, carry):
            row = views[0][0, :]
            slot = unl.slot(i)
            slot[0, :] = row * 2.0
            unl.issue(i)
            return carry + jnp.sum(row)

        acc = pul_loop(n, [pre], body, jnp.float32(0.0), cfg, unloads=[unl])
        acc_ref[0] = acc
    return kernel


def _run(cfg, x, idx):
    n = idx.shape[0]
    blk = x.shape[1]
    return pl.pallas_call(
        _copy_kernel(cfg, n, blk),
        out_shape=(jax.ShapeDtypeStruct((1,), jnp.float32),
                   jax.ShapeDtypeStruct((n, blk), jnp.float32)),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=(pl.BlockSpec(memory_space=pltpu.SMEM),
                   pl.BlockSpec(memory_space=pl.ANY)),
        scratch_shapes=[*ring_scratch(cfg, (1, blk), jnp.float32),
                        *ring_scratch(cfg, (1, blk), jnp.float32)],
        interpret=True,
    )(idx, x)


@pytest.mark.parametrize("strategy", [IssueStrategy.BATCH,
                                      IssueStrategy.SEQUENTIAL])
@pytest.mark.parametrize("distance", [1, 2, 5, 16])
def test_stream_copy_all_knobs(strategy, distance):
    cfg = PULConfig(distance=distance, strategy=strategy, block_shape=(1, 128))
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 128), jnp.float32)
    idx = jax.random.randint(jax.random.PRNGKey(1), (33,), 0, 64, jnp.int32)
    acc, out = _run(cfg, x, idx)
    np.testing.assert_allclose(acc[0], x[idx].sum(), rtol=1e-4, atol=2e-3)
    np.testing.assert_allclose(out, x[idx] * 2.0)


@settings(max_examples=15, deadline=None)
@given(
    d=st.integers(1, 12),
    n=st.integers(1, 40),
    extra_slots=st.integers(0, 3),
    seq=st.booleans(),
)
def test_stream_property_any_shape(d, n, extra_slots, seq):
    """Result is knob-independent: any (distance, slots, strategy, n) gives
    exactly the oracle (the paper's knobs change WHEN bytes move, not WHAT)."""
    strategy = IssueStrategy.SEQUENTIAL if seq else IssueStrategy.BATCH
    base = PULConfig(distance=d, strategy=strategy).num_slots
    cfg = PULConfig(distance=d, strategy=strategy, slots=base + extra_slots,
                    block_shape=(1, 128))
    x = jax.random.normal(jax.random.PRNGKey(n), (32, 128), jnp.float32)
    idx = jax.random.randint(jax.random.PRNGKey(d), (n,), 0, 32, jnp.int32)
    acc, out = _run(cfg, x, idx)
    # near-cancelling sums need an absolute floor (fp32 accumulation order)
    np.testing.assert_allclose(acc[0], x[idx].sum(), rtol=1e-4, atol=2e-3)
    np.testing.assert_allclose(out, x[idx] * 2.0)


def test_n_blocks_smaller_than_distance():
    cfg = PULConfig(distance=16, block_shape=(1, 128))
    x = jnp.ones((8, 128), jnp.float32)
    idx = jnp.arange(3, dtype=jnp.int32)
    acc, out = _run(cfg, x, idx)
    np.testing.assert_allclose(acc[0], 3 * 128.0)


_MAX_BLOCKS, _D = 11, 4


def _run_bounded(cfg, x, start, n):
    """Copy-and-sum kernel over blocks start .. n - 1 of `x`, with the trip
    count and start read from SMEM at run time (bound: _MAX_BLOCKS)."""
    def kernel(bounds_smem, x_hbm, acc_ref, o_hbm, pbuf, psem, ubuf, usem):
        first, end = bounds_smem[0], bounds_smem[1]
        pre = PreloadStream(x_hbm, pbuf, psem, index_map=lambda i: (i, 0),
                            cfg=cfg, n_blocks=_MAX_BLOCKS)
        unl = UnloadStream(o_hbm, ubuf, usem, index_map=lambda i: (i, 0),
                           cfg=cfg, n_blocks=_MAX_BLOCKS)

        def body(i, views, carry):
            row = views[0][0, :]
            unl.slot(i, first)[0, :] = row * 2.0
            unl.issue(i)
            return carry + jnp.sum(row)

        acc_ref[0] = pul_loop(end, [pre], body, jnp.float32(0.0), cfg,
                              unloads=[unl], max_blocks=_MAX_BLOCKS,
                              start=first)

    blk = x.shape[1]
    return pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((1,), jnp.float32),
                   jax.ShapeDtypeStruct(x.shape, jnp.float32)),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=(pl.BlockSpec(memory_space=pltpu.SMEM),
                   pl.BlockSpec(memory_space=pl.ANY)),
        scratch_shapes=[*ring_scratch(cfg, (1, blk), jnp.float32),
                        *ring_scratch(cfg, (1, blk), jnp.float32)],
        interpret=True,
    )(jnp.asarray([start, n], jnp.int32), x)


@pytest.mark.parametrize("strategy", [IssueStrategy.BATCH,
                                      IssueStrategy.SEQUENTIAL])
@pytest.mark.parametrize("start,n", [(0, 0), (0, 1), (0, _D - 1), (0, _D),
                                     (0, _MAX_BLOCKS), (3, 9), (5, 5)])
def test_traced_trip_count_reads_only_its_blocks(strategy, start, n):
    """A trip count (and first block) read at run time: blocks outside
    start .. n - 1 are NaN, so one that was waited on or computed would
    poison the sum; the blocks inside arrive and write back whole."""
    cfg = PULConfig(distance=_D, strategy=strategy, block_shape=(1, 128))
    x = jax.random.normal(jax.random.PRNGKey(n), (_MAX_BLOCKS, 128))
    inside = (np.arange(_MAX_BLOCKS) >= start) & (np.arange(_MAX_BLOCKS) < n)
    x = jnp.where(inside[:, None], x, jnp.nan)
    acc, out = _run_bounded(cfg, x, start, n)
    assert np.isfinite(acc[0])
    np.testing.assert_allclose(acc[0], x[start:n].sum(), rtol=1e-5,
                               atol=1e-4)
    np.testing.assert_array_equal(out[start:n], x[start:n] * 2.0)


def test_vmem_budget_guard():
    cfg = PULConfig(distance=64, block_shape=(1024, 1024))
    with pytest.raises(ValueError, match="VMEM budget"):
        ring_scratch(cfg, (1024, 1024), jnp.float32)
