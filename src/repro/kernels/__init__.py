"""PUL Pallas kernels: the paper's technique at TPU compute hot-spots.

Each kernel pairs with a pure-jnp oracle in ref.py. Every kernel runs in
the Pallas interpreter on CPU and lowers to Mosaic on TPU
(`repro.core.interpret_mode`); ops.py exposes jit'd wrappers.
"""
from repro.kernels import ref
from repro.kernels.ops import (
    attention_op,
    filter_op,
    gather_op,
    matmul_op,
    sum_op,
)
from repro.kernels.pul_sum import pul_sum
from repro.kernels.pul_gather import pul_gather
from repro.kernels.pul_matmul import pul_matmul
from repro.kernels.pul_attention import (
    pul_attention,
    pul_paged_sweep_decode_attention,
    pul_paged_sweep_mla_decode_attention,
)
from repro.kernels.pul_filter import pul_filter
from repro.kernels.pul_decode import pul_decode_attention

__all__ = ["ref", "sum_op", "gather_op", "matmul_op", "attention_op",
           "filter_op", "pul_sum", "pul_gather", "pul_matmul",
           "pul_attention", "pul_filter", "pul_decode_attention",
           "pul_paged_sweep_decode_attention",
           "pul_paged_sweep_mla_decode_attention"]
