"""PUL core: the paper's contribution as a composable JAX/Pallas layer.

Public API:
  - PULConfig, IssueStrategy, MemoryTier, PEModel (pul.py)
  - PreloadStream, UnloadStream, pul_loop, ring_scratch, interpret_mode
    (pipeline.py)
  - DMAEngine, StreamStats, speedup (dma.py)
  - plan_stream, optimal_distance, predicted_speedup (planner.py)
"""
from repro.core.pul import (
    DRAM,
    HBM,
    MICROBLAZE,
    NVM,
    PES,
    REMOTE_HBM,
    TIERS,
    TPU_LANE,
    TPU_SUBLANE,
    TPU_V5E_MXU,
    TPU_V5E_VPU,
    UPMEM_DPU,
    Direction,
    IssueStrategy,
    MemoryTier,
    PEModel,
    PULConfig,
    TransferRequest,
)
from repro.core.pipeline import (
    VMEM_BUDGET_BYTES,
    PreloadStream,
    interpret_mode,
    UnloadStream,
    pul_loop,
    pul_streams,
    ring_scratch,
)
from repro.core.dma import (
    DMAEngine,
    KVPageWorkload,
    StreamStats,
    kv_page_latency_hidden,
    run_kv_page_workload,
    speedup,
)
from repro.core.planner import (
    Plan,
    choose_block_rows,
    kv_page_bytes,
    kv_page_flops,
    optimal_distance,
    plan_kv_page_stream,
    plan_stream,
    predicted_speedup,
    roofline_time,
)

__all__ = [
    "PULConfig", "IssueStrategy", "Direction", "MemoryTier", "PEModel",
    "TransferRequest", "DRAM", "NVM", "HBM", "REMOTE_HBM", "TIERS", "PES",
    "MICROBLAZE", "UPMEM_DPU", "TPU_V5E_VPU", "TPU_V5E_MXU",
    "TPU_LANE", "TPU_SUBLANE", "VMEM_BUDGET_BYTES",
    "PreloadStream", "UnloadStream", "pul_loop", "pul_streams", "ring_scratch",
    "interpret_mode",
    "DMAEngine", "StreamStats", "speedup",
    "KVPageWorkload", "run_kv_page_workload", "kv_page_latency_hidden",
    "Plan", "plan_stream", "optimal_distance", "choose_block_rows",
    "predicted_speedup", "roofline_time",
    "plan_kv_page_stream", "kv_page_bytes", "kv_page_flops",
]
