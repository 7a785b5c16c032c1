"""Paged KV cache managed by the PUL engine.

The serving-side realization of the paper's tiered-memory model: KV state is
split into fixed-size *pages* of ``page_tokens`` tokens (tile-aligned per
``core.pul.TPU_SUBLANE``), living in a pool of physical frames split across

  * a **hot tier** — the fast memory the decode kernels read (HBM on TPU;
    jnp arrays here), bounded at ``hot_frames`` pages, and
  * a **cold tier** — the slow memory (host DRAM / remote HBM; a numpy dict
    here) that evicted pages spill to, with real data movement both ways.

Eviction emits UNLOAD descriptors and restore emits PRELOAD descriptors
(`core.pul.TransferRequest`); restores are *planned*: `core.planner`
derives the preload distance d* = ceil(T_io / T_c) from page transfer time
vs per-page decode compute, and the restore batch is replayed through the
discrete-event twin (`core.dma`) so the engine reports how much restore
latency the schedule hides — the paper's claim, measured per serving step.

Hot storage comes in two layouts, both behind the versioned
:class:`KVStoreLayout` protocol (``KV_LAYOUT_VERSION``):

  * **per-layer planes** (v2, the kernel-true serving layout): each pageable
    cache leaf owns a *plane* whose leading axis is the layer (scan-group)
    index — attention leaves are ``(L, NF, K, P, hd)``, MLA's compressed
    leaves ``(L, NF, P, kvr)``. A plane IS the page-frame layout the decode
    kernels consume, so ``layer_view`` / ``page_view_tree`` are pure
    indexing — zero-copy under jit, no gather, no transpose — and the
    single-sweep decode kernel walks all layers of one plane with a
    prefetched layer scalar. The current token's rows are committed either
    *fused* (in the sweep kernel's epilogue, see
    ``kernels.pul_paged_sweep_decode_attention``) or *eagerly* via
    :meth:`KVStoreLayout.commit_token`.
  * **packed rows** (v1, the portable/oracle layout): token t of a page is
    one ``(F,)`` row concatenating every layer's features
    (:class:`PackedKVLayout` ``pack``/``unpack``); kept for the dense
    assembly oracle and for direct pool users (``KVPagePool(pcfg,
    features=F)``).

The cold tier always holds packed ``(P, F)`` rows regardless of the hot
layout, so UNLOAD/PRELOAD byte accounting, the DMA twin's KV-page workload,
and the lifecycle sanitizer are layout-independent.

Page *contents* pack every attention layer's K and V for a token range into
one logical page, so one page id covers the whole model and a prefix page
can be shared by every request with that prompt prefix (refcounted; only
full, immutable prompt pages are shared).
"""
from __future__ import annotations

import dataclasses
import warnings
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.events import EventKind, TraceLog
from repro.configs.base import ModelConfig
from repro.obs.tracer import NULL_TRACER
from repro.core.dma import DMAEngine, KVPageWorkload, run_kv_page_workload
from repro.core.planner import kv_page_flops, plan_kv_page_stream
from repro.core.pul import (
    Direction,
    MemoryTier,
    PEModel,
    HBM,
    REMOTE_HBM,
    TPU_LANE,
    TPU_SUBLANE,
    TPU_V5E_VPU,
    TransferRequest,
)

# kv-bearing cache leaves (dict key -> leaf is pageable when its seq axis
# matches max_seq): standard GQA attention and MLA's compressed cache
_KV_LEAF_KEYS = ("k", "v", "c_kv", "k_rope")

#: Version of the KV store-layout protocol. v1 was the ad-hoc
#: ``page_views``/``pack_new_rows`` pair over a single packed store plane;
#: v2 is the per-layer-plane :class:`KVStoreLayout` protocol below.
KV_LAYOUT_VERSION = 2


def _path_keys(path) -> Tuple[str, ...]:
    return tuple(getattr(p, "key", str(p)) for p in path)


def _lanes(n: int) -> int:
    """`n` rounded up to whole TPU lanes: the minor dim of a page plane."""
    return -(-n // TPU_LANE) * TPU_LANE


@dataclasses.dataclass(frozen=True)
class _LeafEntry:
    keys: Tuple[str, ...]       # dict path into the cache tree
    shape: Tuple[int, ...]      # full leaf shape
    grouped: bool               # True: (G, B, S, feat...); False: (B, S, feat...)
    nfeat: int                  # packed per-token features of this leaf
    offset: int                 # column offset in the packed row

    @property
    def plane_key(self) -> str:
        """Stable string id of this entry's store plane ("groups/0:global/k")."""
        return "/".join(self.keys)

    @property
    def feat(self) -> Tuple[int, ...]:
        """Per-token feature dims: (K, hd) for attention, (kvr,) for MLA."""
        return self.shape[3:] if self.grouped else self.shape[2:]

    @property
    def layers(self) -> int:
        """Leading layer (scan-group) extent of this entry's plane."""
        return self.shape[0] if self.grouped else 1


class KVStoreLayout:
    """Versioned protocol between the page pool, the decode kernels, the
    engine, and the DMA benchmark (``KV_LAYOUT_VERSION = 2``).

    A layout owns the mapping between a model's cache tree and physical
    page *planes* — one jnp array per pageable cache leaf, laid out so the
    kernels consume it directly:

      * attention leaves: ``(L, NF, K, P, hd)`` (layer, frame, kv head,
        page token, head dim)
      * MLA compressed leaves: ``(L, NF, P, feat)``

    with ``L`` the leaf's layer extent (scan groups; 1 for unscanned
    leaves), ``NF`` the pool's hot-frame count, and ``P`` tokens per page.
    The minor dim (``hd`` / ``feat``) is stored padded with zeros to whole
    128-lane tiles: Mosaic DMAs whole tiles, so a page of a narrower
    feature (MLA's 64-wide rope stream, a 64- or 112-wide head) cannot be
    sliced out of its plane otherwise. The TPU's HBM layout tiles the
    minor dim in 128-lane units too (the compiled kernel sees a 64-wide
    plane as 128 lanes), so the padding adds no device memory. Kernels
    pad their queries to the plane width; packed rows, the cold tier and
    all byte accounting carry only the real features.

    Required interface (all pure jnp unless stated):

      * :meth:`init_planes` — allocate zeroed planes for ``NF`` frames.
      * :meth:`layer_view` — ``{plane_key: (NF, ...) page frames}`` of one
        layer. **Zero-copy**: pure leading-axis indexing, no gather or
        transpose under jit (property-tested in
        ``tests/test_paged_sweep.py``).
      * :meth:`page_view_tree` — a cache tree whose pageable leaves are
        whole planes (grouped leaves keep their leading scan axis); the
        per-layer decode kernels address it directly.
      * :meth:`commit_token` — the *eager* commit: scatter one packed row
        per slot into ``(frame, offset)``. The *fused* commit is the same
        contract implemented in the sweep kernel's epilogue
        (``kernels.pul_paged_sweep_decode_attention``); the pool accounts
        it via :meth:`KVPagePool.note_fused_commit`.
      * :meth:`read_frame_packed` / :meth:`write_frame_packed` — bridge one
        frame to the packed ``(P, F)`` row layout the cold tier and DMA
        descriptors use (tier movement is layout-independent).
      * :meth:`pack_planes` — materialize the packed ``(NF, P, F)`` store
        (a copy; oracle/assembly path only).

    ``features`` (the packed row width F) and ``entries`` describe the
    geometry; ``layout_version`` pins the protocol revision a layout
    implements.
    """

    layout_version: int = KV_LAYOUT_VERSION
    features: int = 0
    entries: List[_LeafEntry] = []

    def init_planes(self, n_frames: int, page_tokens: int,
                    dtype) -> Dict[str, jnp.ndarray]:
        raise NotImplementedError

    def layer_view(self, planes: Dict[str, jnp.ndarray],
                   layer: int) -> Dict[str, jnp.ndarray]:
        raise NotImplementedError

    def page_view_tree(self, tree: Any,
                       planes: Dict[str, jnp.ndarray]) -> Any:
        raise NotImplementedError

    def commit_token(self, planes: Dict[str, jnp.ndarray],
                     rows: jnp.ndarray, frames, offsets,
                     dtype) -> Dict[str, jnp.ndarray]:
        raise NotImplementedError

    def read_frame_packed(self, planes: Dict[str, jnp.ndarray],
                          frame: int) -> np.ndarray:
        raise NotImplementedError

    def write_frame_packed(self, planes: Dict[str, jnp.ndarray], frame: int,
                           rows, dtype) -> Dict[str, jnp.ndarray]:
        raise NotImplementedError

    def pack_planes(self, planes: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        raise NotImplementedError


class PackedKVLayout(KVStoreLayout):
    """Mapping between a model's cache tree and its paged KV store.

    Implements :class:`KVStoreLayout` v2 (per-layer planes) and keeps the
    v1 packed-row codec: token t of slot b occupies row (b, t) — the
    concatenation over every pageable cache leaf of that token's features
    (all layers, all kv heads). `pack`/`unpack` are pure jnp functions
    (jit-able, shape-polymorphic in S so prefill buckets and the decode
    max_seq share one layout).
    """

    layout_version = KV_LAYOUT_VERSION

    def __init__(self, cfg: ModelConfig, batch: int, max_seq: int):
        from repro.models import transformer as T
        spec, _ = T.cache_specs(cfg, batch, max_seq)
        self.cfg = cfg
        self.batch = batch
        self.max_seq = max_seq
        self.entries: List[_LeafEntry] = []
        off = 0
        flat, _ = jax.tree_util.tree_flatten_with_path(spec)
        for path, leaf in sorted(flat, key=lambda kv: _path_keys(kv[0])):
            keys = _path_keys(path)
            if keys[-1] not in _KV_LEAF_KEYS:
                continue
            grouped = keys[0] == "groups"
            seq_ax = 2 if grouped else 1
            if len(leaf.shape) <= seq_ax or leaf.shape[seq_ax] != max_seq:
                continue
            nfeat = int(np.prod(leaf.shape)) // (batch * max_seq)
            self.entries.append(_LeafEntry(keys, tuple(leaf.shape), grouped,
                                           nfeat, off))
            off += nfeat
        self.features = off

    # ------------------------------------------------------------------ #
    def _get(self, tree: Any, keys: Tuple[str, ...]) -> Any:
        node = tree
        for k in keys:
            node = node[k]
        return node

    def _leaf_rows(self, leaf: jnp.ndarray, e: _LeafEntry) -> jnp.ndarray:
        """(B, S, nfeat) view of one cache leaf."""
        if e.grouped:                       # (G, B, S, feat...) -> (B, S, -1)
            G, B, S = leaf.shape[:3]
            x = jnp.moveaxis(leaf, 0, 2)    # (B, S, G, feat...)
            return x.reshape(B, S, -1)
        B, S = leaf.shape[:2]
        return leaf.reshape(B, S, -1)

    def pack(self, tree: Any) -> jnp.ndarray:
        """Cache tree -> (B, S, F) packed KV rows (S = tree's seq size)."""
        return jnp.concatenate(
            [self._leaf_rows(self._get(tree, e.keys), e)
             for e in self.entries], axis=-1)

    def pack_rows(self, tree: Any, idx: jnp.ndarray) -> jnp.ndarray:
        """One row per slot: (B, F) at per-slot positions `idx` (B,)."""
        B = idx.shape[0]
        rows = jnp.arange(B)
        outs = []
        for e in self.entries:
            leaf = self._get(tree, e.keys)
            S = leaf.shape[2 if e.grouped else 1]
            i = jnp.clip(idx, 0, S - 1)
            if e.grouped:
                x = jnp.moveaxis(leaf, 0, 2)        # (B, S, G, feat...)
                outs.append(x[rows, i].reshape(B, -1))
            else:
                outs.append(leaf[rows, i].reshape(B, -1))
        return jnp.concatenate(outs, axis=-1)

    def _pack_new_rows_impl(self, tree: Any) -> jnp.ndarray:
        outs = []
        for e in self.entries:
            leaf = self._get(tree, e.keys)
            if e.grouped:
                B = leaf.shape[1]
                outs.append(jnp.moveaxis(leaf, 0, 1).reshape(B, -1))
            else:
                outs.append(leaf.reshape(leaf.shape[0], -1))
        return jnp.concatenate(outs, axis=-1)

    def pack_new_rows(self, tree: Any) -> jnp.ndarray:
        """Deprecated v1 API: pack a paged-decode output tree's NEW-TOKEN
        rows into (B, F) for an out-of-kernel scatter.

        `tree` holds only the current token's features per pageable leaf —
        grouped (G, B, feat...) or ungrouped (B, feat...) — in `pack` entry
        order. Superseded by the :class:`KVStoreLayout` commit contract:
        the sweep kernel commits rows in its fused epilogue
        (`KVPagePool.note_fused_commit`) and the eager fallback is
        :meth:`commit_token` / `KVPagePool.write_rows`."""
        warnings.warn(
            "PackedKVLayout.pack_new_rows is deprecated; the KVStoreLayout "
            "protocol commits new-token rows fused (sweep-kernel epilogue) "
            "or eagerly via commit_token/KVPagePool.write_rows",
            PendingDeprecationWarning, stacklevel=2)
        return self._pack_new_rows_impl(tree)

    def _page_views_packed(self, tree: Any, store: jnp.ndarray) -> Any:
        NP, P, _ = store.shape
        new = jax.tree_util.tree_map(lambda x: x, tree)
        for e in self.entries:
            cols = store[:, :, e.offset:e.offset + e.nfeat]   # (NP, P, nfeat)
            feat = e.feat
            if e.grouped:
                G = e.shape[0]
                view = jnp.moveaxis(cols.reshape(NP, P, G, *feat), 2, 0)
            else:
                view = cols.reshape(NP, P, *feat)
            if len(feat) == 2:              # (K, hd) -> pages (.., NP, K, P, hd)
                view = jnp.swapaxes(view, -3, -2)
            node = new
            for k in e.keys[:-1]:
                node = node[k]
            node[e.keys[-1]] = view
        return new

    def page_views(self, tree: Any, store: jnp.ndarray) -> Any:
        """Deprecated v1 API: slice a PACKED store ((NP, P, F)) into
        per-layer kernel views — a gather/transpose under jit every step.

        Superseded by :meth:`page_view_tree` over per-layer planes, where
        the "view" is the stored array itself (zero-copy). Kept for one
        release for direct packed-store users."""
        warnings.warn(
            "PackedKVLayout.page_views is deprecated; use the KVStoreLayout "
            "protocol (page_view_tree/layer_view over per-layer planes, "
            "which are zero-copy) instead",
            PendingDeprecationWarning, stacklevel=2)
        return self._page_views_packed(tree, store)

    def unpack_into(self, tree: Any, packed: jnp.ndarray) -> Any:
        """Return `tree` with every pageable leaf replaced from `packed`
        ((B, S, F)); non-pageable leaves (SSM states, idx) pass through."""
        B, S, _ = packed.shape
        # tree_map rebuilds every container, so in-place edits below only
        # touch the fresh copy, never the caller's tree
        new = jax.tree_util.tree_map(lambda x: x, tree)
        for e in self.entries:
            cols = packed[..., e.offset:e.offset + e.nfeat]
            if e.grouped:
                G = e.shape[0]
                feat = e.shape[3:]
                leaf = jnp.moveaxis(cols.reshape(B, S, G, *feat), 2, 0)
            else:
                leaf = cols.reshape(B, S, *e.shape[2:])
            node = new
            for k in e.keys[:-1]:
                node = node[k]
            node[e.keys[-1]] = leaf.astype(self._get(tree, e.keys).dtype)
        return new

    # ------------------------------------------------------------------ #
    # KVStoreLayout v2: per-layer planes
    # ------------------------------------------------------------------ #
    def plane_shape(self, e: _LeafEntry, n_frames: int,
                    page_tokens: int) -> Tuple[int, ...]:
        feat = e.feat
        if len(feat) == 2:                  # attention: (L, NF, K, P, hd)
            return (e.layers, n_frames, feat[0], page_tokens,
                    _lanes(feat[1]))
        return (e.layers, n_frames, page_tokens,       # MLA: (L, NF, P, f)
                _lanes(feat[0]))

    def init_planes(self, n_frames: int, page_tokens: int,
                    dtype) -> Dict[str, jnp.ndarray]:
        """Zeroed per-layer page planes for `n_frames` physical frames."""
        return {e.plane_key: jnp.zeros(
                    self.plane_shape(e, n_frames, page_tokens), dtype)
                for e in self.entries}

    def layer_view(self, planes: Dict[str, jnp.ndarray],
                   layer: int) -> Dict[str, jnp.ndarray]:
        """One layer's page frames per plane — pure leading-axis indexing
        (zero-copy under jit): attention planes yield (NF, K, P, hd),
        MLA planes (NF, P, feat). Unscanned (L == 1) entries ignore
        `layer`."""
        return {e.plane_key:
                planes[e.plane_key][layer if e.layers > 1 else 0]
                for e in self.entries}

    def page_view_tree(self, tree: Any,
                       planes: Dict[str, jnp.ndarray]) -> Any:
        """Return `tree` with every pageable leaf replaced by its plane —
        THE stored array, not a slice of one (grouped leaves keep their
        leading scan axis; unscanned leaves drop their singleton layer
        axis). This is what makes the kernel-true decode zero-copy: the
        leaf the kernel addresses is the buffer the pool owns."""
        new = jax.tree_util.tree_map(lambda x: x, tree)
        for e in self.entries:
            plane = planes[e.plane_key]
            view = plane if e.grouped else plane[0]
            node = new
            for k in e.keys[:-1]:
                node = node[k]
            node[e.keys[-1]] = view
        return new

    def commit_token(self, planes: Dict[str, jnp.ndarray],
                     rows: jnp.ndarray, frames, offsets,
                     dtype) -> Dict[str, jnp.ndarray]:
        """Eager commit: scatter one packed (F,) row per slot into its
        (frame, offset) page position across every plane. The fused
        equivalent runs in the sweep kernel's epilogue."""
        frames = jnp.asarray(frames, jnp.int32)
        offsets = jnp.asarray(offsets, jnp.int32)
        B = rows.shape[0]
        out = dict(planes)
        for e in self.entries:
            cols = rows[:, e.offset:e.offset + e.nfeat].astype(dtype)
            plane = planes[e.plane_key]
            feat = e.feat
            if len(feat) == 2:
                vals = cols.reshape(B, e.layers, *feat)       # (B, L, K, hd)
                # advanced indices (frames @ axis 1, offsets @ axis 3) are
                # separated by a slice, so the broadcast B axis leads
                out[e.plane_key] = plane.at[
                    :, frames, :, offsets, :feat[-1]].set(vals)
            else:
                vals = cols.reshape(B, e.layers, *feat)       # (B, L, f)
                # adjacent advanced indices keep their position: (L, B, f)
                out[e.plane_key] = plane.at[:, frames, offsets, :feat[-1]].set(
                    jnp.moveaxis(vals, 0, 1))
        return out

    def read_frame_packed(self, planes: Dict[str, jnp.ndarray],
                          frame: int) -> np.ndarray:
        """One frame's packed (P, F) rows (numpy; cold-tier spill format)."""
        cols = []
        for e in self.entries:
            sl = np.asarray(planes[e.plane_key][:, frame, ..., :e.feat[-1]])
            if len(e.feat) == 2:            # (L, K, P, hd) -> (P, L*K*hd)
                sl = sl.transpose(2, 0, 1, 3)
            else:                           # (L, P, f) -> (P, L*f)
                sl = sl.transpose(1, 0, 2)
            cols.append(sl.reshape(sl.shape[0], -1))
        return np.concatenate(cols, axis=-1)

    def write_frame_packed(self, planes: Dict[str, jnp.ndarray], frame: int,
                           rows, dtype) -> Dict[str, jnp.ndarray]:
        """Fill one frame from packed (P, F) rows (cold-tier restore /
        prefill page fill); returns the updated planes dict."""
        rows = jnp.asarray(rows).astype(dtype)
        P = rows.shape[0]
        out = dict(planes)
        for e in self.entries:
            cols = rows[:, e.offset:e.offset + e.nfeat]
            feat = e.feat
            if len(feat) == 2:              # (P, L, K, hd) -> (L, K, P, hd)
                vals = cols.reshape(P, e.layers, *feat).transpose(1, 2, 0, 3)
            else:                           # (P, L, f) -> (L, P, f)
                vals = cols.reshape(P, e.layers, *feat).transpose(1, 0, 2)
            out[e.plane_key] = planes[e.plane_key].at[
                :, frame, ..., :feat[-1]].set(vals)
        return out

    def pack_planes(self, planes: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        """Materialize the packed (NF, P, F) store from the planes — a
        COPY; only the dense-assembly oracle path pays it."""
        cols = []
        for e in self.entries:
            plane = planes[e.plane_key][..., :e.feat[-1]]
            if len(e.feat) == 2:            # (L,NF,K,P,hd) -> (NF,P,L,K,hd)
                sl = jnp.transpose(plane, (1, 3, 0, 2, 4))
            else:                           # (L,NF,P,f) -> (NF,P,L,f)
                sl = jnp.transpose(plane, (1, 2, 0, 3))
            cols.append(sl.reshape(sl.shape[0], sl.shape[1], -1))
        return jnp.concatenate(cols, axis=-1)


# -------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class PageConfig:
    """Knobs of the paged-KV pool (the serving face of PULConfig)."""

    page_tokens: int = 16               # tokens per page, TPU_SUBLANE-aligned
    hot_frames: int = 0                 # 0 -> sized to fit every live slot
    fast_tier: MemoryTier = HBM
    slow_tier: MemoryTier = REMOTE_HBM
    pe: PEModel = TPU_V5E_VPU
    preload_distance: Optional[int] = None   # None -> planner d*
    fifo_depth: int = 64
    share_prefix_pages: bool = True
    trace: bool = False             # record page-lifecycle events for the
                                    # sanitizer (repro.analysis); off = the
                                    # pool never touches the trace path, so
                                    # production pays zero overhead

    def __post_init__(self):
        if self.page_tokens % TPU_SUBLANE != 0:
            raise ValueError(
                f"page_tokens ({self.page_tokens}) must be a multiple of "
                f"TPU_SUBLANE ({TPU_SUBLANE}) to keep page DMAs tile-aligned")


@dataclasses.dataclass
class PoolMetrics:
    page_faults: int = 0        # pages restored from the cold tier
    evictions: int = 0          # pages written out to the cold tier
    shared_hits: int = 0        # prompt pages reused via prefix sharing
    pages_allocated: int = 0
    modeled_restore_time: float = 0.0   # DMA-twin time of all restore batches
    modeled_restore_stall: float = 0.0  # PE stall within those batches
    # cache-economics counters (repro.obs.metrics.cache_economics):
    bytes_hot_written: int = 0  # bytes scattered into the hot store (prefill
                                # page fills + decode row commits, fused or
                                # eager)
    # prefetch-quality counters for planned d* restores (accuracy /
    # timeliness / coverage, per the prefetching survey in PAPERS.md):
    planned_preloads: int = 0   # restores issued through ensure_hot's
                                # planned d* batch
    unplanned_restores: int = 0  # demand restores outside a planned batch
                                # (none today; exists so a speculative
                                # planner's misses become visible)
    useful_preloads: int = 0    # restored pages read before re-eviction
    wasted_preloads: int = 0    # restored pages evicted/freed unread
    descriptors: List[TransferRequest] = dataclasses.field(default_factory=list)

    @property
    def modeled_latency_hidden(self) -> float:
        """Fraction of restore wall-time the planned preload overlapped."""
        if self.modeled_restore_time <= 0:
            return 1.0
        return 1.0 - self.modeled_restore_stall / self.modeled_restore_time

    def validate(self) -> None:
        """Cross-check the counters' arithmetic invariants; raises
        ValueError naming the broken one. Called from the engine's metrics
        hook so a drifted counter surfaces at the snapshot that drifted,
        not in a downstream report."""
        for name in ("page_faults", "evictions", "shared_hits",
                     "pages_allocated", "bytes_hot_written",
                     "planned_preloads", "unplanned_restores",
                     "useful_preloads", "wasted_preloads"):
            v = getattr(self, name)
            if v < 0:
                raise ValueError(f"PoolMetrics.{name} is negative ({v})")
        if (self.useful_preloads + self.wasted_preloads
                > self.planned_preloads + self.unplanned_restores):
            raise ValueError(
                "PoolMetrics: more preload outcomes (useful + wasted) than "
                "restores issued")
        if self.modeled_restore_time < 0 or self.modeled_restore_stall < 0:
            raise ValueError("PoolMetrics modeled restore times are negative")
        # every restore re-loads a page that previously spilled: the planned
        # preloads (PRELOAD descriptors) must pair 1:1 with page faults, and
        # can never outnumber the evictions that created cold copies
        preloads = sum(1 for d in self.descriptors
                       if d.direction is Direction.PRELOAD)
        unloads = sum(1 for d in self.descriptors
                      if d.direction is Direction.UNLOAD)
        if preloads != self.page_faults:
            raise ValueError(
                f"PoolMetrics: {preloads} PRELOAD descriptors but "
                f"{self.page_faults} page faults (restores must be planned)")
        if unloads != self.evictions:
            raise ValueError(
                f"PoolMetrics: {unloads} UNLOAD descriptors but "
                f"{self.evictions} evictions")
        if self.page_faults > self.evictions:
            raise ValueError(
                f"PoolMetrics: {self.page_faults} restores exceed "
                f"{self.evictions} evictions — a page was restored that "
                "never spilled")
        hidden = self.modeled_latency_hidden
        if not 0.0 <= hidden <= 1.0:
            raise ValueError(
                f"PoolMetrics.modeled_latency_hidden = {hidden} out of "
                "[0, 1]")


@dataclasses.dataclass
class _PageMeta:
    frame: Optional[int]        # hot frame index, or None when cold
    refcount: int = 1
    last_used: int = 0
    shared_key: Optional[tuple] = None
    deadline: float = float("inf")   # owning request's TTFT deadline tick
                                     # (inf: none) — eviction prefers pages
                                     # whose requests can afford the restore
    pending_read: bool = False  # restored but not yet read: cleared at first
                                # READ (a useful preload), still set at the
                                # next evict/free (a wasted one) — the
                                # prefetch-accuracy bookkeeping


ZERO_FRAME = 0      # reserved all-zeros frame (unallocated page-table slots)
TRASH_FRAME = 1     # reserved write sink (inactive slots' decode writes)
RESERVED_FRAMES = 2


class KVPagePool:
    """Physical page frames + residency + refcounts + tier movement.

    Two hot-storage modes behind one lifecycle:

      * ``KVPagePool(pcfg, features=F)`` — packed mode (v1): one
        ``(NF, P, F)`` store array, exposed as ``pool.store``.
      * ``KVPagePool(pcfg, layout=<KVStoreLayout>)`` — per-layer mode (v2):
        storage is ``pool.planes`` (one plane per pageable cache leaf; see
        :class:`KVStoreLayout`) and all data movement delegates to the
        layout. The packed view, when the oracle path needs it, is
        :meth:`packed_store`.

    Frame ids, page ids, refcounts, eviction order, DMA descriptors, and
    the lifecycle trace are identical across modes — a frame spans every
    layer plane, so the cold tier and byte accounting stay packed."""

    def __init__(self, pcfg: PageConfig, features: Optional[int] = None, *,
                 layout: Optional[KVStoreLayout] = None,
                 gqa_group: int = 1, dtype=jnp.bfloat16, tracer=None):
        if (features is None) == (layout is None):
            raise ValueError(
                "KVPagePool takes exactly one of `features` (packed mode) "
                "or `layout` (per-layer mode)")
        self.cfg = pcfg
        self.layout = layout
        self.features = layout.features if layout is not None else features
        self.dtype = dtype
        P = pcfg.page_tokens
        self.page_bytes = P * self.features * jnp.dtype(dtype).itemsize
        self.row_bytes = self.features * jnp.dtype(dtype).itemsize
        n = max(pcfg.hot_frames, RESERVED_FRAMES + 1)
        if layout is not None:
            self.planes: Dict[str, jnp.ndarray] = layout.init_planes(
                n, P, dtype)
            self._n_frames = n
            # layer extent of the store (sweep-kernel SMEM scalar range +
            # per-layer trace provenance)
            self.n_layers = max((e.layers for e in layout.entries), default=1)
        else:
            self.store = jnp.zeros((n, P, self.features), dtype)
            self._n_frames = n
            self.n_layers = 1
        self.free_frames: List[int] = list(range(RESERVED_FRAMES, n))
        self.pages: "OrderedDict[int, _PageMeta]" = OrderedDict()
        self.cold: Dict[int, np.ndarray] = {}
        self.prefix_index: Dict[tuple, int] = {}
        self.metrics = PoolMetrics()
        # lifecycle event trace for the sanitizer (repro.analysis); None
        # when tracing is off — every emission site guards on this, so the
        # untraced hot path never builds an event
        self.trace: Optional[TraceLog] = TraceLog() if pcfg.trace else None
        # unified tracer (repro.obs): page-lifecycle events are bridged into
        # the same stream as engine spans and DMA descriptors; NULL_TRACER
        # keeps every emission site a cheap attribute check when off
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self._bridge_seq = 0    # event sequence when TraceLog is off
        self._next_id = 1
        self._clock = 0
        # restore planning: d* from page transfer time vs per-page compute
        self.plan = plan_kv_page_stream(
            page_tokens=P, kv_features=self.features, tier=pcfg.slow_tier,
            pe=pcfg.pe, gqa_group=gqa_group, fifo_depth=pcfg.fifo_depth,
            itemsize=jnp.dtype(dtype).itemsize)
        self.distance = pcfg.preload_distance or self.plan.cfg.distance
        self._dma = DMAEngine(pcfg.slow_tier, pcfg.pe,
                              fifo_depth=pcfg.fifo_depth,
                              tracer=self.tracer)
        self._flops_per_page = kv_page_flops(P, self.features, gqa_group)

    # ------------------------------------------------------------------ #
    @property
    def hot_frames(self) -> int:
        return self._n_frames

    @property
    def capacity(self) -> int:
        """Usable hot frames (page working set must fit here per step)."""
        return self.hot_frames - RESERVED_FRAMES

    def hot_in_use(self) -> int:
        return sum(1 for m in self.pages.values() if m.frame is not None)

    def packed_store(self) -> jnp.ndarray:
        """The packed (NF, P, F) store: the array itself in packed mode, a
        materialized copy of the planes in per-layer mode (oracle path)."""
        if self.layout is not None:
            return self.layout.pack_planes(self.planes)
        return self.store

    # ------------------------------------------------------------------ #
    # layout-dispatched frame data movement (cold tier stays packed)
    # ------------------------------------------------------------------ #
    def _read_frame(self, frame: int) -> np.ndarray:
        if self.layout is not None:
            return self.layout.read_frame_packed(self.planes, frame)
        return np.asarray(self.store[frame])

    def _write_frame(self, frame: int, rows) -> None:
        if self.layout is not None:
            self.planes = self.layout.write_frame_packed(
                self.planes, frame, rows, self.dtype)
        else:
            self.store = self.store.at[frame].set(
                jnp.asarray(rows).astype(self.dtype))

    def _scatter_rows(self, frames, offsets, rows) -> None:
        if self.layout is not None:
            self.planes = self.layout.commit_token(
                self.planes, rows, frames, offsets, self.dtype)
        else:
            self.store = self.store.at[
                jnp.asarray(frames), jnp.asarray(offsets)].set(
                    rows.astype(self.dtype))

    # ------------------------------------------------------------------ #
    def _emit(self, kind: EventKind, **fields) -> None:
        if self.trace is not None:
            self.trace.emit(self._clock, kind, **fields)
        if self.tracer.enabled:
            seq = (self.trace.events[-1].seq if self.trace is not None
                   else self._bridge_seq)
            self._bridge_seq = seq + 1
            self.tracer.page_event(seq, self._clock, kind, fields)

    def tick(self) -> None:
        self._clock += 1
        self._emit(EventKind.TICK)

    def alloc(self, shared_key: Optional[tuple] = None, *,
              needed: Sequence[int] = ()) -> int:
        """Allocate a fresh page in the hot tier; returns its page id.

        `needed` is the caller's CURRENT working set (page ids the ongoing
        step still has to read): frame stealing will never evict them, so an
        allocation can't trigger a same-step fault/restore round-trip."""
        pid = self._next_id
        self._next_id += 1
        frame = self._take_frame(needed=needed)
        self.pages[pid] = _PageMeta(frame=frame, last_used=self._clock,
                                    shared_key=shared_key)
        if shared_key is not None:
            self.prefix_index[shared_key] = pid
        self.metrics.pages_allocated += 1
        self._emit(EventKind.ALLOC, pid=pid, frame=frame, refcount=1,
                   shared_key=shared_key)
        return pid

    def lookup_shared(self, key: tuple) -> Optional[int]:
        if not self.cfg.share_prefix_pages:
            return None
        pid = self.prefix_index.get(key)
        if pid is not None:
            self.pages[pid].refcount += 1
            self.metrics.shared_hits += 1
            self._emit(EventKind.REF, pid=pid,
                       refcount=self.pages[pid].refcount, shared_key=key)
        return pid

    def ref(self, pid: int) -> None:
        self.pages[pid].refcount += 1
        self._emit(EventKind.REF, pid=pid, refcount=self.pages[pid].refcount)

    def unref(self, pid: int) -> None:
        meta = self.pages[pid]
        meta.refcount -= 1
        self._emit(EventKind.UNREF, pid=pid, refcount=meta.refcount)
        if meta.refcount > 0:
            return
        if meta.pending_read:               # freed without ever being read
            meta.pending_read = False
            self.metrics.wasted_preloads += 1
        if meta.shared_key is not None:
            self.prefix_index.pop(meta.shared_key, None)
        if meta.frame is not None:
            self.free_frames.append(meta.frame)
        self.cold.pop(pid, None)
        del self.pages[pid]
        self._emit(EventKind.FREE, pid=pid)

    # ------------------------------------------------------------------ #
    def note_deadline(self, pids: Sequence[int], deadline: float) -> None:
        """Tag pages with their owning request's absolute TTFT-deadline
        tick (inf: no deadline). Eviction orders victims by LATEST deadline
        first — a page whose request has slack can afford the restore
        round-trip; one racing a deadline cannot. The engine refreshes tags
        at every admission/resume, so a shared page carries its most recent
        requester's urgency (a deliberate, cheap approximation)."""
        for pid in pids:
            self.pages[pid].deadline = deadline
            self._emit(EventKind.DEADLINE, pid=pid, deadline=deadline)

    def _take_frame(self, needed: Sequence[int]) -> int:
        """Get a free hot frame, evicting pages not in `needed` — latest
        request deadline first (deadline-aware), then LRU within a tie."""
        if self.free_frames:
            return self.free_frames.pop()
        needed = set(needed)
        victims = sorted(
            ((-m.deadline, m.last_used), pid) for pid, m in self.pages.items()
            if m.frame is not None and pid not in needed)
        if not victims:
            raise RuntimeError(
                f"hot tier exhausted: {self.capacity} frames all needed this "
                "step; raise PageConfig.hot_frames or admit fewer tokens")
        _, victim = victims[0]
        self.evict(victim, cause="steal", pinned=needed)
        return self.free_frames.pop()

    def evict(self, pid: int, *, cause: str = "explicit",
              pinned: Sequence[int] = ()) -> None:
        """Hot -> cold: real data movement + an UNLOAD descriptor.

        `cause` is sanitizer provenance: "steal" marks capacity evictions
        (which must follow the deadline-then-LRU victim order over the
        non-`pinned` hot pages); "explicit" marks policy-driven spills
        (preemption, pause) that are exempt from victim-order checks."""
        meta = self.pages[pid]
        assert meta.frame is not None, f"page {pid} already cold"
        if meta.pending_read:               # restored but never read before
            meta.pending_read = False       # spilling again: wasted preload
            self.metrics.wasted_preloads += 1
        self._emit(EventKind.EVICT, pid=pid, frame=meta.frame, cause=cause,
                   pinned=tuple(sorted(pinned)))
        self.cold[pid] = self._read_frame(meta.frame)
        self.free_frames.append(meta.frame)
        self.metrics.evictions += 1
        self.metrics.descriptors.append(TransferRequest(
            Direction.UNLOAD, src=meta.frame * self.page_bytes,
            dst=pid * self.page_bytes, nbytes=self.page_bytes, tag=pid))
        meta.frame = None

    def evict_pages(self, pids: Sequence[int]) -> None:
        for pid in pids:
            if self.pages[pid].frame is not None:
                self.evict(pid)

    def ensure_hot(self, pids: Sequence[int]) -> int:
        """Restore any cold page in `pids`; returns the page-fault count.

        Restores are issued as one planned batch: preload distance d* (from
        `core.planner`), BATCH issue order, and the batch is replayed on the
        DMA twin to account the modeled stall (the per-step page-fault cost
        a TPU deployment would see).
        """
        self.tick()
        faults = []
        for pid in pids:
            meta = self.pages[pid]
            meta.last_used = self._clock
            self._emit(EventKind.TOUCH, pid=pid)
            if meta.frame is None:
                faults.append(pid)
        for pid in faults:
            meta = self.pages[pid]
            frame = self._take_frame(needed=pids)
            data = self.cold.pop(pid)
            self._write_frame(frame, data)
            meta.frame = frame
            meta.pending_read = True
            self._emit(EventKind.RESTORE, pid=pid, frame=frame)
            self.metrics.descriptors.append(TransferRequest(
                Direction.PRELOAD, src=pid * self.page_bytes,
                dst=frame * self.page_bytes, nbytes=self.page_bytes, tag=pid))
        if faults:
            self.metrics.page_faults += len(faults)
            self.metrics.planned_preloads += len(faults)
            stats = run_kv_page_workload(
                self._dma,
                KVPageWorkload(page_bytes=self.page_bytes,
                               flops_per_page=self._flops_per_page,
                               pages_per_step=len(faults), steps=1),
                distance=self.distance)
            self.metrics.modeled_restore_time += stats.total_time
            self.metrics.modeled_restore_stall += stats.stall_time
        return len(faults)

    # ------------------------------------------------------------------ #
    def frames_of(self, pids: Sequence[Optional[int]]) -> np.ndarray:
        """Physical frame per page id (ZERO_FRAME for unallocated slots).
        All pages must be hot (call ensure_hot first)."""
        out = np.full((len(pids),), ZERO_FRAME, np.int32)
        for i, pid in enumerate(pids):
            if pid is None:
                continue
            meta = self.pages[pid]
            if meta.pending_read:           # first read since restore:
                meta.pending_read = False   # the preload was useful
                self.metrics.useful_preloads += 1
            if self.trace is not None or self.tracer.enabled:
                self._emit(EventKind.READ, pid=pid, frame=meta.frame)
            frame = meta.frame
            assert frame is not None, f"page {pid} is cold at gather time"
            out[i] = frame
        return out

    def write_page(self, pid: int, rows: jnp.ndarray, n_valid: int) -> None:
        """Fill (a prefix of) one hot page with packed KV rows."""
        meta = self.pages[pid]
        # the event precedes the scatter so a write to a cold page is in
        # the trace even if the scatter itself corrupts the store
        self._emit(EventKind.WRITE_PAGE, pid=pid, frame=meta.frame,
                   n_valid=n_valid)
        P = self.cfg.page_tokens
        pad = P - n_valid
        if pad:
            rows = jnp.pad(rows[:n_valid], ((0, pad), (0, 0)))
        self._write_frame(meta.frame, rows)
        self.metrics.bytes_hot_written += self.page_bytes

    def write_rows(self, frames: np.ndarray, offsets: np.ndarray,
                   rows: jnp.ndarray) -> None:
        """Eagerly commit one packed row per slot into (frame, offset)
        positions — the out-of-kernel half of the KVStoreLayout commit
        contract (`commit_token`). Inactive slots should point at
        TRASH_FRAME."""
        # the event precedes validation so a zero-frame write reaches the
        # sanitizer trace even though the assert stops the scatter
        self._emit(EventKind.WRITE_ROWS,
                   frames=tuple(int(f) for f in frames))
        # validate BEFORE the scatter: the reserved zero frame backs every
        # unallocated page-table slot and must stay all-zeros
        assert ZERO_FRAME not in frames.tolist(), "write to the zero frame"
        live = sum(1 for f in frames.tolist() if f != TRASH_FRAME)
        self.metrics.bytes_hot_written += live * self.row_bytes
        self._scatter_rows(frames, offsets, rows)

    def note_fused_commit(self, frames: np.ndarray,
                          offsets: np.ndarray) -> None:
        """Account a FUSED commit: the sweep kernel's epilogue scatters the
        current token's rows into the planes in-kernel (one write per
        layer), so no host-side scatter runs — only validation, byte
        accounting, and the lifecycle trace happen here. Call BEFORE the
        kernel so the events precede the writes they describe (the same
        order `write_rows` guarantees), and so a zero-frame table stops
        the step before the kernel touches the reserved frame."""
        del offsets  # positions are per-layer-identical; frames identify pages
        for layer in range(self.n_layers):
            self._emit(EventKind.WRITE_ROWS, layer=layer,
                       frames=tuple(int(f) for f in frames))
        assert ZERO_FRAME not in frames.tolist(), "write to the zero frame"
        live = sum(1 for f in frames.tolist() if f != TRASH_FRAME)
        self.metrics.bytes_hot_written += live * self.row_bytes
