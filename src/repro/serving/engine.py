"""Serving engines: dense reference + paged, PUL-tiered continuous batching.

Two engines share the zoo's prefill/decode entry points:

  * :class:`ServingEngine` — the dense-cache reference ("continuous-
    batching-lite"): a fixed pool of B slots over monolithic per-slot KV
    that never leaves fast memory; admission re-prefills the batch. Kept as
    the differential-test oracle and as the simplest serving path.

  * :class:`PagedServingEngine` — the production-shaped engine this repo
    exists to showcase: KV lives in fixed-size pages managed by the PUL
    page pool (`serving.kv_pages`), requests are admitted by a token-budget
    scheduler (`serving.scheduler`), slots refill per step without touching
    their neighbours (per-slot cache fill levels), same-bucket requests
    sharing a page-aligned prompt prefix share prompt pages, and cold pages
    ride UNLOAD/PRELOAD descriptors planned at the paper's d* distance.

Decode runs one of two equivalent paths:

  * **assembly** (default): each step's dense cache view is rebuilt from
    pages (token r of slot b == packed row r) — optionally through the
    page-indexed PUL gather (``use_pallas_gather=True``) — then decoded as
    usual; greedy token streams match the dense reference bit-for-bit, the
    invariant `tests/test_paged_serving.py` enforces. Kept as the oracle.
  * **kernel-true** (``use_paged_kernel=True``): attention streams straight
    over the page frames (`kernels.pul_paged_decode_attention`, or the MLA
    variant over compressed pages), the page table acting as the preload
    trace; the current token's K/V merges into the online softmax in-kernel
    and is scattered into its tail page afterwards. No dense per-slot view
    is ever materialized — the serving realization of the paper's claim.

Fully-shared prompts are cheaper still: when a request's whole page-aligned
prompt already lives in shared pages, admission refs the pages and replays
the cached first-token logits — zero prefill compute (`prefill_skips`).

Scheduling is policy-driven (``PagedEngineConfig.policy``): ``fcfs`` is the
original strict-FIFO admission; ``priority`` and ``slo-edf`` additionally
PREEMPT running requests to make room for urgent arrivals — the victim's
private pages spill to the cold tier (the existing swap-out machinery), its
slot is vacated, and the request requeues for readmission, resuming
mid-decode from its restored pages token-for-token. ``slo-edf`` orders the
queue by TTFT deadline and preempts only when a pending deadline would
otherwise be missed (no running slot frees up in time).

Chunked prefill (``prefill_chunk_tokens > 0``): long prompts prefill in
page-aligned chunks, ONE bounded pass per engine tick, interleaved with the
decode step — a long prompt can no longer head-of-line-block every short
request's decode tick. Each pass re-runs the compiled bucket prefill over
the prompt prefix so far (the smallest bucket that fits it, so per-tick
prefill span is bounded by the prefix, not the full prompt) and banks the
new chunk's KV pages; rows are bitwise identical to a monolithic prefill
(causal attention: row t depends only on tokens <= t), so token streams
stay dense-reference-exact. The slot joins decode on the pass that
completes the prompt. On a real accelerator each pass would attend to the
banked pages instead of recomputing the prefix; the scheduling shape — and
the per-tick latency bound that protects decode — is the same.

MoE caveat: capacity-factor dispatch mixes tokens across the batch, so MoE
archs serve fine but are not bitwise batch-size-invariant; the differential
zoo subset uses dense archs.
"""
from __future__ import annotations

import dataclasses
import functools
import time
import warnings
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import zoo
from repro.obs.metrics import (
    MetricsRegistry,
    cache_economics,
    economics_into_registry,
)
from repro.obs.tracer import NULL_TRACER, Tracer
from repro.serving.kv_pages import (
    KVPagePool,
    PackedKVLayout,
    PageConfig,
    TRASH_FRAME,
    ZERO_FRAME,
    _path_keys,
)
from repro.serving.scheduler import (
    Admission,
    AdmissionScheduler,
    Request,
    SchedulerConfig,
)


def percentile(xs, q: float) -> float:
    """q-th percentile (linear interpolation) as a plain float.

    Degenerate inputs are first-class: an EMPTY sample returns 0.0 instead
    of raising (np.percentile([]) crashes), so a metrics snapshot taken on
    a tiny/zero-length run — exactly what the SLO benchmark's smoke config
    produces — can never take the engine down."""
    xs = list(xs)
    if not xs:
        return 0.0
    return float(np.percentile(np.asarray(xs, np.float64), q))


def mean(xs) -> float:
    """Mean as a plain float; 0.0 for an empty sample (np.mean([]) is nan
    with a RuntimeWarning — poison for a JSON metrics report)."""
    xs = list(xs)
    if not xs:
        return 0.0
    return float(np.mean(np.asarray(xs, np.float64)))


def _set_idx(tree, idx: np.ndarray):
    """Overwrite every cache `idx` leaf with per-slot fill levels."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    vec = jnp.asarray(idx, jnp.int32)
    out = []
    for path, leaf in flat:
        keys = _path_keys(path)
        if keys[-1] == "idx":
            leaf = jnp.broadcast_to(vec, leaf.shape).astype(leaf.dtype)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


def _drain_results(requests: Dict[int, Request]) -> Dict[int, List[int]]:
    """Collect every tracked request's output and prune the completed ones
    (a long-lived engine must not accumulate historical requests)."""
    out = {rid: r.out_tokens for rid, r in requests.items()}
    for rid in [rid for rid, r in requests.items() if r.done]:
        del requests[rid]
    return out


# ========================================================================== #
# dense reference engine
# ========================================================================== #
@dataclasses.dataclass(frozen=True)
class EngineConfig:
    batch_slots: int = 4
    max_seq: int = 256
    prefill_bucket: int = 64
    greedy: bool = True
    sample_seed: int = 0            # rng seed for greedy=False sampling
                                    # (mirrors PagedEngineConfig.sample_seed
                                    # so sampling runs are differential-
                                    # testable across the two engines)


class ServingEngine:
    """Dense-cache slot engine (right-padded bucket prefill, batch re-prefill
    on admission). The differential-test oracle for the paged engine."""

    def __init__(self, cfg: ModelConfig, params, engine_cfg: EngineConfig = EngineConfig()):
        from repro.serving.config import ServingConfig
        if isinstance(engine_cfg, ServingConfig):
            engine_cfg = engine_cfg.dense()
        # token-indexed caches, as in the paged engine: right-padded rows of
        # any length share one layout, and sliding windows are a mask term
        self.model_cfg = dataclasses.replace(cfg, paged_kv=True)
        self.cfg = engine_cfg
        self.model = zoo.build_model(self.model_cfg)
        self.params = params
        B, S = engine_cfg.batch_slots, engine_cfg.max_seq
        self._prefill = jax.jit(
            lambda p, b: self.model.prefill(p, b, max_seq=S))
        self._decode = jax.jit(self.model.decode_step)
        self.caches = None
        self.slot_req: List[Optional[Request]] = [None] * B
        self.slot_pos: np.ndarray = np.zeros((B,), np.int32)  # next position
        self.queue: List[Request] = []
        self.requests: Dict[int, Request] = {}   # every request ever submitted
        self._rng = np.random.default_rng(engine_cfg.sample_seed)

    # ------------------------------------------------------------------ #
    def submit(self, req: Request):
        self.requests[req.rid] = req
        self.queue.append(req)

    def _free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slot_req) if r is None]

    def _admit(self):
        """Fill free slots; (re)prefill the whole batch when admitting.

        A production engine prefills only new slots with per-slot cache
        writes (see PagedServingEngine); to keep one compiled path we
        re-prefill the batch — same results, admission just costs a batch
        prefill (documented trade)."""
        free = self._free_slots()
        if not free or not self.queue:
            return
        while free and self.queue:
            self.slot_req[free.pop(0)] = self.queue.pop(0)
        self._prefill_all()

    def _prefill_all(self):
        """Right-padded prefill of every occupied slot: a row's logits come
        from its last real token, and its cache fill level and next
        position are its own length (padding rows are masked, then
        overwritten by decode)."""
        B, bucket = self.cfg.batch_slots, self.cfg.prefill_bucket
        toks = np.zeros((B, bucket), np.int32)
        lengths = np.ones((B,), np.int32)
        for i, r in enumerate(self.slot_req):
            if r is None:
                continue
            prompt = (r.prompt + r.out_tokens)[-bucket:]
            toks[i, :len(prompt)] = prompt
            lengths[i] = len(prompt)
        batch = {"tokens": jnp.asarray(toks), "lengths": jnp.asarray(lengths)}
        logits, caches = self._prefill(self.params, batch)
        self.caches = _set_idx(caches, lengths)
        self.slot_pos = lengths
        self._emit(np.asarray(logits))

    def _emit(self, logits: np.ndarray):
        for i, r in enumerate(self.slot_req):
            if r is None:
                continue
            if self.cfg.greedy:
                nxt = int(np.argmax(logits[i]))
            else:
                z = logits[i].astype(np.float64) - logits[i].max()
                p = np.exp(z)
                nxt = int(self._rng.choice(p.shape[-1], p=p / p.sum()))
            r.out_tokens.append(nxt)
            if len(r.out_tokens) >= r.max_new_tokens:
                r.done = True
                self.slot_req[i] = None

    # ------------------------------------------------------------------ #
    def step(self):
        """One engine tick: admit + one decode step for all live slots."""
        self._admit()
        if self.caches is None or all(r is None for r in self.slot_req):
            return
        B = self.cfg.batch_slots
        toks = np.zeros((B, 1), np.int32)
        for i, r in enumerate(self.slot_req):
            if r is not None and r.out_tokens:
                toks[i, 0] = r.out_tokens[-1]
        batch = {"tokens": jnp.asarray(toks),
                 "pos0": jnp.asarray(self.slot_pos)}
        logits, self.caches = self._decode(self.params, batch, self.caches)
        self.slot_pos = self.slot_pos + 1
        self._emit(np.asarray(logits))

    def run(self, max_ticks: int = 1000) -> Dict[int, List[int]]:
        """Drive steps until every tracked request completes (or the tick
        cap); returns {rid: generated tokens} for ALL submitted requests —
        including those already admitted into slots before run() was called
        (a queue-only snapshot would silently drop their outputs)."""
        ticks = 0
        pending = lambda: self.queue or any(r is not None for r in self.slot_req)
        while pending() and ticks < max_ticks:
            self.step()
            ticks += 1
        return _drain_results(self.requests)


# ========================================================================== #
# paged engine
# ========================================================================== #
@dataclasses.dataclass(frozen=True)
class PagedEngineConfig:
    batch_slots: int = 4
    max_seq: int = 256
    page_tokens: int = 16
    hot_pages: int = 0              # 0 -> size for every live slot resident
    prefill_buckets: Tuple[int, ...] = (16, 32, 64)
    max_active_tokens: int = 0      # 0 -> slots * max_seq
    preload_distance: Optional[int] = None   # None -> planner d*
    share_prefix_pages: bool = True
    use_pallas_gather: bool = False  # route page assembly through pul_gather
    use_paged_kernel: bool = False   # kernel-true decode: attention streams
                                     # straight over pages (no dense assembly);
                                     # False keeps assemble-then-attend as the
                                     # oracle path
    sweep_decode: bool = True        # kernel-true decode as ONE sweep: the
                                     # layer scan walks the full per-layer
                                     # planes (zero-copy views), the sweep
                                     # kernel selects its layer via an SMEM
                                     # scalar and commits the new token's
                                     # rows in its fused epilogue. False
                                     # keeps the per-layer launch + eager
                                     # write_rows scatter (parity baseline)
    policy: str = "fcfs"            # "fcfs" | "priority" | "slo-edf"
    prefill_chunk_tokens: int = 0   # >0: prompts longer than this prefill in
                                    # page-aligned chunks, one pass per tick,
                                    # interleaved with decode (0 = monolithic
                                    # prefill at admission)
    greedy: bool = True
    sample_seed: int = 0            # rng seed for greedy=False sampling
    shadow_check: bool = False      # record the page-lifecycle trace and
                                    # replay it through the sanitizer
                                    # (repro.analysis) EVERY tick, raising
                                    # LifecycleViolationError at the tick
                                    # that broke the contract. Test-only:
                                    # off (default) => no trace, no checker,
                                    # zero hot-path overhead


@dataclasses.dataclass
class EngineMetrics:
    ticks: int = 0
    tokens_emitted: int = 0
    prefills: int = 0
    prefill_skips: int = 0      # admissions served entirely from shared pages
    chunk_passes: int = 0       # chunked-prefill passes (subset of prefills)
    decode_steps: int = 0
    preemptions: int = 0        # slots swapped out for a more urgent arrival
    readmissions: int = 0       # preempted requests resumed mid-stream
    slo_violations: int = 0     # first tokens emitted after their deadline
    wall_time: float = 0.0

    @property
    def tokens_per_sec(self) -> float:
        """Throughput; 0.0 (not a ZeroDivisionError) when no wall time has
        accumulated — snapshots are taken before the first step too."""
        if self.wall_time <= 0.0:
            return 0.0
        return self.tokens_emitted / self.wall_time


class PagedServingEngine:
    """Continuous batching over a paged, PUL-tiered KV cache."""

    def __init__(self, cfg: ModelConfig, params,
                 engine_cfg: PagedEngineConfig = PagedEngineConfig(),
                 metrics_hook: Optional[Callable[[Dict[str, Any]], None]] = None,
                 tracer: Optional[Tracer] = None):
        from repro.serving.config import ServingConfig
        if isinstance(engine_cfg, ServingConfig):
            engine_cfg = engine_cfg.paged()
        self.base_cfg = cfg
        self.model_cfg = dataclasses.replace(cfg, paged_kv=True)
        self.cfg = engine_cfg
        self.metrics_hook = metrics_hook
        # one tracer threaded through the whole stack (engine spans,
        # scheduler decisions, page lifecycle, DMA twin); NULL_TRACER (the
        # default) makes every emission site a no-op
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.model = zoo.build_model(self.model_cfg)
        self.params = params

        B, S, P = engine_cfg.batch_slots, engine_cfg.max_seq, engine_cfg.page_tokens
        if S % P:
            raise ValueError(f"max_seq ({S}) must be a multiple of "
                             f"page_tokens ({P})")
        if max(engine_cfg.prefill_buckets) > S:
            raise ValueError("prefill bucket exceeds max_seq")
        if engine_cfg.prefill_chunk_tokens and (
                engine_cfg.prefill_chunk_tokens % P):
            raise ValueError(
                f"prefill_chunk_tokens ({engine_cfg.prefill_chunk_tokens}) "
                f"must be a multiple of page_tokens ({P}) so chunk "
                "boundaries are page-aligned")
        self.n_pages_per_slot = S // P

        self.layout = PackedKVLayout(self.model_cfg, B, S)
        hot = engine_cfg.hot_pages or (B * self.n_pages_per_slot + 2)
        gqa = cfg.num_heads // max(cfg.num_kv_heads, 1)
        pcfg = PageConfig(page_tokens=P, hot_frames=hot + 2,
                          preload_distance=engine_cfg.preload_distance,
                          share_prefix_pages=engine_cfg.share_prefix_pages,
                          trace=engine_cfg.shadow_check)
        if self.layout.features:
            # v2 hot tier: per-layer planes — the arrays the sweep kernel
            # walks ARE the store, so page views under jit are zero-copy
            self.pool = KVPagePool(pcfg, layout=self.layout, gqa_group=gqa,
                                   tracer=self.tracer)
        else:
            # no pageable KV (pure-SSM archs): a vestigial packed pool keeps
            # the allocator/trace machinery alive with 1 feature column
            self.pool = KVPagePool(pcfg, 1, gqa_group=gqa,
                                   tracer=self.tracer)
        # shadow mode: an incremental lifecycle checker consumes the pool
        # trace every tick (O(new events) per tick), so a violation names
        # the offending event at the tick it happened
        self._shadow_checker = None
        if engine_cfg.shadow_check:
            from repro.analysis.sanitizer import LifecycleChecker
            self._shadow_checker = LifecycleChecker()
        self.scheduler = AdmissionScheduler(SchedulerConfig(
            prefill_buckets=engine_cfg.prefill_buckets,
            max_active_tokens=engine_cfg.max_active_tokens or B * S,
            page_tokens=P, policy=engine_cfg.policy, max_seq=S),
            tracer=self.tracer)

        # compiled entry points: one prefill per bucket, one decode; the
        # kernel-true path binds the planner's d* as the in-kernel preload
        # distance (static arg, so it is part of the compiled artifact)
        self._prefill_fns: Dict[int, Callable] = {}
        self._decode = jax.jit(self.model.decode_step)
        d = max(1, min(self.pool.distance, self.pool.cfg.fifo_depth))
        self._paged_decode = jax.jit(functools.partial(
            self.model.paged_decode_step, pul_distance=d))
        # single-sweep decode: planes ride as a donated argument so the
        # fused in-kernel commit updates them in place (no copy of the
        # store per step); returns (logits, new_tree, planes)
        self._sweep_decode = jax.jit(functools.partial(
            self.model.paged_decode_step, pul_distance=d),
            donate_argnums=(3,))

        # slot state
        self.slot_req: List[Optional[Request]] = [None] * B
        self.slot_len = np.zeros((B,), np.int32)    # tokens cached per slot
        self.slot_pages: List[List[int]] = [[] for _ in range(B)]
        self.paused: List[bool] = [False] * B
        spec, _ = self.model.cache_specs(B, S)
        self.resident = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), spec)
        self.metrics = EngineMetrics()
        self.requests: Dict[int, Request] = {}
        self._rng = np.random.default_rng(engine_cfg.sample_seed)
        self._paused_state: Dict[int, Dict[Tuple[str, ...], Any]] = {}
        # policy-preempted (swapped-out) requests: rid -> saved slot state
        # (page ids — cold until readmission —, fill level, non-pageable
        # rows, chunked-prefill progress); the request itself is requeued
        self._swapped: Dict[int, Dict[str, Any]] = {}
        # in-flight chunked prefills: slot -> {"prompt", "filled"}
        self._chunk: Dict[int, Dict[str, Any]] = {}
        self._tick = 0
        # prefill-compute reuse: first-token logits per fully page-aligned
        # shared prompt, keyed (bucket, prompt tuple); bounded LRU. Only
        # sound when no non-pageable recurrent state exists (pages rebuild
        # attention KV exactly; SSM/conv state cannot be rebuilt from pages).
        pageable = {e.keys for e in self.layout.entries}
        self._has_recurrent = any(
            _path_keys(path) not in pageable and _path_keys(path)[-1] != "idx"
            for path, _ in jax.tree_util.tree_flatten_with_path(spec)[0])
        self._prompt_logits: "OrderedDict[tuple, np.ndarray]" = OrderedDict()

    # ------------------------------------------------------------------ #
    def _prefill_for(self, bucket: int) -> Callable:
        if bucket not in self._prefill_fns:
            model = self.model
            self._prefill_fns[bucket] = jax.jit(
                lambda p, b, _bucket=bucket: model.prefill(
                    p, b, max_seq=_bucket))
        return self._prefill_fns[bucket]

    def submit(self, req: Request):
        """Reject-at-submit anything that can NEVER be served: a queue slot
        for an impossible request is a permanent head-of-line wedge."""
        cost = self.scheduler.request_cost(req)
        if cost > self.cfg.max_seq:
            raise ValueError(
                f"request {req.rid}: prompt ({len(req.prompt)}) + "
                f"max_new_tokens ({req.max_new_tokens}) = {cost} exceeds "
                f"max_seq ({self.cfg.max_seq}); it can never fit a slot")
        if self.scheduler.request_pages(req) > self.pool.capacity:
            raise ValueError(
                f"request {req.rid} needs {self.scheduler.request_pages(req)}"
                f" pages; hot tier holds {self.pool.capacity}")
        if cost > self.scheduler.cfg.max_active_tokens:
            raise ValueError(f"request {req.rid} exceeds the token budget")
        self.requests[req.rid] = req
        self.scheduler.submit(req, self._tick)
        if self.tracer.enabled:
            # request lifecycle span: submit -> last token (or rejection);
            # async because it crosses many engine scopes
            self.tracer.async_begin(
                "requests", f"req{req.rid}", req.rid, cat="request",
                prompt_tokens=len(req.prompt),
                max_new_tokens=req.max_new_tokens,
                priority=req.priority, ttft_deadline=req.ttft_deadline)

    # ------------------------------------------------------------------ #
    def _live_slots(self) -> List[int]:
        """Slots that decode this tick: occupied, not paused, and not still
        mid-chunked-prefill (a chunking slot has no first token yet)."""
        return [i for i, r in enumerate(self.slot_req)
                if r is not None and not self.paused[i]
                and i not in self._chunk]

    def _active_tokens(self) -> int:
        """Budget charge of the live batch — the SAME cost function the
        scheduler uses at admission (`AdmissionScheduler.request_cost`), so
        per-tick accounting can never drift from submit-time checks."""
        return sum(self.scheduler.request_cost(r)
                   for r in self.slot_req if r is not None)

    def _live_page_count(self) -> int:
        return sum(len(self.slot_pages[i])
                   for i, r in enumerate(self.slot_req) if r is not None)

    # ------------------------------------------------------------------ #
    # admission + per-slot prefill
    # ------------------------------------------------------------------ #
    def _run_admission(self) -> List[Admission]:
        free = [i for i, r in enumerate(self.slot_req) if r is None]
        return self.scheduler.admit(
            free,
            active_tokens=self._active_tokens(),
            free_hot_frames=self.pool.capacity - self._live_page_count(),
            now=self._tick,
            total_hot_frames=self.pool.capacity)

    def _admit(self):
        self._place(self._run_admission())
        if self.scheduler.cfg.policy == "fcfs":
            return
        # preemptive policies: while the policy-ordered head is still queued
        # and a running victim should yield, swap the victim out (pages to
        # the cold tier, request requeued) and retry admission. Bounded by
        # the slot count — at most one preemption per occupied slot per tick.
        for _ in range(len(self.slot_req)):
            cand = self.scheduler.head()
            if cand is None:
                return
            victim = self._preemption_victim(cand)
            if victim is None:
                return
            if self.tracer.enabled:
                policy = self.scheduler.cfg.policy
                self.tracer.decision(
                    "preempt", rid=self.slot_req[victim].rid, slot=victim,
                    for_rid=cand.rid, policy=policy,
                    reason=("deadline-lookahead" if policy == "slo-edf"
                            else "priority"))
            self._preempt_to_queue(victim)
            self._place(self._run_admission())

    def _place(self, admissions: List[Admission]):
        """Route admissions: swapped-out requests resume from saved pages,
        long prompts start chunked prefill, fully-shared prompts skip
        compute, the rest batch into per-bucket prefill groups."""
        if self.tracer.enabled:
            for a in admissions:
                # slot-occupancy span: one per admission episode, keyed by
                # the occupying request (a preempted request re-opens one)
                self.tracer.async_begin(
                    "slots", f"slot{a.slot}", a.request.rid, cat="slot",
                    slot=a.slot, rid=a.request.rid)
        by_bucket: Dict[int, List[Admission]] = {}
        for a in admissions:
            if a.request.rid in self._swapped:
                self._resume_swapped(a)
                continue
            if self._try_shared_prefill(a):
                continue                     # served without prefill compute
            chunk = self.cfg.prefill_chunk_tokens
            if chunk and len(a.request.prompt) > chunk:
                self._start_chunk(a)
                continue
            by_bucket.setdefault(a.bucket, []).append(a)
        for bucket, group in sorted(by_bucket.items()):
            self._prefill_group(bucket, group)

    # ------------------------------------------------------------------ #
    # policy-driven preemption (swap-out to the cold tier + requeue)
    # ------------------------------------------------------------------ #
    def _occupied_slots(self) -> List[int]:
        """Preemption-victim candidates: occupied, not manually paused (a
        paused slot's pages are already cold and its slot is a user
        decision, not the scheduler's to reassign)."""
        return [i for i, r in enumerate(self.slot_req)
                if r is not None and not self.paused[i]]

    def _remaining_ticks(self, slot: int) -> int:
        """Estimated ticks until `slot` frees naturally: one token per tick
        plus, mid-chunked-prefill, the remaining chunk passes."""
        r = self.slot_req[slot]
        rem = r.max_new_tokens - len(r.out_tokens)
        st = self._chunk.get(slot)
        if st is not None:
            chunk = self.cfg.prefill_chunk_tokens
            left = len(st["prompt"]) - st["filled"]
            rem += -(-left // chunk)
        return max(rem, 0)

    def _preemption_victim(self, cand: Request) -> Optional[int]:
        """Pick the slot to swap out for queued request `cand`, or None.

        priority: any running request with strictly lower priority may
        yield — lowest priority first, latest-admitted within a tie (least
        sunk work). slo-edf: preempt ONLY when cand's TTFT deadline would
        otherwise be missed (no slot frees up in time on its own); the
        victim is the running request with the LATEST pending deadline
        (none at all preferred) — never one more urgent than cand.
        """
        occupied = self._occupied_slots()
        if not occupied:
            return None
        policy = self.scheduler.cfg.policy
        if policy == "priority":
            victims = [i for i in occupied
                       if self.slot_req[i].priority < cand.priority]
            if not victims:
                return None
            return min(victims, key=lambda i: (self.slot_req[i].priority,
                                               -self.slot_req[i].admit_tick))
        if policy == "slo-edf":
            deadline = cand.deadline_tick()
            if deadline == float("inf"):
                return None                  # no deadline, no urgency
            if self._tick + min(self._remaining_ticks(i)
                                for i in occupied) <= deadline:
                return None                  # a slot frees up in time
            victims = [i for i in occupied
                       if self.slot_req[i].deadline_tick() > deadline]
            if not victims:
                return None
            return max(victims,
                       key=lambda i: (self.slot_req[i].deadline_tick(),
                                      self.slot_req[i].admit_tick))
        return None

    def _preempt_to_queue(self, slot: int):
        """Swap a running request out of its slot: private pages spill to
        the cold tier (shared prefix pages stay hot for their other
        readers), non-pageable (recurrent) rows and chunked-prefill
        progress are snapshotted, and the request requeues for readmission
        — where it resumes mid-stream, token-for-token."""
        req = self.slot_req[slot]
        state = {
            "pages": self.slot_pages[slot],
            "slot_len": int(self.slot_len[slot]),
            "nonpageable": self._nonpageable_rows(slot),
            "chunk": self._chunk.pop(slot, None),
        }
        self.pool.evict_pages([pid for pid in state["pages"]
                               if self.pool.pages[pid].refcount == 1])
        self._swapped[req.rid] = state
        self.slot_req[slot] = None
        self.slot_pages[slot] = []
        self.slot_len[slot] = 0
        self.paused[slot] = False
        self.metrics.preemptions += 1
        self.scheduler.requeue(req, now=self._tick)
        if self.tracer.enabled:
            self.tracer.async_end("slots", f"slot{slot}", req.rid,
                                  cat="slot", preempted=True)

    def _resume_swapped(self, a: Admission):
        """Readmit a swapped-out request: saved pages re-attach to the new
        slot (still cold — the next decode step's planned preload restores
        them, counted as page faults), non-pageable rows are written back,
        and an interrupted chunked prefill picks up where it left off."""
        state = self._swapped.pop(a.request.rid)
        req = a.request
        req.resuming = False
        slot = a.slot
        self.slot_req[slot] = req
        self.slot_pages[slot] = state["pages"]
        self.slot_len[slot] = state["slot_len"]
        self.paused[slot] = False
        if state["nonpageable"]:
            self._write_nonpageable_rows(slot, state["nonpageable"])
        if state["chunk"] is not None:
            self._chunk[slot] = state["chunk"]
        self.pool.note_deadline(state["pages"], req.deadline_tick())
        self.metrics.readmissions += 1
        if self.tracer.enabled:
            self.tracer.decision("resume", rid=req.rid, slot=slot,
                                 pages=len(state["pages"]))

    def _try_shared_prefill(self, a: Admission) -> bool:
        """Admit a request whose WHOLE prompt is already resident as shared
        pages without running prefill compute (ROADMAP prefix-cache compute
        reuse): every full page of the (bucketed) prompt hits the prefix
        index and the first-token logits were cached by the prefill that
        built those pages. Only page-aligned prompts qualify (a partial tail
        page is private and would still need compute), and only when the
        model carries no recurrent state (which pages cannot rebuild)."""
        P = self.cfg.page_tokens
        prompt = a.request.prompt[-a.bucket:]
        n = len(prompt)
        if (not self.cfg.share_prefix_pages or not self.layout.features
                or self._has_recurrent or n == 0 or n % P):
            return False
        key = (a.bucket, tuple(prompt))
        logits = self._prompt_logits.get(key)
        if logits is None:
            return False
        page_keys = [(a.bucket, tuple(prompt[:(j + 1) * P]))
                     for j in range(n // P)]
        if any(k not in self.pool.prefix_index for k in page_keys):
            return False
        pids = [self.pool.lookup_shared(k) for k in page_keys]
        self.pool.note_deadline(pids, a.request.deadline_tick())
        self.slot_req[a.slot] = a.request
        self.slot_pages[a.slot] = pids
        self.slot_len[a.slot] = n
        self.paused[a.slot] = False
        self.metrics.prefill_skips += 1
        self._prompt_logits.move_to_end(key)
        self._emit_token(a.slot, logits)
        return True

    def _write_prompt_pages(self, slot: int, key_bucket: int,
                            prompt: List[int], lo: int, hi: int,
                            packed, working: set):
        """Allocate (or prefix-share) and fill the pages covering prompt
        tokens [lo, hi) of `slot`, appending to its page table. `packed` is
        this slot's (S >= hi, F) packed KV rows; `lo` must be page-aligned.
        FULL pages are shareable under (key_bucket, prompt-prefix) keys —
        identical whether written monolithically or chunk-by-chunk."""
        P = self.cfg.page_tokens
        req = self.slot_req[slot]
        pids = self.slot_pages[slot]
        assert lo % P == 0 and lo // P == len(pids)
        for j in range(lo // P, -(-hi // P)):
            plo, phi = j * P, min((j + 1) * P, hi)
            if phi == (j + 1) * P:          # full page: shareable
                key = (key_bucket, tuple(prompt[:phi]))
                pid = self.pool.lookup_shared(key)
                if pid is None:
                    pid = self.pool.alloc(shared_key=key
                                          if self.cfg.share_prefix_pages
                                          else None,
                                          needed=working)
                    self.pool.write_page(pid, packed[plo:phi], phi - plo)
            else:                            # partial tail page: private
                pid = self.pool.alloc(needed=working)
                self.pool.write_page(pid, packed[plo:phi], phi - plo)
            pids.append(pid)
            working.add(pid)
        self.pool.note_deadline(pids, req.deadline_tick())

    def _prefill_group(self, bucket: int, group: List[Admission]):
        with self.tracer.span("engine", f"prefill@{bucket}"):
            self._prefill_group_inner(bucket, group)

    def _prefill_group_inner(self, bucket: int, group: List[Admission]):
        B, P = self.cfg.batch_slots, self.cfg.page_tokens
        toks = np.zeros((B, bucket), np.int32)
        lengths = np.ones((B,), np.int32)
        prompts: Dict[int, List[int]] = {}
        for a in group:
            prompt = a.request.prompt[-bucket:]      # right-pad, keep tail
            toks[a.slot, :len(prompt)] = prompt
            lengths[a.slot] = len(prompt)
            prompts[a.slot] = prompt
            self.slot_req[a.slot] = a.request
        batch = {"tokens": jnp.asarray(toks), "lengths": jnp.asarray(lengths)}
        logits, caches = self._prefill_for(bucket)(self.params, batch)
        self.metrics.prefills += 1
        packed = (self.layout.pack(caches)
                  if self.layout.features else None)   # (B, bucket, F)

        # pages every live slot (and this admission group so far) still
        # needs: allocations must not evict them mid-step
        working = {pid for pages in self.slot_pages for pid in pages}
        for a in group:
            slot, prompt = a.slot, prompts[a.slot]
            n = len(prompt)
            self.slot_pages[slot] = []
            if self.layout.features:
                self._write_prompt_pages(slot, bucket, prompt, 0, n,
                                         packed[slot], working)
            self.slot_len[slot] = n
            self.paused[slot] = False
            self._merge_resident(caches, slot)
            if (self.cfg.share_prefix_pages and self.layout.features
                    and not self._has_recurrent and n and n % P == 0):
                # whole prompt landed in shared pages: cache the first-token
                # logits so an identical prompt can skip prefill entirely
                self._prompt_logits[(bucket, tuple(prompt))] = \
                    np.asarray(logits[slot])
                if len(self._prompt_logits) > 512:
                    self._prompt_logits.popitem(last=False)
            self._emit_token(slot, np.asarray(logits[slot]))

    # ------------------------------------------------------------------ #
    # chunked prefill: one bounded pass per tick, interleaved with decode
    # ------------------------------------------------------------------ #
    def _start_chunk(self, a: Admission):
        """Claim the slot for a long prompt without running any prefill
        yet; `_advance_chunks` fills it one page-aligned chunk per tick.
        The slot stays out of the decode batch until the prompt completes."""
        self.slot_req[a.slot] = a.request
        self.slot_pages[a.slot] = []
        self.slot_len[a.slot] = 0
        self.paused[a.slot] = False
        self._chunk[a.slot] = {"prompt": a.request.prompt[-a.bucket:],
                               "filled": 0}

    def _advance_chunks(self):
        for slot in sorted(self._chunk):
            with self.tracer.span("engine", f"chunk-pass@{slot}"):
                self._chunk_pass(slot)

    def _chunk_pass(self, slot: int):
        """One chunked-prefill pass: extend the slot's prefix by (up to)
        `prefill_chunk_tokens` tokens and bank the new pages. The pass runs
        the compiled prefill of the SMALLEST bucket holding the prefix so
        far — per-tick prefill span is bounded by the prefix, and causal
        attention makes the rows bitwise identical to a monolithic prefill
        (row t depends only on tokens <= t; padding rows are masked to
        exact zeros). The final pass — the same shape the dense reference
        uses — merges non-pageable (recurrent) state and emits the first
        token, so chunking is invisible in the token stream."""
        st = self._chunk[slot]
        req = self.slot_req[slot]
        prompt, f = st["prompt"], st["filled"]
        n = len(prompt)
        hi = min(f + self.cfg.prefill_chunk_tokens, n)
        bucket = self.scheduler.pick_bucket(hi)
        B, P = self.cfg.batch_slots, self.cfg.page_tokens
        toks = np.zeros((B, bucket), np.int32)
        toks[slot, :hi] = prompt[:hi]
        lengths = np.ones((B,), np.int32)
        lengths[slot] = hi
        logits, caches = self._prefill_for(bucket)(
            self.params, {"tokens": jnp.asarray(toks),
                          "lengths": jnp.asarray(lengths)})
        self.metrics.prefills += 1
        self.metrics.chunk_passes += 1
        working = {pid for pages in self.slot_pages for pid in pages}
        if self.layout.features:
            packed = self.layout.pack(caches)
            self._write_prompt_pages(slot, req.bucket, prompt, f, hi,
                                     packed[slot], working)
        st["filled"] = hi
        self.slot_len[slot] = hi
        if hi < n:
            return                          # more chunks to go; decode runs on
        del self._chunk[slot]               # prompt complete: slot goes live
        self._merge_resident(caches, slot)
        if (self.cfg.share_prefix_pages and self.layout.features
                and not self._has_recurrent and n and n % P == 0):
            self._prompt_logits[(req.bucket, tuple(prompt))] = \
                np.asarray(logits[slot])
            if len(self._prompt_logits) > 512:
                self._prompt_logits.popitem(last=False)
        self._emit_token(slot, np.asarray(logits[slot]))

    def _merge_resident(self, fresh, slot: int):
        """Copy one slot's NON-pageable cache rows (SSM states, idx) from a
        freshly prefilled tree into the carried resident tree."""
        pageable = {e.keys for e in self.layout.entries}
        flat, treedef = jax.tree_util.tree_flatten_with_path(self.resident)
        flat_fresh = dict(jax.tree_util.tree_flatten_with_path(fresh)[0])
        out = []
        for path, leaf in flat:
            keys = _path_keys(path)
            if keys in pageable:
                out.append(leaf)
                continue
            src = flat_fresh[path]
            ax = 1 if keys[0] == "groups" else 0
            idx = (slice(None),) * ax + (slot,)
            out.append(leaf.at[idx].set(src[idx].astype(leaf.dtype)))
        self.resident = jax.tree_util.tree_unflatten(treedef, out)

    # ------------------------------------------------------------------ #
    # decode
    # ------------------------------------------------------------------ #
    def _assemble(self) -> Any:
        """Build the decode cache tree: pages -> dense token-indexed view."""
        if not self.layout.features:
            return _set_idx(self.resident, self.slot_len)
        B, P = self.cfg.batch_slots, self.cfg.page_tokens
        frames = np.full((B, self.n_pages_per_slot), ZERO_FRAME, np.int32)
        for i in self._live_slots():
            pids = self.slot_pages[i]
            frames[i, :len(pids)] = self.pool.frames_of(pids)
        store = self.pool.packed_store()
        if self.cfg.use_pallas_gather:
            from repro.kernels import pul_page_gather
            from repro.core import PULConfig
            d = min(self.pool.distance, self.pool.cfg.fifo_depth)
            packed = pul_page_gather(
                store, jnp.asarray(frames),
                cfg=PULConfig(distance=max(1, d)))
        else:
            packed = store[jnp.asarray(frames)].reshape(
                B, self.cfg.max_seq, -1)
        tree = self.layout.unpack_into(self.resident, packed)
        return _set_idx(tree, self.slot_len)

    def _ensure_tail_pages(self):
        """Every live slot needs a writable page for the incoming token.
        The step's whole working set is threaded into alloc so a tail-page
        allocation can never evict a page this very step still reads (which
        ensure_hot would immediately restore — churn, not capacity)."""
        P = self.cfg.page_tokens
        live = self._live_slots()
        working = {pid for i in live for pid in self.slot_pages[i]}
        for i in live:
            pos = int(self.slot_len[i])
            if pos // P == len(self.slot_pages[i]):
                pid = self.pool.alloc(needed=working)
                self.pool.note_deadline([pid],
                                        self.slot_req[i].deadline_tick())
                self.slot_pages[i].append(pid)
                working.add(pid)

    def _sweep_cache_tree(self):
        """Decode cache tree for the single-sweep path: pageable leaves are
        tiny placeholders — the sweep branch reads only the tree POSITION
        (the KV data rides in the donated planes), so no page view is ever
        materialized into the tree. Grouped placeholders keep a leading
        layer axis so the backbone scan can slice them; non-pageable leaves
        (SSM state, idx) come from `resident` as usual."""
        pageable = {e.keys: e for e in self.layout.entries}

        def repl(path, leaf):
            e = pageable.get(_path_keys(path))
            if e is None:
                return leaf
            if e.grouped:
                return jnp.zeros((e.shape[0], 1), leaf.dtype)
            return jnp.zeros((1,), leaf.dtype)

        return jax.tree_util.tree_map_with_path(repl, self.resident)

    def _paged_kernel_decode(self, live, toks, pos0, frames, offs):
        """Kernel-true decode: attention streams straight over page frames;
        no dense per-slot KV view is assembled.

        ``sweep_decode=True`` (default) runs ONE sweep: the layer scan
        carries the full per-layer planes (``layer_view`` is zero-copy —
        the plane IS the stored array), the kernel picks its layer via an
        SMEM scalar, and its fused epilogue commits the current token's
        rows into each slot's tail page inside the same launch. The planes
        are donated to the jit call, so the hot tier updates in place.
        ``sweep_decode=False`` keeps per-layer launches over per-layer
        views, with the caller doing the eager write_rows scatter (the
        parity baseline).

        Returns (logits, new_tree); new_tree's pageable leaves hold only
        the current token's rows."""
        B = self.cfg.batch_slots
        page_table = np.full((B, self.n_pages_per_slot), ZERO_FRAME, np.int32)
        for i in live:
            pids = self.slot_pages[i]
            page_table[i, :len(pids)] = self.pool.frames_of(pids)
        if self.cfg.sweep_decode:
            tree = _set_idx(self._sweep_cache_tree(), self.slot_len)
            # account + lifecycle-trace the fused commit BEFORE the launch
            # (events must precede the write they describe)
            self.pool.note_fused_commit(frames, offs)
            logits, new_tree, planes = self._sweep_decode(
                self.params, {"tokens": jnp.asarray(toks),
                              "pos0": jnp.asarray(pos0),
                              "page_table": jnp.asarray(page_table),
                              "frames": jnp.asarray(frames),
                              "offsets": jnp.asarray(offs)},
                tree, self.pool.planes)
            self.pool.planes = planes
            return logits, new_tree
        tree = self.layout.page_view_tree(self.resident, self.pool.planes)
        tree = _set_idx(tree, self.slot_len)
        return self._paged_decode(
            self.params, {"tokens": jnp.asarray(toks),
                          "pos0": jnp.asarray(pos0),
                          "page_table": jnp.asarray(page_table)}, tree)

    def _merge_nonpageable(self, new_tree):
        """Fold a paged-decode step's NON-pageable outputs (SSM state, idx)
        into the resident tree; pageable leaves (page views in, new-token
        rows out) never live in `resident`."""
        pageable = {e.keys for e in self.layout.entries}
        flat, treedef = jax.tree_util.tree_flatten_with_path(self.resident)
        flat_new = dict(jax.tree_util.tree_flatten_with_path(new_tree)[0])
        out = []
        for path, leaf in flat:
            keys = _path_keys(path)
            out.append(leaf if keys in pageable else flat_new[path])
        self.resident = jax.tree_util.tree_unflatten(treedef, out)

    def _decode_step(self):
        live = self._live_slots()
        if not live:
            return
        B = self.cfg.batch_slots
        self._ensure_tail_pages()
        needed = sorted({pid for i in live for pid in self.slot_pages[i]})
        faults = self.pool.ensure_hot(needed)

        toks = np.zeros((B, 1), np.int32)
        pos0 = np.zeros((B,), np.int32)
        for i in live:
            toks[i, 0] = self.slot_req[i].out_tokens[-1]
            pos0[i] = self.slot_len[i]
        # tail-page commit coordinates for every slot this step (TRASH sink
        # for slots not decoding); the fused sweep needs them BEFORE launch
        P = self.cfg.page_tokens
        frames = np.full((B,), TRASH_FRAME, np.int32)
        offs = np.zeros((B,), np.int32)
        if self.layout.features:
            for i in live:
                pos = int(self.slot_len[i])
                pid = self.slot_pages[i][pos // P]
                frames[i] = self.pool.pages[pid].frame
                offs[i] = pos % P
        kernel_true = self.cfg.use_paged_kernel and self.layout.features
        sweep = kernel_true and self.cfg.sweep_decode
        if kernel_true:
            logits, new_tree = self._paged_kernel_decode(
                live, toks, pos0, frames, offs)
        else:
            tree = self._assemble()
            logits, new_tree = self._decode(
                self.params, {"tokens": jnp.asarray(toks),
                              "pos0": jnp.asarray(pos0)}, tree)
        self.metrics.decode_steps += 1

        # write the step's new KV rows back into each live slot's tail page
        # (the sweep already committed them in its fused epilogue)
        if self.layout.features and not sweep:
            rows = (self.layout._pack_new_rows_impl(new_tree) if kernel_true
                    else self.layout.pack_rows(new_tree,
                                               jnp.asarray(self.slot_len)))
            self.pool.write_rows(frames, offs, rows)
        if kernel_true:
            self._merge_nonpageable(new_tree)
        else:
            self.resident = new_tree

        logits = np.asarray(logits)
        for i in live:
            self.slot_len[i] += 1
            self._emit_token(i, logits[i])
        return faults

    def _emit_token(self, slot: int, logits: np.ndarray):
        r = self.slot_req[slot]
        if self.cfg.greedy:
            nxt = int(np.argmax(logits))
        else:
            z = logits.astype(np.float64) - logits.max()
            p = np.exp(z)
            nxt = int(self._rng.choice(p.shape[-1], p=p / p.sum()))
        r.out_tokens.append(nxt)
        self.metrics.tokens_emitted += 1
        if r.first_token_tick < 0:
            r.first_token_tick = self._tick
            if r.ttft_deadline >= 0 and r.ttft > r.ttft_deadline:
                self.metrics.slo_violations += 1
        out_of_room = int(self.slot_len[slot]) + 1 >= self.cfg.max_seq
        if len(r.out_tokens) >= r.max_new_tokens or out_of_room:
            self._finish(slot)

    def _finish(self, slot: int):
        req = self.slot_req[slot]
        req.done = True
        if self.tracer.enabled:
            self.tracer.async_end("requests", f"req{req.rid}", req.rid,
                                  cat="request", tokens=len(req.out_tokens))
            self.tracer.async_end("slots", f"slot{slot}", req.rid,
                                  cat="slot")
        for pid in self.slot_pages[slot]:
            self.pool.unref(pid)
        self.slot_pages[slot] = []
        self.slot_req[slot] = None
        self.slot_len[slot] = 0
        self.paused[slot] = False
        self._paused_state.pop(slot, None)

    # ------------------------------------------------------------------ #
    # preemption (vLLM-style swap-out: pages spill to the cold tier)
    # ------------------------------------------------------------------ #
    def _nonpageable_rows(self, slot: int) -> Dict[Tuple[str, ...], Any]:
        """Snapshot one slot's rows of every NON-pageable cache leaf (SSM /
        recurrent state). Attention KV needs no snapshot — it is rebuilt
        from pages — but recurrent state advances in `resident` every decode
        step, including for paused slots fed dummy tokens."""
        pageable = {e.keys for e in self.layout.entries}
        out = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(self.resident)[0]:
            keys = _path_keys(path)
            if keys in pageable or keys[-1] == "idx":
                continue
            ax = 1 if keys[0] == "groups" else 0
            out[keys] = leaf[(slice(None),) * ax + (slot,)]
        return out

    def _write_nonpageable_rows(self, slot: int,
                                saved: Dict[Tuple[str, ...], Any]):
        flat, treedef = jax.tree_util.tree_flatten_with_path(self.resident)
        out = []
        for path, leaf in flat:
            keys = _path_keys(path)
            if keys in saved:
                ax = 1 if keys[0] == "groups" else 0
                idx = (slice(None),) * ax + (slot,)
                leaf = leaf.at[idx].set(saved[keys])
            out.append(leaf)
        self.resident = jax.tree_util.tree_unflatten(treedef, out)

    def preempt(self, slot: int):
        """Pause a slot and evict its private pages to the cold tier.
        Shared prefix pages stay hot while other requests reference them.
        Recurrent (non-pageable) state is snapshotted: paused slots still
        ride through the batched decode step with dummy inputs, which would
        otherwise advance their SSM/conv state."""
        assert self.slot_req[slot] is not None
        if self.tracer.enabled:
            self.tracer.instant("engine", "pause", slot=slot,
                                rid=self.slot_req[slot].rid)
        self.paused[slot] = True
        self._paused_state[slot] = self._nonpageable_rows(slot)
        self.pool.evict_pages(
            [pid for pid in self.slot_pages[slot]
             if self.pool.pages[pid].refcount == 1])

    def resume(self, slot: int):
        """Un-pause; the next decode step's ensure_hot restores the pages
        through the planned preload path (counted as page faults), and the
        snapshotted recurrent state is written back."""
        assert self.slot_req[slot] is not None
        if self.tracer.enabled:
            self.tracer.instant("engine", "unpause", slot=slot,
                                rid=self.slot_req[slot].rid)
        self.paused[slot] = False
        saved = self._paused_state.pop(slot, None)
        if saved:
            self._write_nonpageable_rows(slot, saved)

    # ------------------------------------------------------------------ #
    def step(self):
        t0 = time.perf_counter()
        tr = self.tracer
        tr.set_tick(self._tick)
        with tr.span("engine", "tick"):
            with tr.span("engine", "admit"):
                self._admit()
            self._advance_chunks()
            with tr.span("engine", "decode"):
                faults = self._decode_step() or 0
        self._tick += 1
        self.metrics.ticks = self._tick
        self.metrics.wall_time += time.perf_counter() - t0
        if tr.enabled:
            tr.counter("gauges", "live_slots", len(self._live_slots()))
            tr.counter("gauges", "queued", len(self.scheduler))
            tr.counter("gauges", "hot_pages_in_use", self.pool.hot_in_use())
            tr.counter("gauges", "page_faults_step", faults)
        if self._shadow_checker is not None:
            self._run_shadow_check()
        if self.metrics_hook:
            # snapshot() runs OUTSIDE the guard: a PoolMetrics invariant
            # violation must still crash loudly. Only the user-supplied
            # observer is sandboxed — a broken hook must not take the tick
            # loop down with it, so it is disabled after its first raise.
            snap = self.snapshot(page_faults_step=faults)
            try:
                self.metrics_hook(snap)
            except Exception as e:
                warnings.warn(
                    f"metrics_hook raised {e!r}; disabling the hook for the "
                    "rest of this engine's life", RuntimeWarning,
                    stacklevel=2)
                self.metrics_hook = None

    def _run_shadow_check(self):
        """Feed the tick's new trace events through the lifecycle checker;
        raise at the first violation (with event provenance)."""
        from repro.analysis.sanitizer import LifecycleViolationError
        fresh = self._shadow_checker.feed_log(self.pool.trace)
        if fresh:
            raise LifecycleViolationError(fresh)

    def snapshot(self, **extra) -> Dict[str, Any]:
        pm = self.pool.metrics
        pm.validate()   # counter-arithmetic invariants (PoolMetrics docs)
        lat = self.scheduler.queue_latencies()
        snap = {
            "tick": self._tick,
            "policy": self.scheduler.cfg.policy,
            "tokens_emitted": self.metrics.tokens_emitted,
            "tokens_per_sec": self.metrics.tokens_per_sec,
            "prefills": self.metrics.prefills,
            "prefill_skips": self.metrics.prefill_skips,
            "chunk_passes": self.metrics.chunk_passes,
            "preemptions": self.metrics.preemptions,
            "readmissions": self.metrics.readmissions,
            "slo_violations": self.metrics.slo_violations,
            "rejected": self.scheduler.rejected,
            "swapped_out": len(self._swapped),
            "live_slots": len(self._live_slots()),
            "queued": len(self.scheduler),
            "page_faults": pm.page_faults,
            "evictions": pm.evictions,
            "shared_page_hits": pm.shared_hits,
            "pages_allocated": pm.pages_allocated,
            "hot_pages_in_use": self.pool.hot_in_use(),
            "preload_distance": self.pool.distance,
            "modeled_restore_latency_hidden": pm.modeled_latency_hidden,
            "mean_queue_latency": mean(lat),
        }
        snap.update(extra)
        return snap

    def economics(self) -> Dict[str, Any]:
        """Cache economics of the run so far: bytes moved per token emitted
        per tier, and prefetch accuracy / timeliness / coverage of the
        planned d* restores (see ``repro.obs.metrics.cache_economics``)."""
        return cache_economics(page_bytes=self.pool.page_bytes,
                               tokens_emitted=self.metrics.tokens_emitted,
                               pool_metrics=self.pool.metrics)

    def metrics_registry(self) -> MetricsRegistry:
        """Current counters as a flat registry (JSON / Prometheus export)."""
        reg = MetricsRegistry()
        policy = self.scheduler.cfg.policy
        for k, v in self.snapshot().items():
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                continue
            reg.set(f"pul_engine_{k}", v, policy=policy)
        economics_into_registry(reg, self.economics(), policy=policy)
        return reg

    def run(self, max_ticks: int = 1000) -> Dict[int, List[int]]:
        """Drive steps until every submitted request completes (or the tick
        cap); returns {rid: generated tokens} for ALL submitted requests."""
        pending = lambda: (len(self.scheduler)
                           or any(r is not None for r in self.slot_req))
        ticks = 0
        while pending() and ticks < max_ticks:
            self.step()
            ticks += 1
        return _drain_results(self.requests)
