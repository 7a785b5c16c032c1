"""One serving configuration to rule the four layers.

The serving stack historically grew a config dataclass per layer:

  * :class:`~repro.serving.engine.EngineConfig` — dense reference engine;
  * :class:`~repro.serving.engine.PagedEngineConfig` — paged engine;
  * :class:`~repro.serving.scheduler.SchedulerConfig` — admission control;
  * :class:`~repro.serving.kv_pages.PageConfig` — the page pool.

Every entry point had to rebuild the same knobs into whichever subset its
layer wanted, and the launchers each carried their own flag-to-dataclass
plumbing. :class:`ServingConfig` collapses that: ONE documented facade
holding the union of the knobs, with projections onto each layer config
(:meth:`dense`, :meth:`paged`, :meth:`scheduler`, :meth:`pages`) and a
single argparse adapter (:meth:`add_flags` / :meth:`from_flags`) shared by
``repro.launch.serve`` and ``examples/serve_lm.py``.

Both engines accept a ``ServingConfig`` directly — ``ServingEngine``
projects it with :meth:`dense`, ``PagedServingEngine`` with :meth:`paged`
— so callers no longer need to know which layer config a knob lives in.
The per-layer dataclasses remain the internal representation (and remain
accepted), so existing code keeps working unchanged.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

# Default prefill bucket ladder; buckets above max_seq are dropped by
# from_flags/paged (the scheduler requires every bucket <= max_seq).
_BUCKET_LADDER = (16, 32, 64, 128)


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    """Union of every serving knob, documented once.

    Capacity / shape:
      batch_slots: concurrent decode slots (the continuous batch width).
      max_seq: per-slot token capacity (prompt + generated); paged mode
        requires it to be a multiple of ``page_tokens``.

    Sampling:
      greedy: argmax decoding (True) or temperature-1 sampling (False).
      sample_seed: rng seed for ``greedy=False`` — shared by both engines
        so sampled runs stay differential-testable.

    Dense reference engine only:
      prefill_bucket: the single left-padded prefill width.

    Page store (paged engine):
      page_tokens: tokens per KV page.
      hot_pages: fast-tier frames (0 = size for every live slot resident).
      preload_distance: PUL preload distance for page restores
        (None = the planner's d*).
      share_prefix_pages: share page-aligned prompt prefixes across
        requests (and reuse their cached first-token logits).

    Admission (paged engine):
      prefill_buckets: ascending compiled prefill widths.
      max_active_tokens: token budget across live slots (0 = slots cap).
      policy: "fcfs" | "priority" | "slo-edf" (the latter two preempt).
      prefill_chunk_tokens: page-aligned chunked prefill threshold
        (0 = monolithic prefill at admission).

    Debug:
      shadow_check: trace the page lifecycle and replay it through the
        sanitizer every tick (test-only; zero overhead when off).
    """

    batch_slots: int = 4
    max_seq: int = 256
    greedy: bool = True
    sample_seed: int = 0
    prefill_bucket: int = 64
    page_tokens: int = 16
    hot_pages: int = 0
    preload_distance: Optional[int] = None
    share_prefix_pages: bool = True
    prefill_buckets: Tuple[int, ...] = (16, 32, 64)
    max_active_tokens: int = 0
    policy: str = "fcfs"
    prefill_chunk_tokens: int = 0
    shadow_check: bool = False

    # ------------------------------------------------------------------ #
    # projections onto the per-layer configs
    # ------------------------------------------------------------------ #
    def dense(self):
        """Project onto the dense reference engine's EngineConfig."""
        from repro.serving.engine import EngineConfig
        return EngineConfig(
            batch_slots=self.batch_slots, max_seq=self.max_seq,
            prefill_bucket=self.prefill_bucket, greedy=self.greedy,
            sample_seed=self.sample_seed)

    def paged(self):
        """Project onto the paged engine's PagedEngineConfig."""
        from repro.serving.engine import PagedEngineConfig
        buckets = tuple(b for b in self.prefill_buckets if b <= self.max_seq)
        return PagedEngineConfig(
            batch_slots=self.batch_slots, max_seq=self.max_seq,
            page_tokens=self.page_tokens, hot_pages=self.hot_pages,
            prefill_buckets=buckets or (self.max_seq,),
            max_active_tokens=self.max_active_tokens,
            preload_distance=self.preload_distance,
            share_prefix_pages=self.share_prefix_pages,
            policy=self.policy,
            prefill_chunk_tokens=self.prefill_chunk_tokens,
            greedy=self.greedy, sample_seed=self.sample_seed,
            shadow_check=self.shadow_check)

    def scheduler(self):
        """Project onto the admission scheduler's SchedulerConfig (the
        same derivation PagedServingEngine applies internally)."""
        from repro.serving.scheduler import SchedulerConfig
        p = self.paged()
        return SchedulerConfig(
            prefill_buckets=p.prefill_buckets,
            max_active_tokens=(p.max_active_tokens
                               or p.batch_slots * p.max_seq),
            page_tokens=p.page_tokens, policy=p.policy, max_seq=p.max_seq)

    def pages(self):
        """Project onto the page pool's PageConfig (hot-frame sizing as
        PagedServingEngine derives it, reserved frames included)."""
        from repro.serving.kv_pages import PageConfig
        slot_pages = self.max_seq // self.page_tokens
        hot = self.hot_pages or (self.batch_slots * slot_pages + 2)
        return PageConfig(
            page_tokens=self.page_tokens, hot_frames=hot + 2,
            preload_distance=self.preload_distance,
            share_prefix_pages=self.share_prefix_pages,
            trace=self.shadow_check)

    # ------------------------------------------------------------------ #
    # the one flag surface
    # ------------------------------------------------------------------ #
    @staticmethod
    def add_flags(ap) -> None:
        """Register the serving knobs on an argparse parser (the flag
        names ``repro.launch.serve`` has always exposed)."""
        ap.add_argument("--slots", type=int, default=4)
        ap.add_argument("--max-seq", type=int, default=128)
        ap.add_argument("--page-tokens", type=int, default=16)
        ap.add_argument("--hot-pages", type=int, default=0)
        ap.add_argument("--distance", type=int, default=0,
                        help="page-restore preload distance (0 = planner d*)")
        ap.add_argument("--max-active-tokens", type=int, default=0)
        ap.add_argument("--no-prefix-sharing", action="store_true")
        ap.add_argument("--policy", default="fcfs",
                        choices=("fcfs", "priority", "slo-edf"),
                        help="admission policy; priority and slo-edf preempt "
                             "running requests (swap-out to the cold tier)")
        ap.add_argument("--prefill-chunk", type=int, default=0,
                        help="chunked prefill: page-aligned tokens per tick "
                             "for prompts longer than this (0 = monolithic)")

    @classmethod
    def from_flags(cls, args) -> "ServingConfig":
        """Build from a parsed argparse namespace (see :meth:`add_flags`).

        Unknown knobs keep their dataclass defaults, so a launcher that
        registers only a subset of the flags still gets a full config."""
        get = lambda name, default: getattr(args, name, default)
        max_seq = get("max_seq", 128)
        return cls(
            batch_slots=get("slots", 4),
            max_seq=max_seq,
            prefill_bucket=min(64, max_seq // 2),
            page_tokens=get("page_tokens", 16),
            hot_pages=get("hot_pages", 0),
            preload_distance=get("distance", 0) or None,
            max_active_tokens=get("max_active_tokens", 0),
            share_prefix_pages=not get("no_prefix_sharing", False),
            policy=get("policy", "fcfs"),
            prefill_chunk_tokens=get("prefill_chunk", 0),
            prefill_buckets=tuple(b for b in _BUCKET_LADDER
                                  if b <= max_seq) or (max_seq,),
        )
