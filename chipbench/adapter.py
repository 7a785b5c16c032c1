"""The harness's side of the program under test (`repro`), the same for
every architecture family.

Everything the harness needs from the program goes through here: its model
configuration and parameter tree, which the configuration's family maps
(`adapters/<family>.py`, found by `discover.family`), its paged serving
engine, requests, counters, and the compile cache directory its entry
points use. Besides this module, only the family adapters and
`program_spans.py` import `repro`. Reading the program's state is limited
to public attributes, read once after the measured window.
"""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402

from chipbench import discover  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402,F401
from repro.models import build_model  # noqa: E402
from repro.serving import PagedServingEngine, Request, ServingConfig  # noqa: E402


def model_config(name: str, arch: dict):
    """The program's config of registry entry `name`, with every size and
    option taken from the configuration file `arch`, by its family."""
    return discover.family(arch).adapter.model_config(name, arch)


def vocab_rows(cfg) -> int:
    """Rows of the embedding and head tables the program stores."""
    return cfg.padded_vocab


def params(arch: dict, cfg, w: dict):
    """The benchmark's weights `w` (the family reference's layout) in the
    program's parameter tree, mapped by the family; checked leaf by leaf
    against the tree the program's model expects."""
    tree = discover.family(arch).adapter.params(cfg, w)
    want = build_model(cfg).abstract_params()
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("weights do not match the program's parameter tree")
    return tree


def engine(cfg, tree, cell: dict):
    """The paged serving engine as the cell configures it, with the
    compiled fused sweep decode on where the program still has the knobs."""
    knobs = dict(batch_slots=cell["slots"], max_seq=cell["max_seq"],
                 page_tokens=cell["page_tokens"],
                 prefill_buckets=tuple(cell["prefill_buckets"]),
                 policy=cell["policy"])
    fields = {f.name for f in dataclasses.fields(ServingConfig)}
    for knob in ("use_paged_kernel", "sweep_decode"):
        if knob in fields:
            knobs[knob] = True
    return PagedServingEngine(cfg, tree, ServingConfig(**knobs))


def request(rid: int, prompt: list, max_new: int, priority: int):
    return Request(rid=rid, prompt=prompt, max_new_tokens=max_new,
                   priority=priority)


def counters(eng) -> dict:
    """The engine's and the page pool's counters (a cheap attribute read)."""
    m, pm = eng.metrics, eng.pool.metrics
    return {"ticks": m.ticks, "tokens_emitted": m.tokens_emitted,
            "prefills": m.prefills, "decode_steps": m.decode_steps,
            "preemptions": m.preemptions, "readmissions": m.readmissions,
            "page_faults": pm.page_faults, "evictions": pm.evictions,
            "shared_hits": pm.shared_hits, "queued": len(eng.scheduler)}


def busy(eng) -> bool:
    """Work queued or in a slot."""
    return bool(len(eng.scheduler)) or any(
        r is not None for r in eng.slot_req)


def tick(eng) -> int:
    """Index of the engine's next tick (what `Request.admit_tick` names)."""
    return eng.metrics.ticks


def wait(eng) -> None:
    """Block until the engine's device work so far has finished."""
    jax.block_until_ready(eng.pool.planes)
