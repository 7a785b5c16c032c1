"""Logical-axis sharding rules with divisibility-aware fallback.

Every tensor in the framework carries *logical* axis names ("embed", "heads",
"ff", "batch", ...). This module resolves them to mesh axes on the production
mesh ``(pod, data, model)``:

  * weights are 2D-sharded: FSDP (ZeRO-3) over ``("pod","data")`` on their
    d_model-sized dim, tensor-parallel over ``"model"`` on heads/ff/vocab/
    experts — so a 314B-param model spreads over all 512 chips;
  * activations are batch-sharded over ``("pod","data")``; KV caches and
    long-context decode additionally shard the sequence dim over ``"data"``
    (batch=1 at 500k tokens cannot use the data axis);
  * each rule is a *priority list*: the resolver picks the first candidate
    whose device count divides the dim and whose mesh axes are not already
    used by an earlier dim of the same tensor, else replicates. This is how
    awkward shapes (40 heads on a 16-way model axis, vocab 92553) stay
    runnable — they fall back to replication for that dim only, and the
    roofline report makes the cost visible (padding them is a recorded
    §Perf optimization, not a silent default).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axis = Union[None, str, Tuple[str, ...]]

# priority list per logical name; None means "replicate" and always succeeds
DEFAULT_RULES: Dict[str, Sequence[Axis]] = {
    # ---- weight dims ----
    "embed": (("pod", "data"), "data", None),   # FSDP / ZeRO-3 shard dim
    "ff": ("model", None),                  # tensor parallel
    "vocab": ("model", None),
    "heads": ("model", None),
    "kv_heads": ("model", None),
    "experts": ("model", None),             # expert parallel
    "dinner": ("model", None),              # mamba inner channels
    "head_dim": (None,),
    "state": (None,),                       # SSM state dim
    "conv": (None,),
    "lora": (None,),
    "kv_rank": (None,),                     # MLA compressed dims stay local
    "q_rank": (None,),
    "norm": (None,),
    # ---- activation dims ----
    "batch": (("pod", "data"), "data", None),
    "seq": (None,),
    "act_embed": (None,),
    "act_heads": ("model", None),
    "act_kv_heads": ("model", None),
    "act_ff": ("model", None),
    # KV cache: sequence shards over whichever axis the batch/head dims left
    # free — on GQA models with few kv heads (8 < 16-way model axis) the
    # model axis takes the sequence dim, keeping 32k x 128-batch caches
    # under HBM limits; decode attention then reduces over the model axis.
    "cache_seq": ("data", "model", None),
    "seq_model": ("model", None),           # remat-carry sequence sharding
    "cache_batch": (("pod", "data"), "data", None),
    "expert_cap": (None,),
    "codebooks": (None,),
}


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    rules: Dict[str, Sequence[Axis]] = dataclasses.field(
        default_factory=lambda: dict(DEFAULT_RULES)
    )

    def with_overrides(self, **over: Sequence[Axis]) -> "ShardingRules":
        r = dict(self.rules)
        r.update(over)
        return ShardingRules(r)


def _axis_size(mesh: Mesh, axis: Axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, str):
        return mesh.shape[axis]
    return math.prod(mesh.shape[a] for a in axis)


def _axis_names(axis: Axis) -> Tuple[str, ...]:
    if axis is None:
        return ()
    if isinstance(axis, str):
        return (axis,)
    return tuple(axis)


def logical_to_spec(
    logical: Sequence[Optional[str]],
    dims: Sequence[int],
    mesh: Mesh,
    rules: ShardingRules = ShardingRules(),
) -> P:
    """Resolve one tensor's logical axes to a PartitionSpec.

    Left-to-right; a mesh axis is used at most once per tensor; a candidate
    is accepted only if its total device count divides the dim size.
    """
    if len(logical) != len(dims):
        raise ValueError(f"logical {logical} does not match rank of shape {dims}")
    used: set = set()
    out = []
    for name, dim in zip(logical, dims):
        picked: Axis = None
        for cand in rules.rules.get(name, (None,)) if name is not None else (None,):
            names = _axis_names(cand)
            if any(n not in mesh.shape for n in names):
                continue  # axis absent on this mesh (e.g. single-pod)
            if any(n in used for n in names):
                continue
            if dim % _axis_size(mesh, cand) != 0:
                continue
            picked = cand
            break
        used.update(_axis_names(picked))
        out.append(picked)
    # trailing Nones can be dropped, PartitionSpec pads implicitly
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def constrain(x, logical: Sequence[Optional[str]], rules: ShardingRules = ShardingRules()):
    """with_sharding_constraint via logical names, using the ambient mesh.

    Identity when tracing outside any mesh (CPU unit tests); inside
    jax.set_mesh / Mesh context it resolves the same way weights do.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    spec = logical_to_spec(logical, x.shape, mesh, rules)
    return jax.lax.with_sharding_constraint(x, spec)


def spec_tree(logical_tree, shape_tree, mesh: Mesh, rules: ShardingRules = ShardingRules()):
    """Map a pytree of logical-axis tuples + shapes to PartitionSpecs."""
    return jax.tree.map(
        lambda logical, shaped: logical_to_spec(logical, shaped.shape, mesh, rules),
        logical_tree,
        shape_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x),
    )


def sharding_tree(logical_tree, shape_tree, mesh: Mesh, rules: ShardingRules = ShardingRules()):
    specs = spec_tree(logical_tree, shape_tree, mesh, rules)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))
