import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: AOT lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: 512 placeholder
host devices form the production meshes; inputs are ShapeDtypeStructs (no
allocation); ``.lower().compile()`` must succeed and the compiled artifact
yields memory_analysis (fits?), cost_analysis (FLOPs/bytes) and the HLO
collective schedule — the inputs to the §Roofline report.

Usage:
  python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all --out results/dryrun
"""
import argparse
import json
import re
import sys
import time
import traceback
from collections import Counter
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import CONFIGS, SHAPES, get_config
from repro.launch import steps as S
from repro.launch.mesh import make_production_mesh, mesh_chips

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s+(?:\([^)]*\)\s+)?[\w:\[\]{}, ]*?"
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
_SHAPE_RE = re.compile(r"\b(pred|[suf]\d+|bf16|f16|c64|c128)\[([\d,]*)\]")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")


def _result_bytes(line: str) -> int:
    """Sum byte size of the result shape(s) left of '=' on an HLO line."""
    lhs = line.split(" = ", 1)[0] if " = " in line else ""
    rhs = line.split(" = ", 1)[1] if " = " in line else line
    # result shape(s) are the first shape token(s) on the rhs, before opcode
    head = rhs.split("(", 1)[0]
    nbytes = 0
    for dt, dims in _SHAPE_RE.findall(head):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        nbytes += n * _DTYPE_BYTES.get(dt, 4)
    return nbytes


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))        # [n_groups, group_size]<=[N]
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return default


_META_RE = re.compile(r'op_name="([^"]*)"')


def collective_bytes_from_hlo(hlo: str, n_devices: int):
    """Per-device wire bytes of every collective (per-partition HLO).

    Operand shapes are not printed inline by this XLA version, so byte
    counts derive from the RESULT shape + replica group size g per the
    standard ring costs:
      all-gather       (g-1)/g * result      (result = gathered buffer)
      reduce-scatter   (g-1)   * result      (result = scattered shard)
      all-reduce       2(g-1)/g * result
      all-to-all       (g-1)/g * result
      collective-permute        result
    `-done` ops are skipped (they would double-count their `-start`).

    Returns (static_total, per_kind, by_depth) where by_depth maps the
    lax.scan nesting depth (count of "/while/" in the op metadata) to bytes.
    XLA executes a loop body once per trip, so the roofline multiplies
    depth-d bytes by the enclosing trip counts (accum, num_groups, ...) —
    the static sum alone undercounts scanned collectives."""
    per_kind = Counter()
    by_depth = Counter()
    total = 0.0
    for line in hlo.splitlines():
        if "-done(" in line or "-done.1" in line:
            continue
        m = _COLL_RE.search(line)
        if m is None:
            continue
        kind = m.group(1)
        g = _group_size(line, n_devices)
        rb = _result_bytes(line)
        if g <= 1:
            continue
        if kind == "all-gather":
            nb = rb * (g - 1) / g
        elif kind == "reduce-scatter":
            nb = rb * (g - 1)
        elif kind == "all-reduce":
            nb = rb * 2 * (g - 1) / g
        elif kind == "all-to-all":
            nb = rb * (g - 1) / g
        else:  # collective-permute
            nb = rb
        meta = _META_RE.search(line)
        depth = meta.group(1).count("/while/") if meta else 0
        by_depth[depth] += int(nb)
        per_kind[kind] += int(nb)
        total += nb
    return int(total), dict(per_kind), dict(by_depth)


def dryrun_cell(arch: str, shape_name: str, *, multi_pod: bool,
                verbose: bool = True, accum: int = 0, variant: str = "",
                moe_backend: str = ""):
    """`variant` selects sharding experiments for the §Perf hillclimbs:
      serve_replicate   — inference weights replicated over (pod,data), TP
                          only over model (kills the per-step FSDP gather;
                          valid when params_bf16/16 fits HBM)
      cache_seq_data    — decode KV cache sequence NOT sharded over the
                          model axis (the pre-fix baseline of §Perf C)
    """
    cfg = get_config(arch)
    if moe_backend:
        import dataclasses
        cfg = dataclasses.replace(cfg, moe_backend=moe_backend)
    shape = SHAPES[shape_name]
    if not cfg.shape_applicable(shape):
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped",
                "reason": "pure full-attention arch; long_500k requires "
                          "sub-quadratic attention (DESIGN.md §5)"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh_chips(mesh)
    from repro.runtime.sharding import ShardingRules
    rules = None                        # cell_specs applies serve-replication
    if variant == "serve_replicate" and shape.kind != "train":
        rules = ShardingRules().with_overrides(embed=(None,))
    elif variant == "serve_fsdp":       # §Perf A baseline: FSDP'd weights
        rules = ShardingRules()
    elif variant == "cache_seq_data":   # §Perf C baseline
        rules = ShardingRules().with_overrides(cache_seq=("data", None))
    t0 = time.time()
    with jax.set_mesh(mesh):
        cell = S.cell_specs(cfg, shape, mesh, rules)
        if shape.kind == "train":
            # microbatch so activations fit HBM; recorded for §Perf
            accum = accum or cfg.train_accum
            while shape.global_batch % accum:
                accum //= 2
            fn = S.make_train_step(cfg, accum=accum)
            in_shardings = (cell["param_specs"], cell["opt_specs"],
                            cell["batch_specs"])
            args = (cell["params"], cell["opt"], cell["batch"])
            donate = (0, 1)
        elif shape.kind == "prefill":
            fn = S.make_prefill_step(cfg, max_seq=shape.seq_len)
            in_shardings = (cell["param_specs"], cell["batch_specs"])
            args = (cell["params"], cell["batch"])
            donate = ()
        else:  # decode
            fn = S.make_decode_step(cfg)
            in_shardings = (cell["param_specs"], cell["batch_specs"],
                            cell["cache_specs"])
            args = (cell["params"], cell["batch"], cell["caches"])
            donate = (2,)
        jfn = jax.jit(fn, in_shardings=in_shardings, donate_argnums=donate)
        lowered = jfn.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll_total, coll_kinds, coll_depth = collective_bytes_from_hlo(hlo, chips)
    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "status": "ok",
        "variant": variant or "default",
        "chips": chips,
        "step_kind": shape.kind,
        "accum": accum if shape.kind == "train" else None,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        # per-device numbers (SPMD per-partition module)
        "argument_bytes_per_dev": int(getattr(ma, "argument_size_in_bytes", 0)),
        "output_bytes_per_dev": int(getattr(ma, "output_size_in_bytes", 0)),
        "temp_bytes_per_dev": int(getattr(ma, "temp_size_in_bytes", 0)),
        "peak_bytes_per_dev": int(getattr(ma, "temp_size_in_bytes", 0))
        + int(getattr(ma, "argument_size_in_bytes", 0)),
        "flops_per_dev": float(ca.get("flops", 0.0)),
        "bytes_accessed_per_dev": float(ca.get("bytes accessed", 0.0)),
        "collective_bytes_per_dev": int(coll_total),
        "collective_kinds": coll_kinds,
        "collective_bytes_by_depth": {str(k): v for k, v in coll_depth.items()},
        "hlo_ops": {
            k: hlo.count(k) for k in
            ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute", "dynamic-slice", "fusion")
        },
    }
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} x "
              f"{'multi' if multi_pod else 'single'}: "
              f"compile={t_compile:.1f}s "
              f"args/dev={result['argument_bytes_per_dev']/2**30:.2f}GiB "
              f"temp/dev={result['temp_bytes_per_dev']/2**30:.2f}GiB "
              f"flops/dev={result['flops_per_dev']:.3e} "
              f"coll/dev={coll_total/2**20:.1f}MiB")
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch, shape) cell on both meshes")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--meshes", default="single,multi")
    ap.add_argument("--accum", type=int, default=0)
    ap.add_argument("--variant", default="")
    ap.add_argument("--moe-backend", default="")
    args = ap.parse_args(argv)

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)

    if args.all:
        meshes = args.meshes.split(",")
        archs = [args.arch] if args.arch else list(CONFIGS)
        shapes = [args.shape] if args.shape else list(SHAPES)
        failures = 0
        for arch in archs:
            for shape_name in shapes:
                for mesh_name in meshes:
                    tag = f"{arch}__{shape_name}__{mesh_name}"
                    fp = outdir / f"{tag}.json"
                    if fp.exists():
                        print(f"[dryrun] {tag}: cached")
                        continue
                    try:
                        res = dryrun_cell(arch, shape_name,
                                          multi_pod=(mesh_name == "multi"),
                                          accum=args.accum)
                    except (KeyboardInterrupt, SystemExit):
                        # never swallow an interrupt into an "error" cell:
                        # the sweep must stop, not record a bogus failure
                        raise
                    except Exception as e:
                        traceback.print_exc()
                        print(f"[dryrun] {tag}: swallowed "
                              f"{type(e).__name__} ({e}); recorded as an "
                              "error cell and continuing the sweep",
                              file=sys.stderr)
                        res = {"arch": arch, "shape": shape_name,
                               "mesh": mesh_name, "status": "error",
                               "error": f"{type(e).__name__}: {e}"}
                        failures += 1
                    fp.write_text(json.dumps(res, indent=2))
        sys.exit(1 if failures else 0)
    else:
        res = dryrun_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                          accum=args.accum, variant=args.variant,
                          moe_backend=args.moe_backend)
        print(json.dumps(res, indent=2))
        tag = f"{res['arch']}__{res['shape']}__{res['mesh']}"
        if args.variant or args.moe_backend:
            tag += f"__{args.variant or args.moe_backend}"
        (outdir / f"{tag}.json").write_text(json.dumps(res, indent=2))


if __name__ == "__main__":
    main()
