"""Kernels: the paged sweep decode's share of its roofline. The work it
needs is read from the live lengths of the decode steps inside the traced
window (the family reference's `sweep_work`: for dense GQA each cached K/V
row read once, QK and PV), not from the pages it streams, so reading the
whole slot capacity shows as a low share. The bound is HBM bandwidth
whenever bytes / bandwidth > FLOPs / peak, which holds for decode
attention."""
from chipbench import discover, programs, trace


def read(ctx):
    if ctx.trace is None or ctx.peaks is None or not ctx.trace.devices:
        return None
    lo, hi = ctx.tw
    kernel = sum(trace.op_time(d, lo, hi, programs.SWEEP_KERNEL.search)
                 for d in ctx.trace.devices)
    if kernel <= 0 or not ctx.traced_decode:
        return None
    sweep_work = discover.family(ctx.arch).reference.sweep_work
    need = 0.0
    for cached in ctx.traced_decode:
        flops, nbytes = sweep_work(ctx.arch, cached)
        need += max(flops / ctx.peaks["bf16_flops"],
                    nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * need / kernel
