"""Model step, whole: model FLOPs of every prompt prefilled and token
generated in the window (the family reference's `prompt_flops` and
`decode_flops`) over the summed wall time of the engine steps that emitted
tokens, times the chip's bf16 peak."""
from chipbench import discover


def read(ctx):
    ref = discover.family(ctx.arch).reference
    flops = 0.0
    for r in ctx.requests:
        for j, t in enumerate(r["tokens"]):
            if not ctx.ws <= t < ctx.we:
                continue
            if j == 0:
                flops += ref.prompt_flops(ctx.arch, r["prompt_len"])
            else:
                flops += ref.decode_flops(ctx.arch, r["prompt_len"] + j - 1)
    busy = sum(t1 - t0 for t0, t1, n in ctx.steps
               if n and ctx.ws <= t0 and t1 <= ctx.we)
    if not flops or not busy or ctx.peaks is None:
        return None
    return 100.0 * flops / (busy * ctx.peaks["bf16_flops"])
