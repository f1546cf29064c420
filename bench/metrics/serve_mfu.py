"""The whole served step's share of the chips' bf16 peak over the traced
window: the useful FLOPs of every batch served in it (the miss prefill
at its valid rows, the exhaustive lookup of its valid queries) over the
window's seconds times the chips times the peak."""


def read(ctx):
    t = ctx.trace
    if t is None or not ctx.traced:
        return None
    f, s = ctx.flops, int(ctx.mix["prompt_len"])
    useful = sum(f.prefill_flops(ctx.cfg, b["misses"], s) if b["misses"]
                 else 0.0 for b in ctx.traced)
    useful += sum(f.lookup_flops(b["n"], ctx.keys, ctx.dim)
                  for b in ctx.traced)
    peak = ctx.peaks["bf16_flops_per_s"] * ctx.chips
    return 100.0 * useful / (t.window_s * peak)
