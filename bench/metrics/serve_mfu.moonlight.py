"""The whole served step's share of the chip's bf16 peak over the traced
window, for the Moonlight cell: the useful FLOPs of every batch served
in it (the miss prefill at its valid rows, routed work at the held share
the program counted; the exhaustive lookup of its valid queries) over
the window's seconds times the chips times the peak. None where the
program counted no routed rows."""
import _moonlight as mo


def read(ctx):
    t, share = ctx.trace, mo.held_share(ctx)
    if t is None or not ctx.traced or share is None:
        return None
    useful = mo.prefill_flops(ctx, share)
    useful += sum(ctx.flops.lookup_flops(b["n"], ctx.keys, ctx.dim)
                  for b in ctx.traced)
    peak = ctx.peaks["bf16_flops_per_s"] * ctx.chips
    return 100.0 * useful / (t.window_s * peak)
