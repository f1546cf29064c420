"""The fused lookup's share of its roofline: the least time of one
exhaustive scan of the cached keys per batch (the larger of 2·n·K·D
operations at the chip's highest rate, int8, and K·D bytes at the HBM
bandwidth), over the device time of the lookup program in the traced
window. The floor takes the fastest rate and the fewest bytes, so no
lower-precision kernel can read above 100 %."""


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    busy = t.module_s(ctx.modules["lookup"])
    f, p = ctx.flops, ctx.peaks
    floor = sum(f.time_floor(f.lookup_flops(b["n"], ctx.keys, ctx.dim),
                             f.lookup_bytes(ctx.keys, ctx.dim),
                             p["int8_ops_per_s"], p["hbm_bytes_per_s"])
                for b in ctx.traced)
    if busy <= 0 or floor <= 0:
        return None
    return 100.0 * floor / busy
