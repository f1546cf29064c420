"""The miss prefill's share of its roofline: the least time the chip
could take for the batches' valid rows (the larger of their FLOPs at the
bf16 peak and their weight and KV bytes at the HBM bandwidth), over the
device time of the prefill program in the traced window."""


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    busy = t.module_s(ctx.modules["prefill"])
    f, s, p = ctx.flops, int(ctx.mix["prompt_len"]), ctx.peaks
    floor = sum(f.time_floor(f.prefill_flops(ctx.cfg, b["misses"], s),
                             f.prefill_bytes(ctx.cfg, b["misses"], s),
                             p["bf16_flops_per_s"], p["hbm_bytes_per_s"])
                for b in ctx.traced if b["misses"])
    if busy <= 0 or floor <= 0:
        return None
    return 100.0 * floor / busy
