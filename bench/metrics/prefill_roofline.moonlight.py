"""The Moonlight miss prefill's share of its roofline: the least time
the chip could take for the traced batches' valid rows (the larger of
their FLOPs at the bf16 peak and the held weights at the HBM bandwidth,
a prefill's weights once a batch), over the device time of the prefill
program (``jit_prefill``) in the traced window. Routed work counts at
the held share the program counted. None where it counted none."""
import _moonlight as mo
import flops_moonlight as fm


def read(ctx):
    t, share = ctx.trace, mo.held_share(ctx)
    if t is None or share is None:
        return None
    busy = t.module_s(ctx.modules["prefill"])
    p, s = ctx.peaks, int(ctx.mix["prompt_len"])
    floor = sum(ctx.flops.time_floor(
        fm.prefill_flops(ctx.cfg, b["misses"], s, share),
        fm.prefill_bytes(ctx.cfg), p["bf16_flops_per_s"],
        p["hbm_bytes_per_s"]) for b in ctx.traced if b["misses"])
    if busy <= 0 or floor <= 0:
        return None
    return 100.0 * floor / busy
