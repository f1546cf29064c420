"""The held experts' grouped matmuls' share of their roofline: the least
time of the traced batches' expert work (the held rows' three matmuls,
6·d·f FLOPs a row, at the bf16 peak, against the held experts' weights
read once a layer by each prefill at the HBM bandwidth), over the device
time of the grouped-matmul operations (XLA's ``ragged-dot`` kernels, not
their metadata) in the traced window. The held rows are the batches'
device rows (the engine's piece plan, padding included, as the device
computes them) times the router's assignments a row, at the held share
the program counted. None where there is no such operation or count."""
import _moonlight as mo
import flops_moonlight as fm

KERNEL, METADATA = "ragged-dot", "ragged-dot-metadata"


def read(ctx):
    t, share = ctx.trace, mo.held_share(ctx)
    if t is None or share is None:
        return None
    busy = mo.ops_s(t, KERNEL, METADATA)
    s, p = int(ctx.mix["prompt_len"]), ctx.peaks
    floor = 0.0
    for b in ctx.traced:
        if not b["misses"]:
            continue
        pieces = mo.device_pieces(ctx, b["misses"])
        rows = fm.assignments(ctx.cfg, sum(pieces), s) * share
        floor += ctx.flops.time_floor(
            rows * fm.routed_row_flops(ctx.cfg),
            fm.expert_bytes(ctx.cfg, len(pieces)),
            p["bf16_flops_per_s"], p["hbm_bytes_per_s"])
    if busy <= 0 or floor <= 0:
        return None
    return 100.0 * floor / busy
