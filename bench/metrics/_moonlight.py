"""What the Moonlight cell's device metrics share: the held share of the
router's assignments (program counters), the rows each traced batch ran
on the device, and the time floor of its valid rows."""
from __future__ import annotations

import flops_moonlight as fm


def held_share(ctx):
    """``moe.rows_held`` / ``moe.assignments``, or None where the program
    keeps no such counters."""
    from repro import tracecount
    routed = tracecount.get("moe.assignments")
    return tracecount.get("moe.rows_held") / routed if routed else None


def device_pieces(ctx, misses: int) -> list:
    """The prefills a batch with ``misses`` misses ran: the engine's
    piece plan, at the harness's engine's smallest bucket."""
    from repro.serve.engine import EngineConfig, prefill_pieces
    return prefill_pieces(misses, int(ctx.mix["prompt_len"]),
                          EngineConfig.min_bucket)


def prefill_flops(ctx, share: float) -> float:
    """Useful FLOPs of the traced batches' valid rows."""
    s = int(ctx.mix["prompt_len"])
    return sum(fm.prefill_flops(ctx.cfg, b["misses"], s, share)
               for b in ctx.traced if b["misses"])


def ops_s(trace, prefix: str, skip: str) -> float:
    """Device seconds, inside the traced span, of the operations whose
    name starts with ``prefix`` and does not start with ``skip``."""
    import numpy as np
    lo, hi = trace.span
    ev = trace.ops
    s, e = np.maximum(ev.start, lo), np.minimum(ev.end, hi)
    base = np.array([str(n).split(" = ")[0].lstrip("%") for n in ev.name],
                    dtype=object)
    pick = np.array([b.startswith(prefix) and not b.startswith(skip)
                     for b in base], bool) & (e > s)
    return float(np.sum((e - s)[pick]))
