"""Sweep of offered rates for an open-loop cell, in one process, to find
the highest rate the engine sustains (the queue does not grow through
the window). The cell's mix file then fixes its rate as a number.

  python bench/sweep.py --workload <cell> --seed <n> --seconds 10 \\
      --rates 8000,12000,16000

One JSON line per rate: the rate served, p50/p95 latency, the mean
queue wait in the first and the last quarter of the window, and the
requests still unserved when the drain ran out. The benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np  # noqa: E402

import harness  # noqa: E402
import serving  # noqa: E402
import traffic  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    import jax
    cache_dir = BENCH.parent / ".jax_cache"
    cache_dir.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = harness.load_cell(args.workload)
    log = lambda m: print(m, file=sys.stderr, flush=True)  # noqa: E731
    su = harness.set_up(cell, args.seed, args.seconds, log)
    cdf = traffic.zipf_cdf(len(su.coords), float(cell.mix["zipf_alpha"]),
                           args.seed)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(cell.mix, rate=rate)
        su.sched = traffic.schedule(mix, cdf, int(cell.cfg["vocab_size"]),
                                    args.seed + i + 1, args.seconds)
        win, rec, _, _ = harness.measure(su, mix, args.seconds, None)
        rec.lookups.clear()
        rec.logits.clear()
        e2e = serving.end_to_end(win)
        served = win.served()
        wait = win.sent() - win.due[served]
        q = len(wait) // 4
        sizes = [b.n for b in win.batches]
        print(json.dumps({
            "rate": rate, **e2e, "failed": win.failed,
            "wait_first_quarter_ms": float(np.mean(wait[:q]) * 1e3),
            "wait_last_quarter_ms": float(np.mean(wait[-q:]) * 1e3),
            "batches": len(sizes), "mean_batch": float(np.mean(sizes)),
            "last_return_s": float(win.batches[-1].returned)}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
