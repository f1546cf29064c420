"""Readings that the limits of ``limits/<cell>.json`` are set from.

  python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 3

For each seed, in one process: the cell's set-up, a short window at the
cell's own load, and the check, which gives the program's readings of
every compared number. Then the control on the same inputs: the plain
references put in the program's place one precision step down from what
the configuration states, and compared with the references at full
precision:

- the model in float8 e4m3 (every matmul operand and the residual
  stream) for the configuration's bfloat16: at every position of the
  sampled misses' prompts, the gap by which its greedy token lies below
  the float32 reference's best, read as ``logit_gap``, and at the last
  position the widest gap between its logits and the reference's, read
  as ``logit_err``;
- the lookup's float32 scan at ``high`` (three bf16 passes) for the
  configuration's float32 at ``highest``: its chosen candidate and cost,
  read by ``lookup_mismatch`` and ``cost_err``.

A limit lies above the largest program reading and below the smallest
control reading. One JSON line per seed goes to standard output, and a
summary last. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np  # noqa: E402

import check  # noqa: E402
import harness  # noqa: E402
from reference import lookup as lookup_ref  # noqa: E402


def control_numbers(cell, su, given: dict) -> dict:
    keys, queries = given["keys"], given["queries"]
    cost, best = lookup_ref.scan_device(queries, keys, "high")
    repo = best == keys.n
    b = np.minimum(best, keys.n - 1)
    ctrl = check.Served(
        index=np.arange(len(best)),
        level=np.where(repo, -1, keys.level[b]),
        slot=np.where(repo, 0, keys.slot[b]),
        payload=np.where(repo, -1, keys.payload[b]),
        cost=cost, hit=~repo, token=np.zeros(len(best), np.int64))
    out = check.lookup_numbers(ctrl, np.arange(len(best)), queries, keys)
    out["logit_gap"] = out["logit_err"] = None
    if given["logits"] is not None:
        ref = harness.reference(cell.cfg)
        out["logit_gap"] = ref.control_gap(su.params, given["prompts"],
                                           cell.cfg)
        out["logit_err"] = check.logit_err(
            np.asarray(ref.last_logits(su.params, given["prompts"],
                                       cell.cfg, quant="fp8")),
            given["logits"])
    return out


def readings(cell, seed: int, seconds: float, log=print) -> dict:
    su = harness.set_up(cell, seed, seconds, log)
    win, rec, delta, _ = harness.measure(su, cell.mix, seconds, None)
    program, served, given = harness.run_check(cell, su, win, rec, delta,
                                               seed, log)
    control = control_numbers(cell, su, given)
    return {"seed": seed, "requests": len(served.index),
            "misses_checked": 0 if given["logits"] is None
            else len(given["logits"]),
            "program": program, "control": control}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    import jax
    cache_dir = BENCH.parent / ".jax_cache"
    cache_dir.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = harness.load_cell(args.workload)
    rows = []
    for s in args.seeds.split(","):
        t = time.perf_counter()
        r = readings(cell, int(s), args.seconds,
                     log=lambda m: print(m, file=sys.stderr, flush=True))
        r["wall_s"] = time.perf_counter() - t
        rows.append(r)
        print(json.dumps(r), flush=True)
    summary = {}
    for k in ("lookup_mismatch", "cost_err", "logit_gap", "logit_err"):
        p = [r["program"][k] for r in rows]
        c = [r["control"][k] for r in rows if r["control"][k] is not None]
        summary[k] = {"program_max": max(p), "control_min":
                      min(c) if c else None}
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
