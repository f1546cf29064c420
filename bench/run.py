"""Benchmark of the similarity-cache serving engine on a TPU, measured
from the client's side of ``SimCacheEngine.serve``.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

runs one cell of ``BENCHMARK.json`` on the chips of this machine: set-up
(catalog, weights, engine, placement, warm-up), a window of ``--seconds``
in which the cell's traffic is offered, then the check of what was
served against the plain references. With ``--trace 0`` the result line
carries the cell's end-to-end metrics; with ``--trace 1`` the profiler
records the first seconds of the window and the line carries the
per-layer metrics, ``busy_s``/``window_s`` and the breakdown.

The last line of standard output is the result, one JSON object; the
numbers compared for ``correct`` are printed beside their limits as the
last lines of standard error and under ``checks``, the result's last
key. Without a TPU, or with fewer chips than the cell asks for, the run
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent
sys.path[:0] = [str(BENCH), str(CHECKOUT / "src")]
# the TPU runtime logs to a fixed directory under /tmp unless told not to
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def log(msg: str) -> None:
    print(f"[bench {time.perf_counter() - T_START:8.3f}] {msg}", flush=True)


def devices_or_exit(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: needs a TPU; JAX found platform "
              f"{devs[0].platform!r}", file=sys.stderr)
        return None
    if len(devs) < chips:
        print(f"bench: the cell needs {chips} chips; JAX found {len(devs)}",
              file=sys.stderr)
        return None
    return devs


def peak_bytes(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def execute(cell, seed: int, seconds: float, trace: bool, devs,
            peaks: dict) -> dict:
    """Set-up, window, check and metrics of one run; the result line."""
    import harness
    from tracing import CompileClock, Trace
    clock = CompileClock()
    log(f"cell {cell.name}, seed {seed}, window {seconds} s, trace "
        f"{int(trace)}; devices {devs[0].device_kind} x {len(devs)}")

    su = harness.set_up(cell, seed, seconds, log)
    setup_s = time.perf_counter() - T_START
    compile_s, compiles = clock.reset()
    for k, v in su.times.items():
        log(f"set-up {k}: {v:.3f} s")
    log(f"set-up: {setup_s:.3f} s, of which compiling {compile_s:.3f} s "
        f"({compiles} programs)")

    trace_dir = str(CHECKOUT / "bench_out" / "trace") if trace else None
    if trace_dir:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
    win, rec, delta, traced_s = harness.measure(su, cell.mix, seconds,
                                                trace_dir)
    _, window_compiles = clock.reset()
    peak = peak_bytes(devs[:cell.chips])
    log(f"window: {len(win.batches)} batches, "
        f"{sum(b.n for b in win.batches)} requests served of "
        f"{win.offered} offered; compiles in the window: {window_compiles}")
    log(f"peak HBM: {peak} bytes")
    log("batch wall times: " + harness.serving.stall_summary(win))
    keys_n = int(su.eng.simcache.fused_layout()[0].shape[0])

    numbers, served, _ = harness.run_check(cell, su, win, rec, delta, seed,
                                        log)
    correct, rows = harness.check.verdict(numbers, cell.limits)

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": win.offered,
              "failed": win.failed, "metrics": {}, "device": device}
    if trace:
        tr = Trace.load(trace_dir)
        ctx = harness.context(cell, win, served, keys_n, tr, traced_s,
                              peaks, su.times["place"], window_compiles)
        for m in cell.per_layer:
            v = harness.reader(m["name"])(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": m["unit"]}
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        result["breakdown"] = {"device_ops": tr.top_ops(),
                               "idle_gaps": tr.idle_gaps()}
        log(f"trace: {len(ctx.traced)} batches in {tr.window_s:.3f} s, "
            f"device busy {device['busy_s']:.3f} s")
    else:
        e2e = harness.serving.end_to_end(win)
        e2e["setup_s"] = setup_s
        log("window statistics: " + ", ".join(f"{k} {v}" for k, v in
                                               e2e.items()))
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": m["unit"]}
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in rows}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness
    cell = harness.load_cell(args.workload)
    devs = devices_or_exit(cell.chips)
    if devs is None:
        return 1
    import flops
    peaks = flops.peaks(devs[0].device_kind)

    import jax
    cache_dir = CHECKOUT / ".jax_cache"
    cache_dir.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    result = execute(cell, args.seed, args.seconds, bool(args.trace), devs,
                     peaks)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
