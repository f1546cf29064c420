"""One run of one cell, from the names in ``BENCHMARK.json`` to its
numbers: set-up, the measured window, the check, the metrics.

Nothing here knows a cell by name. A cell's configuration, traffic mix,
limits and per-layer readers are files found by the names that
``BENCHMARK.json`` gives:

- ``configs/<config>.json`` (the path in the config's ``file``): the
  model's published sizes, the cache deployment, the program's
  ``ArchConfig`` keys under ``program.arch``, and under ``reference``
  the module of ``reference/`` that computes it plainly;
- ``traffic/<traffic>.json``: the mix's parameters (see ``traffic.py``);
- ``limits/<cell>.json``: each number ``check.py`` compares, with its
  limit;
- ``metrics/<metric>.py``: a ``read(ctx)`` that returns the per-layer
  metric or None where it finds nothing to read.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import sys
import time
from contextlib import nullcontext
from pathlib import Path
from types import SimpleNamespace

import numpy as np

import check
import flops
import serving
import traffic

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    mix: dict
    limits: dict
    end_to_end: list           # the entries of BENCHMARK.json it reports
    per_layer: list


def load_cell(name: str, root: Path = CHECKOUT) -> Cell:
    bm = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    (conf,) = [c for c in bm["configs"] if c["name"] == w["config"]]
    bench = root / "bench"
    # a metric without a ``workloads`` list is reported by every cell (a
    # per-layer one: every cell that reports the metric it moves)
    e2e = [m for m in bm["end_to_end"] if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bm["per_layer"] if name in m.get(
        "workloads", [name] if m["moves"] in reported else [])]
    return Cell(
        name=name, chips=int(w["chips"]),
        cfg=json.loads((root / conf["file"]).read_text()),
        mix=json.loads((bench / "traffic" / f"{w['traffic']}.json")
                       .read_text()),
        limits=json.loads((bench / "limits" / f"{name}.json").read_text()),
        end_to_end=e2e, per_layer=per_layer)


def reader(metric: str, root: Path = CHECKOUT):
    """The ``read`` function of ``metrics/<metric>.py``."""
    folder = root / "bench" / "metrics"
    if str(folder) not in sys.path:
        sys.path.append(str(folder))
    path = folder / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def reference(cfg: dict):
    """The plain reference module a configuration names."""
    return importlib.import_module(f"reference.{cfg['reference']}")


# ----------------------------------------------------------------- set-up
def build_engine(cfg: dict, coords: np.ndarray, params):
    from repro.configs.base import ArchConfig
    from repro.serve import EngineConfig, SimCacheEngine
    cache = cfg["cache"]
    k0, k1, k2 = cache["levels"]
    _, h_ici, h_dcn = cache["h"]
    ecfg = EngineConfig(k_device=k0, k_pod=k1, k_global=k2, h_ici=h_ici,
                        h_dcn=h_dcn, h_model=cache["h_model"],
                        gamma=cache["gamma"], metric=cache["metric"],
                        warm_start=True, warm_polish_iters=0)
    return SimCacheEngine(ArchConfig(**cfg["program"]["arch"]), params,
                          ecfg, coords)


def draw_weights(cfg: dict, seed: int):
    import jax
    import weights
    from repro.configs.base import ArchConfig
    from repro.models import model as model_api
    arch = ArchConfig(**cfg["program"]["arch"])
    shapes = jax.eval_shape(lambda: model_api.init_params(arch, 0))
    return jax.block_until_ready(
        weights.draw(shapes, cfg["vocab_size"], seed))


def place(eng) -> None:
    """Solve the placement from the observed history and install it.

    This is ``SimCacheEngine.refresh_placement`` with the configuration's
    §4 warm start and no polish, less its predicted-cost report: that
    report prices every (requested object, slot) pair in one table,
    which at a 2²⁰-request history over 86,016 or 458,752 slots needs
    hundreds of GB of device memory and fails."""
    from repro.core.placement import warmstart
    inst = eng.observed_instance()
    slots = warmstart.warm_start(
        inst, polish_iters=eng.ecfg.warm_polish_iters,
        tol=eng.ecfg.swap_tol).slots
    eng._install(np.where(slots < 0, 0, slots), inst)


def batch_sizes(mix: dict) -> list[int]:
    """Every batch size the window sends."""
    cap = int(mix["batch"])
    if mix["arrival"] == "saturate":
        return [cap]
    return [1 << i for i in range(cap.bit_length()) if 1 << i <= cap]


def warm_up(eng, mix: dict, cdf: np.ndarray, vocab: int, seed: int):
    """Compile every shape the window can reach and nothing else: the
    miss prefill at each bucket up to the batch cap, and the whole serve
    path at each batch size the window sends."""
    import jax.numpy as jnp
    from repro.serve.engine import bucket_size
    s, lo = int(mix["prompt_len"]), eng.ecfg.min_bucket
    top = bucket_size(int(mix["batch"]), lo)
    b = lo
    while b <= top:
        np.asarray(jnp.argmax(eng.prefill(np.zeros((b, s), np.int32)),
                              axis=-1))
        b *= 2
    g = traffic.rng(seed, "warmup")
    for n in batch_sizes(mix):
        objs = traffic.iid_objects(cdf, n, g)
        eng.serve(objs, traffic.prompts(objs, s, vocab, seed))


@dataclasses.dataclass
class Setup:
    eng: object
    params: object
    coords: np.ndarray
    sched: traffic.Schedule
    times: dict                # phase → wall seconds
    seed: int


def set_up(cell: Cell, seed: int, seconds: float, log) -> Setup:
    cfg, mix = cell.cfg, cell.mix
    cache = cfg["cache"]
    times = {}
    clock = time.perf_counter

    t = clock()
    coords = traffic.catalog(int(cache["catalog_objects"]),
                             int(cache["embedding_dim"]), seed)
    times["catalog"] = clock() - t
    t = clock()
    params = draw_weights(cfg, seed)
    times["weights"] = clock() - t
    t = clock()
    eng = build_engine(cfg, coords, params)
    cdf = traffic.zipf_cdf(len(coords), float(mix["zipf_alpha"]), seed)
    hist = traffic.history(cdf, int(mix["batch"]), seed)
    np.add.at(eng.counts, (0, hist), 1.0)
    times["history"] = clock() - t
    t = clock()
    place(eng)
    times["place"] = clock() - t
    t = clock()
    sched = traffic.schedule(mix, cdf, int(cfg["vocab_size"]), seed,
                             seconds)
    times["schedule"] = clock() - t
    t = clock()
    warm_up(eng, mix, cdf, int(cfg["vocab_size"]), seed)
    # what set-up built lives as long as the run: keep the collector's
    # full passes in the window from walking it again and again
    gc.collect()
    gc.freeze()
    times["warm_up"] = clock() - t
    log(f"schedule: {len(sched.due)} requests due over {seconds} s"
        if sched.due is not None else
        f"schedule: batches of {sched.batch}, drawn as they are sent")
    return Setup(eng=eng, params=params, coords=coords, sched=sched,
                 times=times, seed=seed)


# ----------------------------------------------------------------- window
def _stats(eng) -> dict:
    s = eng.stats
    return {"n_requests": s.n_requests, "n_hits": s.n_hits,
            "total_cost": s.total_cost}


def measure(su: Setup, mix: dict, seconds: float, trace_dir: str | None):
    """Run the window. With ``trace_dir`` the profiler records its first
    ``trace_seconds``. Returns (window, recorder, stats delta, seconds
    of the window that were traced)."""
    import jax
    eng = su.eng
    before = _stats(eng)
    k = int(mix["check_batches"])
    rec = serving.Recorder(eng, traffic.rng(su.seed, "check.batches")
                           .choice(2 * k, size=k, replace=False))
    traced = [0.0]
    span = nullcontext
    tick = None
    if trace_dir is not None:
        span = jax.profiler.TraceAnnotation
        limit = float(mix["trace_seconds"])
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        outer = jax.profiler.TraceAnnotation("bench.traced")
        outer.__enter__()

        def tick(now):
            # ``now``: seconds since the window opened, before a batch
            if now >= limit and not traced[0]:
                outer.__exit__(None, None, None)
                traced[0] = now
                jax.profiler.stop_trace()
    if mix["arrival"] == "saturate":
        win = serving.saturate(eng, su.sched, seconds, span, tick=tick)
    else:
        win = serving.open_loop(eng, su.sched, seconds, span,
                                float(mix["drain_s"]), tick=tick)
    if trace_dir is not None and not traced[0]:
        outer.__exit__(None, None, None)
        traced[0] = win.batches[-1].returned if win.batches else seconds
        jax.profiler.stop_trace()
    gc.unfreeze()
    after = _stats(eng)
    delta = {k: after[k] - before[k] for k in before}
    rec.detach()
    return win, rec, delta, traced[0]


# ------------------------------------------------------------------ check
def run_check(cell: Cell, su: Setup, win, rec, delta: dict, seed: int,
              log) -> tuple[dict, check.Served, dict]:
    """Free the engine, then compare with the references. Returns the
    numbers, the per-request results, and what the references were given
    and gave (for the control)."""
    cfg, mix = cell.cfg, cell.mix
    n_served = sum(b.n for b in win.batches)
    rows = check.sample(n_served, check.LOOKUPS, seed,
                        "check.lookup")
    served = check.gather(win, rec.lookups, rows)
    rec.lookups.clear()
    eng = su.eng
    slots = np.asarray(eng.placement.slots)
    slot_cache = np.asarray(eng.placement.slot_cache)
    su.eng = None
    del eng
    gc.collect()

    from reference import lookup as lookup_ref
    cache = cfg["cache"]
    keys = lookup_ref.keys(su.coords, slots, slot_cache, cache["h"],
                           cache["h_model"])
    numbers = check.accounting(served, delta, win)
    numbers["allocation_errors"] = check.allocation_errors(
        slots, slot_cache, cache["levels"], len(su.coords))
    t = time.perf_counter()
    queries = su.coords[su.sched.objects[served.index[rows]]]
    numbers.update(check.lookup_numbers(served, rows, queries, keys))
    log(f"check: lookup reference over {len(rows)} requests "
        f"{time.perf_counter() - t:.3f} s")
    t = time.perf_counter()
    idx, prog = check.recorded_misses(win, served, rec.logits)
    rec.logits.clear()
    keep = check.sample(len(idx), check.MISSES, seed,
                        "check.model")
    idx, prog = idx[keep], prog[keep]
    prompts = su.sched.tokens[served.index[idx]]
    logits = None
    numbers["logit_gap"] = numbers["logit_err"] = 0.0
    if len(idx):
        logits = np.asarray(reference(cfg).last_logits(su.params, prompts,
                                                       cfg))
        numbers["logit_gap"] = check.logit_gap(served.token[idx], logits)
        numbers["logit_err"] = check.logit_err(prog, logits)
    log(f"check: model reference over {len(idx)} misses "
        f"{time.perf_counter() - t:.3f} s")
    given = {"keys": keys, "queries": queries, "prompts": prompts,
             "logits": logits}
    return numbers, served, given


# ---------------------------------------------------------------- metrics
def per_batch(win, served: check.Served) -> list[dict]:
    out, pos = [], 0
    for b in win.batches:
        hits = int(served.hit[pos:pos + b.n].sum())
        out.append({"n": b.n, "misses": b.n - hits, "sent": b.sent,
                    "returned": b.returned})
        pos += b.n
    return out


def context(cell: Cell, win, served, keys_n: int, trace, traced_s: float,
            peaks: dict, place_s: float, window_compiles: int):
    batches = per_batch(win, served)
    waits = None
    if win.due is not None and len(served.index):
        # over the traced batches: stopping the profiler stalls the rest
        traced = win.returned() <= traced_s
        waits = (win.sent() - win.due[served.index])[traced] * 1e3
    return SimpleNamespace(
        cfg=cell.cfg, mix=cell.mix, chips=cell.chips, peaks=peaks,
        modules=json.loads((BENCH / "modules.json").read_text()),
        keys=keys_n, dim=int(cell.cfg["cache"]["embedding_dim"]),
        trace=trace,
        traced=[b for b in batches if b["returned"] <= traced_s],
        hit_rate=float(served.hit.mean()) if len(served.hit) else None,
        mean_cost=float(np.mean(served.cost, dtype=np.float64))
        if len(served.cost) else None,
        place_s=place_s, window_compiles=window_compiles,
        queue_wait_ms=float(np.mean(waits)) if waits is not None
        and len(waits) else None,
        flops=flops)
