"""Serving launcher: a reduced model behind the similarity-cache network
(the paper's system end-to-end; see examples/serve_simcache.py for the
narrated version).

  PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b \
      --requests 256

``--streaming`` switches from the fixed-batch replay loop to the async
multi-stream driver (serve/stream.py): N Poisson request streams
multiplexed into bucketed batches, placement refreshed through the
double buffer in the background (cadence via ``--refresh-every``, plus
NETDUEL promotion churn when ``--netduel``) and swapped in atomically
between batches — the loop never blocks on a solve.

  PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b \
      --streaming --streams 4 --requests 1024 --netduel

``--scenario`` swaps the built-in 3-level hierarchy for a generated
general-graph network (core/scenarios.py: isp / scale_free /
watts_strogatz with degree-centrality cache sizing) and serves
multi-ingress traffic through the on-path strategy plane picked by
``--strategy`` (core/routing.py) — the λ-unaware online alternative to
the offline-placement plane:

  PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b \
      --streaming --scenario scale_free --strategy lce --requests 512
"""
from __future__ import annotations

import argparse
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracecount
from repro.configs.base import ArchConfig
from repro.configs.registry import get_smoke_config, list_archs
from repro.core import catalog as catalog_api
from repro.core import demand as demand_api
from repro.core import scenarios as scenarios_api
from repro.core.catalog import Catalog
from repro.core.routing import STRATEGIES
from repro.core.topology import CacheNetwork
from repro.models import model as model_api
from repro.serve import (EngineConfig, SimCacheEngine, StreamDriver,
                         StreamSpec)

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache(checkout: Path = CHECKOUT) -> str:
    """Turn on JAX's persistent compile cache for an entry point; returns
    its directory. ``JAX_COMPILATION_CACHE_DIR``, when set, is used as
    JAX reads it. Otherwise the cache lives at the fixed path
    ``<checkout>/.jax_cache``: the path is part of what a later process
    must find again, so it never derives from a temp dir, pid or time."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(checkout) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def build_engine(cfg: ArchConfig, ecfg: EngineConfig, cat: Catalog, *,
                 seed: int = 0, mesh: jax.sharding.Mesh | None = None,
                 net: CacheNetwork | None = None) -> SimCacheEngine:
    """A decoder model with weights drawn from ``seed`` behind the
    similarity-cache network over ``cat`` — the construction shared by
    this launcher and ``chip_smoke.py``.

    The weights are drawn by one jitted program: each weight's f32
    normal, scale and cast fuse, so no f32 copy of a weight is ever
    resident. Drawn op by op on a TPU v5e, granite-3-2b's bf16 weights
    (5.07 GB) peaked at 12.1 GB of device memory and took 83 s of
    compiles."""
    if cfg.is_encdec or cfg.mrope:
        raise ValueError("the serving engine runs decoder-only archs")
    params = jax.jit(model_api.init_params, static_argnums=(0, 1))(cfg, seed)
    return SimCacheEngine(cfg, params, ecfg, cat.coords, mesh=mesh, net=net)


def run_batch_loop(eng, cfg, dem, args) -> None:
    rng = np.random.default_rng(0)
    n_batches = args.requests // args.batch
    for i in range(n_batches):
        ids, ings = dem.sample(args.batch, rng)
        prompts = jnp.asarray(rng.integers(0, cfg.vocab,
                                           (args.batch, 16)).astype(np.int32))
        eng.serve(ids, prompts, ingress_ids=ings)
        if i == n_batches // 2 and eng.routing is None:
            pred = eng.refresh_placement()
            print(f"[serve] placement refreshed; predicted C(A)={pred:.2f}")


def run_streaming(eng, cat, args) -> None:
    n_ing = eng.net.n_ingress
    streams = [
        StreamSpec(demand=demand_api.zipf(cat, alpha=1.0,
                                          n_ingress=n_ing, seed=s + 1),
                   rate=1.0 + s, seed=s + 1, name=f"stream{s}")
        for s in range(args.streams)]
    drv = StreamDriver(eng, streams, max_batch=args.batch * 4,
                       batch_window=2.0, prompt_len=16,
                       refresh_every=(0 if eng.routing is not None
                                      else args.refresh_every))
    drv.run(max(args.requests // 8, args.batch))   # observe demand cold
    if eng.routing is None:
        pred = eng.refresh_placement()
        print(f"[serve] initial placement; predicted C(A)={pred:.2f}")
    st = drv.run(args.requests)
    drv.drain_refresh()
    print(f"[serve] streaming: {st.n_requests} requests in "
          f"{st.n_batches} batches ({st.distinct_batch_sizes} distinct "
          f"sizes), {st.requests_per_s:.0f} req/s, latency p50/p95/p99 "
          f"{st.p50_ms:.0f}/{st.p95_ms:.0f}/{st.p99_ms:.0f} ms")
    print(f"[serve] refreshes {st.refreshes_started} swaps {st.swaps} "
          f"(max stall {st.max_swap_stall_s*1e3:.1f} ms) duel churn "
          f"{st.placement_events}; placement v{eng.placement.version}")


def print_phase_table() -> None:
    """The program's phase table (``repro.tracecount``): one line per
    span (calls, mean and max wall milliseconds), then one per counter
    (traces of a jitted body, or units of work of the served path:
    batches, requests, lookup and prefill rows, copies and their
    bytes)."""
    summary = tracecount.summary()
    print(f"[serve] {'phase':<22} {'count':>7} {'mean ms':>10} "
          f"{'max ms':>10}")
    for name, row in sorted(summary["spans"].items()):
        print(f"[serve] {name:<22} {row['count']:>7} "
              f"{row['mean_ms']:>10.3f} {row['max_ms']:>10.3f}")
    print(f"[serve] {'counter':<22} {'count':>10}")
    for name, n in sorted(summary["counts"].items()):
        print(f"[serve] {name:<22} {n:>10}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--algo", default="cascade",
                    choices=["greedy", "localswap", "cascade"])
    ap.add_argument("--streaming", action="store_true",
                    help="async multi-stream driver + background refresh")
    ap.add_argument("--streams", type=int, default=4)
    ap.add_argument("--refresh-every", type=int, default=16,
                    help="background re-solve cadence, in batches")
    ap.add_argument("--netduel", action="store_true",
                    help="§5 online duels; churn triggers refreshes too")
    ap.add_argument("--warm-start", action="store_true",
                    help="§4 continuous-limit warm start on every "
                         "refresh (analytic solve + Prop 4.2 band map + "
                         "bounded polish instead of the O(O·J) solver)")
    ap.add_argument("--warm-polish-iters", type=int, default=512,
                    help="LOCALSWAP polish window after the warm start")
    ap.add_argument("--scenario", default=None,
                    choices=sorted(scenarios_api.GENERATORS),
                    help="serve a generated general-graph network "
                         "through the on-path strategy plane instead "
                         "of the built-in 3-level hierarchy")
    ap.add_argument("--strategy", default="lce", choices=STRATEGIES,
                    help="on-path routing strategy (with --scenario)")
    ap.add_argument("--cache-budget", type=int, default=64,
                    help="total cache slots split over the graph by "
                         "degree centrality (with --scenario)")
    ap.add_argument("--ingress", type=int, default=4,
                    help="number of ingress nodes (with --scenario)")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_smoke_config(args.arch)
    cat = catalog_api.embedding_catalog(n=1000, dim=32, seed=0)
    if args.scenario:
        sc = scenarios_api.scenario(args.scenario,
                                    cache_budget=args.cache_budget,
                                    placement="degree",
                                    n_ingress=args.ingress, seed=0)
        dem = demand_api.zipf(cat, alpha=1.0,
                              n_ingress=sc.net.n_ingress, seed=1)
        ecfg = EngineConfig(algo=args.algo, strategy=args.strategy)
        # the fused simcache is single-ingress; the strategy plane
        # serves the custom net, so no calibrate() here
        eng = build_engine(cfg, ecfg, cat, net=sc.net)
        print(f"[serve] scenario {args.scenario}: "
              f"{sc.graph.n_nodes} nodes, {sc.net.n_caches} caches "
              f"({sc.net.total_slots} slots), "
              f"{sc.net.n_ingress} ingress, strategy {args.strategy}")
    else:
        dem = demand_api.zipf(cat, alpha=1.0, seed=1)
        ecfg = EngineConfig(algo=args.algo, netduel=args.netduel,
                            refresh_on_promotion=args.netduel,
                            warm_start=args.warm_start,
                            warm_polish_iters=args.warm_polish_iters)
        eng = build_engine(cfg, ecfg, cat)
        eng.calibrate(jnp.zeros((args.batch, 16), jnp.int32))

    if args.streaming:
        run_streaming(eng, cat, args)
    else:
        run_batch_loop(eng, cfg, dem, args)
    s = eng.stats
    print(f"[serve] {s.n_requests} requests, hit-rate {s.hit_rate:.1%}, "
          f"mean cost {s.mean_cost:.2f} ms, model batches {s.model_calls}")
    print_phase_table()


if __name__ == "__main__":
    main()
