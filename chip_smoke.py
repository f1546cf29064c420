"""Bring-up smoke: the similarity-cache serving path at real size on a TPU.

Drives ``SimCacheEngine.serve`` once, end to end, in this one process:
granite-3-2b at its published widths (40 layers, d_model 2048, 32 heads
over 8 KV heads, d_ff 8192, vocab 49155, bf16 weights drawn from a seed)
behind a three-level cache of 4,096 + 16,384 + 65,536 keys over a
10⁶ × 128 embedding catalog. Phases, each printed with its wall and
compile seconds:

  a. calibrate   — the measured miss prefill sets the level costs;
  b. cold        — two batches of 64 Zipf requests with 16-token
                   prompts, all misses, prefilled at full width;
  c. refresh     — ``refresh_placement()`` with the §4 warm start;
  d. warm        — eight batches of 64 through the fused Pallas lookup;
                   every served (level, slot, payload) is checked
                   against a float64 NumPy scan of the installed keys.

``--four-chips`` runs the mesh-sharded key tensor instead: the same
phases on a four-device lookup mesh with ``EngineConfig.sharded``, and
every warm lookup is compared bit for bit with the single-device fused
lookup over the same placement.

  python chip_smoke.py
  python chip_smoke.py --four-chips

The last line of standard output is one JSON object naming the device.
Without a TPU, or when any phase fails, the script exits non-zero and
prints no such line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SEED = 0
N_CATALOG = 1_000_000
DIM = 128
LEVELS = dict(k_device=4096, k_pod=16384, k_global=65536)
BATCH = 64
PROMPT_LEN = 16
COLD_BATCHES = 2
WARM_BATCHES = 8
ZIPF_ALPHA = 1.0
F32_EPS = 2.0 ** -23


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling since ``reset``."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration

    def reset(self) -> float:
        s, self.seconds = self.seconds, 0.0
        return s


def phase(clock: CompileClock, name: str, fn, *args):
    """Run one phase; print its wall and compile seconds."""
    clock.reset()
    t0 = time.perf_counter()
    out = fn(*args)
    wall = time.perf_counter() - t0
    print(f"phase {name}: wall {wall:.3f} s, compile {clock.reset():.3f} s",
          flush=True)
    return out


def check_compiled(net, queries) -> None:
    """The lookup the engine serves with is a Mosaic kernel, not the
    Pallas interpreter or the jnp reference."""
    from repro.core.simcache import REPO_LEVEL
    from repro.kernels.knn import fused_lookup, sharded_fused_lookup
    kw = dict(metric=net.metric, gamma=net.gamma, h_repo=net.h_repo,
              repo_level=REPO_LEVEL, use_pallas=net.use_pallas)
    if net.sharded:
        lowered = sharded_fused_lookup.lower(
            queries, *net.sharded_layout(net.n_shards()), net.mesh,
            net.resolved_shard_axes(), **kw)
    else:
        lowered = fused_lookup.lower(queries, *net.fused_layout(), **kw)
    if "tpu_custom_call" not in lowered.as_text():
        raise RuntimeError("the fused lookup did not lower to a TPU kernel")


def host_scan(net, queries):
    """float64 eq. (1) under the l2 metric (γ = 1) over the installed
    keys: (level, slot, payload) of the best approximizer per query, the
    gap to the second best, and the near-tie tolerance of that gap. Keys
    are scanned in layout order and the repository last, ties to the
    first — the kernel's own order.

    The tolerance bounds the f32 error of the gap. The kernel's
    |q|² + |k|² − 2q·k form loses up to δ = 8·eps·(|q|² + |k|²) of d²,
    which moves d = √d² by at most min(√δ, δ/d); a gap takes the error of
    both its ends (the repository's cost is exact)."""
    import numpy as np
    keys, h_key, meta = (np.asarray(a) for a in net.fused_layout())
    q = np.asarray(queries, np.float64)
    k = keys.astype(np.float64)
    sq = (q * q).sum(1)[:, None] + (k * k).sum(1)[None, :]
    d = np.sqrt(np.maximum(sq - 2.0 * q @ k.T, 0.0))
    cost = np.where(meta[3][None, :] > 0, d + h_key[None, :], np.inf)
    delta = 8.0 * F32_EPS * sq
    err = np.minimum(np.sqrt(delta), delta / np.maximum(d, 1e-300))
    cost = np.concatenate([cost, np.full((len(q), 1), net.h_repo)], 1)
    err = np.concatenate([err, np.zeros((len(q), 1))], 1)
    rows = np.arange(len(q))
    best = np.argmin(cost, 1)
    c1, e1 = cost[rows, best], err[rows, best]
    cost[rows, best] = np.inf
    second = np.argmin(cost, 1)
    gap = cost[rows, second] - c1
    tol = e1 + err[rows, second]
    is_repo = best == keys.shape[0]
    kb = np.minimum(best, keys.shape[0] - 1)
    level = np.where(is_repo, -1, meta[0][kb])
    slot = np.where(is_repo, 0, meta[1][kb])
    payload = np.where(is_repo, -1, meta[2][kb])
    return level, slot, payload, gap, tol


def run(cfg, n_catalog: int, four_chips: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import catalog as catalog_api
    from repro.core import demand as demand_api
    from repro.launch.mesh import make_lookup_mesh
    from repro.launch.serve import build_engine
    from repro.serve import EngineConfig
    from repro.serve.engine import _pad_rows, bucket_size

    clock = CompileClock()
    print(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads} heads / {cfg.n_kv_heads} kv, d_ff {cfg.d_ff}, "
          f"vocab {cfg.vocab}, params {cfg.param_dtype}", flush=True)
    cat = phase(clock, "catalog", catalog_api.embedding_catalog,
                n_catalog, DIM, SEED)
    # warm_polish_iters=0: the LOCALSWAP polish carries (objects × slots)
    # best-two tables, 10⁶ × 86,016 f32 here, which no chip holds; the
    # analytic §4 placement is installed as solved
    ecfg = EngineConfig(**LEVELS, warm_start=True, warm_polish_iters=0,
                        sharded=four_chips)
    mesh = make_lookup_mesh(4) if four_chips else None
    eng = phase(clock, "engine", lambda: build_engine(
        cfg, ecfg, cat, seed=SEED, mesh=mesh))
    print(f"catalog {cat.n} x {cat.dim}, cached keys "
          f"{eng.net.total_slots}, mesh "
          f"{dict(mesh.shape) if mesh is not None else None}", flush=True)

    rng = np.random.default_rng(SEED)
    dem = demand_api.zipf(cat, alpha=ZIPF_ALPHA, seed=SEED + 1)

    def batch():
        ids, _ = dem.sample(BATCH, rng)
        toks = rng.integers(0, cfg.vocab, (BATCH, PROMPT_LEN))
        return ids, jnp.asarray(toks.astype(np.int32))

    h_model = phase(clock, "calibrate", eng.calibrate,
                    jnp.zeros((BATCH, PROMPT_LEN), jnp.int32))
    print(f"h_model {h_model:.3f} ms (one prefill of {BATCH} x "
          f"{PROMPT_LEN})", flush=True)

    def cold():
        for _ in range(COLD_BATCHES):
            ids, toks = batch()
            eng.serve(ids, toks)
        return toks

    toks = phase(clock, "cold", cold)
    if eng.stats.n_hits or eng.stats.model_calls != COLD_BATCHES:
        raise RuntimeError(f"cold batches: {eng.stats}")
    logits = np.asarray(eng.prefill(toks), np.float32)
    if not np.all(np.isfinite(logits)):
        raise RuntimeError("prefill logits are not finite")
    print(f"prefill logits finite, shape {logits.shape}, max |logit| "
          f"{np.abs(logits).max():.3f}", flush=True)

    pred = phase(clock, "refresh", eng.refresh_placement)
    print(f"placement v{eng.placement_version}: predicted C(A) "
          f"{pred:.6f}", flush=True)

    net = eng.simcache
    ref = dataclasses.replace(net, sharded=False, mesh=None,
                              shard_axes=None) if four_chips else None
    if (net.metric, net.gamma) != ("l2", 1.0):
        raise RuntimeError("the float64 check scans l2 costs with γ = 1")
    before = dataclasses.replace(eng.stats)
    mismatch, near, worst_gap, worst_tol = 0, 0, 0.0, 0.0

    def warm():
        nonlocal mismatch, near, worst_gap, worst_tol
        for b in range(WARM_BATCHES):
            ids, toks = batch()
            hits0 = eng.stats.n_hits
            eng.serve(ids, toks)
            q = _pad_rows(jnp.asarray(eng.coords[ids]),
                          bucket_size(len(ids), ecfg.min_bucket))
            if b == 0:
                check_compiled(net, q)
            res = net.lookup(q)
            hit = np.asarray(res.hit)[:len(ids)]
            if int(hit.sum()) != eng.stats.n_hits - hits0:
                raise RuntimeError("re-run lookup disagrees with serve")
            got = [np.asarray(a)[:len(ids)]
                   for a in (res.level, res.slot, res.payload)]
            if ref is not None:
                r = ref.lookup(q)
                for f in ("level", "slot", "payload", "cost",
                          "approx_cost"):
                    if not np.array_equal(np.asarray(getattr(res, f)),
                                          np.asarray(getattr(r, f))):
                        raise RuntimeError(
                            f"batch {b}: sharded {f} differs from fused")
                continue
            lvl, slot, pay, gap, tol = host_scan(net, eng.coords[ids])
            worst_tol = max(worst_tol, float(tol.max()))
            bad = ((got[0] != lvl) | (got[1] != slot) | (got[2] != pay))
            mismatch += int(bad.sum())
            near += int((bad & (gap < tol)).sum())
            if bad.any():
                worst_gap = max(worst_gap, float(gap[bad].max()))

    phase(clock, "warm", warm)
    n = eng.stats.n_requests - before.n_requests
    hits = eng.stats.n_hits - before.n_hits
    cost = eng.stats.total_cost - before.total_cost
    print(f"warm: {n} requests, hit rate {hits / n:.4f}, mean cost "
          f"{cost / n:.6f} ms, model batches "
          f"{eng.stats.model_calls - before.model_calls}", flush=True)
    if four_chips:
        print(f"sharded lookup over {net.n_shards()} shards bit-identical "
              f"to the single-device fused lookup on all "
              f"{WARM_BATCHES} warm batches", flush=True)
    else:
        print(f"lookup vs float64 scan: {mismatch} mismatches of {n}, "
              f"{near} at near-ties (gap below its f32 error bound, at "
              f"most {worst_tol:.6f}), largest mismatched gap "
              f"{worst_gap:.6f}", flush=True)
        if mismatch > near:
            raise RuntimeError("lookup mismatches beyond near-ties")
    if hits == 0:
        raise RuntimeError("no warm hits")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"peak HBM {stats.get('peak_bytes_in_use')} bytes of "
          f"{stats.get('bytes_limit')}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the mesh-sharded key tensor on four chips, "
                         "compared with the single-device fused lookup")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    print(f"device {dev.platform} {dev.device_kind} x {len(devices)}",
          flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform "
              f"{dev.platform!r}", file=sys.stderr)
        return 1
    if args.four_chips and len(devices) < 4:
        print(f"chip_smoke: --four-chips needs 4 devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 1

    sys.path.insert(0, str(ROOT / "src"))
    from repro.configs.registry import get_config
    from repro.launch.serve import enable_compile_cache
    print(f"compile cache {enable_compile_cache(ROOT)}", flush=True)
    cfg = dataclasses.replace(get_config("granite-3-2b"),
                              param_dtype="bfloat16")
    run(cfg, N_CATALOG, args.four_chips)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
