"""End-to-end serving-engine tests: the paper's cache network in front of
a real (tiny) model on CPU."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_smoke_config
from repro.core import catalog as catalog_api
from repro.core import demand as demand_api
from repro.launch.mesh import make_mesh
from repro.models import model as model_api
from repro.serve import EngineConfig, SimCacheEngine


def make_engine(k=(16, 24, 32), algo="cascade", sharded=False, mesh=None):
    cfg = dataclasses.replace(get_smoke_config("granite-3-2b"),
                              n_layers=2, d_model=64, n_heads=4,
                              n_kv_heads=2, head_dim=16, d_ff=128, vocab=256)
    params = model_api.init_params(cfg, 0)
    cat = catalog_api.embedding_catalog(n=400, dim=16, seed=1)
    ecfg = EngineConfig(k_device=k[0], k_pod=k[1], k_global=k[2],
                        h_ici=1.0, h_dcn=10.0, h_model=100.0,
                        metric="l2", algo=algo, sharded=sharded)
    eng = SimCacheEngine(cfg, params, ecfg, cat.coords, mesh=mesh)
    return eng, cfg, cat


def serve_trace(eng, cfg, cat, n_batches=12, batch=16, seed=0):
    rng = np.random.default_rng(seed)
    dem = demand_api.zipf(cat, alpha=1.1, seed=3)
    for _ in range(n_batches):
        ids, _ = dem.sample(batch, rng)
        prompts = jnp.asarray(
            rng.integers(0, cfg.vocab, (batch, 8)).astype(np.int32))
        eng.serve(ids, prompts)
    return eng.stats


def test_engine_cold_then_cached():
    eng, cfg, cat = make_engine()
    stats = serve_trace(eng, cfg, cat, n_batches=4)
    assert stats.hit_rate == 0.0                 # no placement yet
    pred = eng.refresh_placement()
    assert pred > 0
    eng.stats = type(eng.stats)()                # count only warm phase
    stats = serve_trace(eng, cfg, cat, n_batches=8, seed=1)
    assert stats.hit_rate > 0.5, stats.hit_rate  # cache absorbs the head
    assert stats.model_calls < 10


def test_engine_cost_drops_with_placement():
    """Mean serving cost after placement must beat the all-repository
    baseline (= caching gain > 0, eq. (4) realized end-to-end)."""
    eng, cfg, cat = make_engine(algo="greedy")
    serve_trace(eng, cfg, cat, n_batches=4)
    eng.refresh_placement()
    eng.stats = type(eng.stats)()                # reset counters
    stats = serve_trace(eng, cfg, cat, n_batches=10, seed=2)
    assert stats.mean_cost < eng.ecfg.h_model * 0.7


def test_engine_calibration_sets_cost_units():
    eng, cfg, cat = make_engine()
    ms = eng.calibrate(jnp.zeros((4, 8), jnp.int32))
    assert ms > 0
    assert eng.ecfg.h_model == ms
    assert eng.ecfg.h_ici < eng.ecfg.h_dcn < eng.ecfg.h_model


def test_calibrate_rebuilds_simcache():
    """Staleness regression: calibrate() used to rebuild the topology but
    leave the already-built simcache (and an armed duel plane) serving
    the old h costs. It must re-install the held allocation against the
    measured costs and re-arm the duel in the new cost units."""
    eng, cfg, cat = make_engine(algo="greedy")
    eng.ecfg.netduel = True
    eng.ecfg.duel_window = 64
    serve_trace(eng, cfg, cat, n_batches=4)
    eng.refresh_placement()
    assert eng.duel is not None
    keys_before = [np.asarray(lv.keys).copy() for lv in eng.simcache.levels]
    v0 = eng.placement.version
    duel_before = eng.duel
    ms = eng.calibrate(jnp.zeros((4, 8), jnp.int32))
    # runtime network now prices the calibrated costs, not the stale ones
    assert [lv.h for lv in eng.simcache.levels] == \
        [0.0, eng.ecfg.h_ici, eng.ecfg.h_dcn]
    assert eng.simcache.h_repo == eng.ecfg.h_model == ms
    assert eng.placement.version > v0
    # same allocation, new prices: the stored keys are unchanged
    for a, lv in zip(keys_before, eng.simcache.levels):
        np.testing.assert_array_equal(a, np.asarray(lv.keys))
    # the duel plane was re-armed (old one was priced in stale units)
    assert eng.duel is not duel_before and eng.duel.t == 0
    # and serving still works end to end in the new units
    stats = serve_trace(eng, cfg, cat, n_batches=4, seed=7)
    assert stats.n_requests == 8 * 16


def test_engine_sharded_data_plane_matches_fused():
    """EngineConfig.sharded + a mesh routes lookups through the
    mesh-sharded fused path; served stats must match the single-device
    fused engine bit-for-bit on the same trace (here a trivial 1-device
    mesh — the 8-way equivalence is covered by test_sharded_lookup)."""
    mesh = make_mesh((1,), ("data",))
    eng_f, cfg, cat = make_engine(algo="greedy")
    eng_s, _, _ = make_engine(algo="greedy", sharded=True, mesh=mesh)
    assert eng_s.lookup_shards is not None
    for eng in (eng_f, eng_s):
        serve_trace(eng, cfg, cat, n_batches=4)
        eng.refresh_placement()
        eng.stats = type(eng.stats)()
    assert eng_s.simcache.sharded and eng_s.simcache.mesh is mesh
    sf = serve_trace(eng_f, cfg, cat, n_batches=6, seed=5)
    ss = serve_trace(eng_s, cfg, cat, n_batches=6, seed=5)
    assert sf.n_hits == ss.n_hits
    assert sf.model_calls == ss.model_calls
    assert sf.total_cost == ss.total_cost
    assert sf.total_approx_cost == ss.total_approx_cost


def test_engine_sharded_requires_mesh():
    with np.testing.assert_raises(ValueError):
        make_engine(sharded=True, mesh=None)


def test_placement_algorithms_rank_sanely():
    """cascade ≤ greedy in predicted cost (Remark 1)."""
    preds = {}
    for algo in ("greedy", "cascade"):
        eng, cfg, cat = make_engine(algo=algo)
        serve_trace(eng, cfg, cat, n_batches=6)
        preds[algo] = eng.refresh_placement(algo)
    assert preds["cascade"] <= preds["greedy"] + 1e-9


def test_observed_placement_tail_matches():
    """Demand-floor regression: the observed window keeps never-requested
    objects at an *exact-zero* rate (no ``+ 1e-9`` floor), so once the
    real gains are exhausted both the f64 host solver and the f32 device
    solver stop at the same pick and leave the same slots empty — the
    tail-fill ambiguity of the floored demand is gone."""
    from repro.core.objective import DeviceInstance
    from repro.core.placement import device_greedy, greedy

    eng, cfg, cat = make_engine(algo="greedy")
    # a head-only window: 12 requested objects with well-separated
    # counts against 72 slots forces the zero-gain tail regime
    eng.counts[0, :12] = 2.0 ** np.arange(12)
    inst = eng.observed_instance()
    assert np.all(inst.lam[0, 12:] == 0.0)
    host = greedy(inst)
    dinst = DeviceInstance.from_instance(inst, materialize_ca=False)
    for scan in (True, False):
        np.testing.assert_array_equal(
            host, device_greedy(dinst, scan=scan))
    assert (host < 0).sum() > 0          # the tail regime was entered
    # end-to-end: both engine paths produce the same predicted cost and
    # the same runtime placement
    pred_dev = eng.refresh_placement(device=True)
    keys_dev = [np.asarray(lv.keys).copy() for lv in eng.simcache.levels]
    pred_host = eng.refresh_placement(device=False)
    keys_host = [np.asarray(lv.keys) for lv in eng.simcache.levels]
    for a, b in zip(keys_dev, keys_host):
        np.testing.assert_array_equal(a, b)
    # predicted C(A) agrees to cost-scale noise (the host MXU-form C_a
    # carries ~sqrt(eps)·|x| self-distance noise on its diagonal that the
    # device's shape-stable form does not)
    assert abs(pred_dev - pred_host) < 1e-3 * eng.ecfg.h_model


def test_engine_counts_duplicates_in_batch():
    """Demand-undercount regression: a batch containing the same object
    k times must add k to its count. The old fancy-indexed
    ``counts[ids] += 1`` collapsed duplicates to a single increment —
    undercounting exactly the hot objects of a skewed trace — so the
    batched counts must match a sequential one-request-at-a-time replay."""
    eng, cfg, cat = make_engine()
    rng = np.random.default_rng(0)
    # duplicate-heavy batches: ids drawn from a tiny head so most
    # batches repeat objects many times
    batches = [rng.integers(0, 5, size=32) for _ in range(6)]
    for ids in batches:
        prompts = jnp.asarray(
            rng.integers(0, cfg.vocab, (len(ids), 8)).astype(np.int32))
        eng.serve(ids, prompts)
    expected = np.zeros(cat.n, dtype=np.float64)
    for ids in batches:                  # sequential replay ground truth
        for o in ids:
            expected[int(o)] += 1.0
    np.testing.assert_array_equal(eng.counts[0], expected)
    assert eng.counts[0, :5].sum() == 6 * 32


def test_engine_counts_thread_ingress_ids():
    """Multi-ingress accounting: serve() with ``ingress_ids`` lands each
    request in its own (ingress, object) cell, and observed_instance
    exposes the full per-ingress matrix instead of a collapsed
    ``lam[None, :]`` copy of row 0."""
    from repro.core.scenarios import scenario

    sc = scenario("isp", cache_budget=24, placement="degree", n_ingress=4,
                  seed=0)
    cfg = dataclasses.replace(get_smoke_config("granite-3-2b"),
                              n_layers=2, d_model=64, n_heads=4,
                              n_kv_heads=2, head_dim=16, d_ff=128, vocab=256)
    params = model_api.init_params(cfg, 0)
    cat = catalog_api.embedding_catalog(n=100, dim=8, seed=1)
    ecfg = EngineConfig(metric="l2", strategy="lce")
    eng = SimCacheEngine(cfg, params, ecfg, cat.coords, net=sc.net)
    assert eng.counts.shape == (4, 100)
    rng = np.random.default_rng(1)
    ids = rng.integers(0, 100, size=40)
    ings = rng.integers(0, 4, size=40)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab, (40, 8)).astype(np.int32))
    eng.serve(ids, prompts, ingress_ids=ings)
    expected = np.zeros((4, 100))
    np.add.at(expected, (ings, ids), 1.0)
    np.testing.assert_array_equal(eng.counts, expected)
    inst = eng.observed_instance()
    assert inst.lam.shape == (4, 100)
    np.testing.assert_allclose(inst.lam, expected / expected.sum())


def test_engine_strategy_plane_serves_end_to_end():
    """EngineConfig.strategy on a general-graph net: every request is
    answered, hits never exceed h_repo, occupancy respects capacities,
    and repeated traffic on a small head warms the path caches."""
    from repro.core.scenarios import scenario

    sc = scenario("scale_free", cache_budget=32, placement="betweenness",
                  n_ingress=4, seed=1)
    cfg = dataclasses.replace(get_smoke_config("granite-3-2b"),
                              n_layers=2, d_model=64, n_heads=4,
                              n_kv_heads=2, head_dim=16, d_ff=128, vocab=256)
    params = model_api.init_params(cfg, 0)
    cat = catalog_api.embedding_catalog(n=100, dim=8, seed=1)
    ecfg = EngineConfig(metric="l2", strategy="lce")
    eng = SimCacheEngine(cfg, params, ecfg, cat.coords, net=sc.net)
    assert eng.routing is not None and eng.simcache is None
    rng = np.random.default_rng(2)
    for _ in range(8):
        ids = rng.integers(0, 10, size=16)       # tiny head: re-requests
        ings = rng.integers(0, 4, size=16)
        prompts = jnp.asarray(
            rng.integers(0, cfg.vocab, (16, 8)).astype(np.int32))
        out, stats = eng.serve(ids, prompts, ingress_ids=ings)
        assert all(r is not None for r in out)   # every request answered
    assert (eng.routing.occupancy() <= sc.net.capacities).all()
    assert stats.n_hits > 0                      # warm head produced hits
    assert stats.mean_cost <= float(sc.net.h_repo.max()) + 1e-9


def test_engine_cold_observed_instance_is_uniform():
    eng, cfg, cat = make_engine()
    inst = eng.observed_instance()
    assert inst.lam.sum() == pytest.approx(1.0)
    assert np.all(inst.lam == inst.lam[0, 0])


def test_engine_netduel_online_plane():
    """EngineConfig.netduel: the duel plane observes every served batch
    (priced by the data-plane lookup costs), promotions rebuild the
    runtime cache, and the engine keeps serving correctly throughout."""
    eng, cfg, cat = make_engine(algo="greedy")
    eng.ecfg.netduel = True
    eng.ecfg.duel_window = 64
    eng.ecfg.duel_arm_prob = 0.5
    serve_trace(eng, cfg, cat, n_batches=4)
    eng.refresh_placement()
    assert eng.duel is not None
    assert eng.duel.t == 0
    stats = serve_trace(eng, cfg, cat, n_batches=16, seed=2)
    assert eng.duel.t == 16 * 16                 # every batch observed
    assert eng.duel.n_promotions > 0
    assert eng.placement_events > 0              # churn rebuilt the cache
    assert stats.hit_rate > 0.3                  # still serving sanely
    # the runtime cache serves exactly the duel's current placement
    stored = np.sort(np.concatenate(
        [np.asarray(lv.values)[np.asarray(lv.values) >= 0]
         for lv in eng.simcache.levels]))
    assert stored.size == eng.duel.slots_np.size


def test_build_engine_serves_and_rejects_encdec():
    """The launcher's shared construction: weights from the seed, the
    engine over the catalog; an encoder-decoder arch is refused."""
    from repro.launch.serve import build_engine
    cfg = dataclasses.replace(get_smoke_config("granite-3-2b"),
                              n_layers=2, d_model=64, n_heads=4,
                              n_kv_heads=2, head_dim=16, d_ff=128, vocab=256)
    cat = catalog_api.embedding_catalog(n=200, dim=8, seed=2)
    ecfg = EngineConfig(k_device=4, k_pod=4, k_global=4)
    eng = build_engine(cfg, ecfg, cat, seed=3)
    out, stats = eng.serve(np.arange(4), jnp.zeros((4, 8), jnp.int32))
    assert stats.model_calls == 1 and all(o is not None for o in out)
    assert eng.prefill(jnp.zeros((2, 8), jnp.int32)).shape == (2, cfg.vocab)
    with pytest.raises(ValueError):
        build_engine(get_smoke_config("whisper-small"), ecfg, cat)


def test_compile_cache_honours_env_then_fixed_path(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and nothing else is set; without it
    the cache lives at the fixed <checkout>/.jax_cache."""
    import jax

    from repro.launch.serve import enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    assert enable_compile_cache(tmp_path) == str(tmp_path / "env")
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        fixed = str(tmp_path / ".jax_cache")
        assert enable_compile_cache(tmp_path) == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
