"""Streaming serving-engine tests: batch bucketing (retrace regression +
masked-padding equivalence), the double-buffered placement swap (async
background solve differential vs the synchronous path, and an explicit
pre/post-swap replay), and the multi-stream driver.

The 8-way variants ride scripts/ci.sh pass 2
(--xla_force_host_platform_device_count=8), like the other mesh suites.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracecount
from repro.configs.registry import get_smoke_config
from repro.core import catalog as catalog_api
from repro.core import demand as demand_api
from repro.launch.mesh import make_mesh
from repro.models import model as model_api
from repro.serve import (EngineConfig, SimCacheEngine, StreamDriver,
                         StreamSpec, bucket_size)


def make_engine(n_objects=300, netduel=True, bucket=True, sharded=False,
                mesh=None, **ecfg_kw):
    cfg = dataclasses.replace(get_smoke_config("granite-3-2b"),
                              n_layers=2, d_model=64, n_heads=4,
                              n_kv_heads=2, head_dim=16, d_ff=128,
                              vocab=256)
    params = model_api.init_params(cfg, 0)
    cat = catalog_api.embedding_catalog(n=n_objects, dim=16, seed=1)
    ecfg = EngineConfig(k_device=8, k_pod=12, k_global=16,
                        h_ici=1.0, h_dcn=10.0, h_model=100.0,
                        metric="l2", algo="greedy", netduel=netduel,
                        duel_window=64, duel_arm_prob=0.5, duel_seed=0,
                        bucket=bucket, sharded=sharded, **ecfg_kw)
    eng = SimCacheEngine(cfg, params, ecfg, cat.coords, mesh=mesh)
    return eng, cfg, cat


def mixed_batches(cat, cfg, sizes, seed=0):
    """One fixed request trace with the given per-batch sizes."""
    rng = np.random.default_rng(seed)
    dem = demand_api.zipf(cat, alpha=1.1, seed=3)
    batches = []
    for k in sizes:
        ids, _ = dem.sample(k, rng)
        prompts = jnp.asarray(
            rng.integers(0, cfg.vocab, (k, 8)).astype(np.int32))
        batches.append((ids, prompts))
    return batches


def accounting(eng):
    """The full serving/duel accounting a differential run must pin."""
    acct = {"n_hits": eng.stats.n_hits,
            "n_requests": eng.stats.n_requests,
            "model_calls": eng.stats.model_calls,
            "total_cost": eng.stats.total_cost,
            "total_approx_cost": eng.stats.total_approx_cost,
            "placement_events": eng.placement_events}
    if eng.duel is not None:
        acct["n_promotions"] = eng.duel.n_promotions
        acct["duel_served_cost"] = eng.duel.served_cost
        acct["duel_t"] = eng.duel.t
        acct["duel_slots"] = tuple(int(s) for s in eng.duel.slots_np)
    return acct


# ===================================================================
# bucketing
# ===================================================================
def test_bucket_size():
    assert bucket_size(1) == 8
    assert bucket_size(8) == 8
    assert bucket_size(9) == 16
    assert bucket_size(64) == 64
    assert bucket_size(700) == 1024
    assert bucket_size(3, lo=1) == 4


def test_bucketed_matches_unbucketed_exactly():
    """The masked-padding contract end to end: a mixed-batch-size trace
    served through the bucketed path produces bit-identical accounting —
    hits, costs, duel trajectory, promotion churn — to the unbucketed
    engine. Padding rows never leak into stats, counts, or the duel."""
    sizes = [1, 7, 16, 9, 33, 5, 16, 2, 31]
    accts = {}
    for bucket in (True, False):
        eng, cfg, cat = make_engine(bucket=bucket)
        batches = mixed_batches(cat, cfg, [16] * 4 + sizes)
        for ids, prompts in batches[:4]:          # cold
            eng.serve(ids, prompts)
        eng.refresh_placement()
        for ids, prompts in batches[4:]:
            eng.serve(ids, prompts)
        accts[bucket] = accounting(eng)
        accts[bucket]["counts"] = eng.counts.copy().tobytes()
    assert accts[True] == accts[False]


def test_retrace_regression_one_compile_per_bucket():
    """Serving batch sizes {1, 7, 64, 700} buckets to {8, 64, 1024}: the
    fused lookup and the duel scan must each compile at most once per
    bucket (3), not once per batch size (4) — and a second pass over the
    same sizes must add no traces at all."""
    eng, cfg, cat = make_engine()
    sizes = [1, 7, 64, 700]
    assert {bucket_size(s) for s in sizes} == {8, 64, 1024}
    warm = mixed_batches(cat, cfg, [16] * 4, seed=9)
    for ids, prompts in warm:
        eng.serve(ids, prompts)
    eng.refresh_placement()
    batches = mixed_batches(cat, cfg, sizes + sizes, seed=1)
    with tracecount.snapshot() as s:
        for ids, prompts in batches[:4]:
            eng.serve(ids, prompts)
        assert s.delta("fused_lookup") <= 3, \
            "fused lookup retraced beyond one compile per bucket"
        assert s.delta("duel_scan") <= 3, \
            "duel scan retraced beyond one compile per bucket"
        # steady state: the same sizes again compile nothing new
        lookups0, duels0 = s.delta("fused_lookup"), s.delta("duel_scan")
        for ids, prompts in batches[4:]:
            eng.serve(ids, prompts)
        assert s.delta("fused_lookup") == lookups0
        assert s.delta("duel_scan") == duels0


def test_unbucketed_retraces_per_batch_size():
    """The inverse pin: without bucketing, every distinct batch size is
    its own compile of the fused lookup — the pathology the bucketed
    path removes (and serving_bench.py quantifies)."""
    eng, cfg, cat = make_engine(netduel=False, bucket=False)
    for ids, prompts in mixed_batches(cat, cfg, [16] * 2, seed=9):
        eng.serve(ids, prompts)
    eng.refresh_placement()
    # the jit cache is process-global (keyed on shape), so these sizes
    # must not appear in any other test in this module
    sizes = [10, 11, 13, 14]
    with tracecount.snapshot() as s:
        for ids, prompts in mixed_batches(cat, cfg, sizes, seed=1):
            eng.serve(ids, prompts)
        assert s.delta("fused_lookup") == len(sizes)


# ===================================================================
# double-buffered placement: the atomic swap
# ===================================================================
def _swap_differential(sharded=False, mesh=None, **ecfg_kw):
    """Serve a stream across a mid-stream background refresh + atomic
    swap (run A); then replay the same requests against the pre- and
    post-swap placements explicitly (run B: synchronous solve installed
    at the same batch boundary; the solve itself must match A's
    background solve bit-for-bit). Accounting must agree exactly.
    ``ecfg_kw`` forwards EngineConfig overrides to all three runs (the
    warm-start variants below swap the solver for the §4 continuous
    pipeline — the differential contract is solver-independent)."""
    sizes = [16, 9, 16, 23, 16, 11, 16, 16, 7, 16]
    swap_after = 5                       # solve after batch 4, swap at 5

    # ---- run A: streamed, background solve, atomic swap
    eng_a, cfg, cat = make_engine(sharded=sharded, mesh=mesh, **ecfg_kw)
    batches = mixed_batches(cat, cfg, [16] * 4 + sizes)
    for ids, prompts in batches[:4]:
        eng_a.serve(ids, prompts)
    eng_a.refresh_placement()
    v0 = eng_a.placement.version
    traj_a = []
    for b, (ids, prompts) in enumerate(batches[4:]):
        if b == swap_after - 1:
            assert eng_a.request_refresh()
            assert eng_a.refresh_in_flight
            assert not eng_a.request_refresh()   # one in flight at a time
        eng_a.serve(ids, prompts)                # old placement serves
        if b == swap_after - 1:
            assert eng_a.wait_refresh(timeout=120)
            assert eng_a.poll_refresh()          # the atomic swap
            assert not eng_a.refresh_in_flight
        else:
            assert not eng_a.poll_refresh()
        traj_a.append(accounting(eng_a))
    assert eng_a.placement.version > v0
    slots_post = np.asarray(eng_a.placement.slots).copy()

    # ---- run B: same trace, *synchronous* solve at the same boundary
    eng_b, _, _ = make_engine(sharded=sharded, mesh=mesh, **ecfg_kw)
    for ids, prompts in batches[:4]:
        eng_b.serve(ids, prompts)
    eng_b.refresh_placement()
    traj_b = []
    pending = None
    for b, (ids, prompts) in enumerate(batches[4:]):
        if b == swap_after - 1:
            # snapshot + solve at A's request point (before this batch)
            inst = eng_b.observed_instance()
            pending = eng_b._solve(inst, eng_b.ecfg.algo,
                                   eng_b.ecfg.device_placement)[0], inst
        eng_b.serve(ids, prompts)
        if b == swap_after - 1:
            slots_b, inst = pending
            # background solve == synchronous solve on the same snapshot
            np.testing.assert_array_equal(slots_b, slots_post)
            eng_b._install(slots_b, inst)
        traj_b.append(accounting(eng_b))
    assert traj_a == traj_b

    # ---- run C: explicit replay against the captured post-swap
    # placement (no solver at all — the placement is installed verbatim)
    eng_c, _, _ = make_engine(sharded=sharded, mesh=mesh, **ecfg_kw)
    for ids, prompts in batches[:4]:
        eng_c.serve(ids, prompts)
    eng_c.refresh_placement()
    for b, (ids, prompts) in enumerate(batches[4:]):
        eng_c.serve(ids, prompts)
        if b == swap_after - 1:
            eng_c._install(slots_post, eng_c.observed_instance())
    assert accounting(eng_c) == traj_a[-1]


def test_atomic_swap_differential():
    _swap_differential()


@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs 8 devices (ci.sh pass 2)")
def test_atomic_swap_differential_8way():
    mesh = make_mesh((8,), ("data",))
    _swap_differential(sharded=True, mesh=mesh)


def test_atomic_swap_differential_warmstart():
    """Warm-started background refresh (EngineConfig.warm_start: the §4
    analytic solve + Prop 4.2 band map + bounded polish) swapped in by
    poll_refresh is serving-equivalent to the synchronous warm-start
    solve at the same batch boundary — the warm path is deterministic,
    so the whole mid-swap differential holds bit-for-bit."""
    _swap_differential(warm_start=True, warm_polish_iters=128)


@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs 8 devices (ci.sh pass 2)")
def test_atomic_swap_differential_warmstart_8way():
    mesh = make_mesh((8,), ("data",))
    _swap_differential(sharded=True, mesh=mesh, warm_start=True,
                       warm_polish_iters=128)


def test_refresh_in_flight_flag_and_versioning():
    eng, cfg, cat = make_engine(netduel=False)
    for ids, prompts in mixed_batches(cat, cfg, [16] * 4):
        eng.serve(ids, prompts)
    assert eng.placement.version == 0 and eng.simcache is None
    eng.refresh_placement()
    assert eng.placement.version == 1
    assert not eng.refresh_in_flight
    assert not eng.poll_refresh()            # nothing pending
    assert eng.request_refresh()
    assert eng.wait_refresh(timeout=120)
    assert eng.refresh_in_flight             # solved but not yet swapped
    assert eng.poll_refresh()
    assert eng.placement.version == 2
    assert eng.refresh_count == 2 and eng.swap_count == 1
    assert eng.max_swap_stall_s > 0.0


# ===================================================================
# multi-stream driver
# ===================================================================
def _streams(cat, n=3):
    rates = [5.0, 9.0, 2.0]
    return [StreamSpec(demand=demand_api.zipf(cat, alpha=1.1, seed=s + 1),
                       rate=rates[s % len(rates)], seed=s + 1,
                       name=f"user{s}") for s in range(n)]


def test_stream_driver_conserves_requests_and_versions():
    eng, cfg, cat = make_engine(refresh_on_promotion=True)
    drv = StreamDriver(eng, _streams(cat), max_batch=64, batch_window=3.0)
    st_cold = drv.run(100)
    assert st_cold.n_requests == 100
    eng.refresh_placement()
    st = drv.run(400)
    drv.drain_refresh()
    assert st.n_requests == 400
    assert sum(st.batch_sizes) == 400
    assert len(st.batch_latencies_ms) == st.n_batches
    assert st.distinct_batch_sizes > 1       # arrival-driven mixed sizes
    # versions observed by the serving loop never go backwards
    assert all(b >= a for a, b in zip(st.versions, st.versions[1:]))
    assert eng.stats.n_requests == 500


def test_stream_driver_is_deterministic_in_accounting():
    """Two identically seeded driver runs produce identical request
    traces and identical serving accounting (wall-clock latencies may
    differ; the accounting may not)."""
    accts = []
    for _ in range(2):
        eng, cfg, cat = make_engine()
        drv = StreamDriver(eng, _streams(cat), max_batch=32,
                           batch_window=2.0)
        drv.run(80)
        eng.refresh_placement()
        st = drv.run(200)
        accts.append((accounting(eng), tuple(st.batch_sizes)))
    assert accts[0] == accts[1]


def test_stream_driver_refresh_cadence():
    """refresh_every triggers background solves on a fixed cadence; all
    of them eventually swap in and serving never observes a stall longer
    than the per-batch budget by construction of the poll point."""
    eng, cfg, cat = make_engine(netduel=False)
    drv = StreamDriver(eng, _streams(cat), max_batch=32,
                       batch_window=2.0, refresh_every=4)
    drv.run(64)
    eng.refresh_placement()
    st = drv.run(256)
    drv.drain_refresh()
    assert st.refreshes_started > 0
    assert eng.swap_count > 0
    assert eng.refresh_count >= eng.swap_count
    assert not eng.refresh_in_flight
    assert st.requests_per_s > 0 and st.p99_ms >= st.p50_ms >= 0


def test_stream_driver_stall_window_is_per_run():
    """Stall-window regression: DriverStats.max_swap_stall_s must be the
    max over the swaps of *that* run. The old code copied the engine's
    all-time max, so a second run with no swaps at all still reported
    the first run's stall as its own."""
    eng, cfg, cat = make_engine(netduel=False)
    drv = StreamDriver(eng, _streams(cat), max_batch=32,
                       batch_window=2.0, refresh_every=4)
    drv.run(64)
    eng.refresh_placement()
    st1 = drv.run(256)
    drv.drain_refresh()
    assert eng.swap_count > 0
    assert st1.max_swap_stall_s > 0.0        # this run did swap
    assert st1.max_swap_stall_s <= eng.max_swap_stall_s
    # second run: no refresh cadence → no swaps → no stall to report
    drv.refresh_every = 0
    st2 = drv.run(64)
    assert st2.swaps == 0
    assert st2.max_swap_stall_s == 0.0, \
        "a swap-free run must not report the engine's all-time stall"
    assert eng.max_swap_stall_s > 0.0        # the all-time max survives


def test_stream_driver_threads_ingress_ids():
    """Ingress-threading regression: a multi-ingress stream population
    must land each request in its own (ingress, object) demand cell.
    The old driver popped ``(t, obj, _ing)`` and dropped the ingress, so
    every request was accounted to ingress 0."""
    from repro.core.scenarios import scenario

    sc = scenario("isp", cache_budget=24, placement="degree",
                  n_ingress=3, seed=0)
    cfg = dataclasses.replace(get_smoke_config("granite-3-2b"),
                              n_layers=2, d_model=64, n_heads=4,
                              n_kv_heads=2, head_dim=16, d_ff=128,
                              vocab=256)
    params = model_api.init_params(cfg, 0)
    cat = catalog_api.embedding_catalog(n=200, dim=16, seed=1)
    ecfg = EngineConfig(metric="l2", strategy="sim-lru", netduel=False)
    eng = SimCacheEngine(cfg, params, ecfg, cat.coords, net=sc.net)
    specs = [StreamSpec(demand=demand_api.zipf(cat, alpha=1.0,
                                               n_ingress=3, seed=s + 1),
                        rate=4.0, seed=s + 1) for s in range(2)]
    drv = StreamDriver(eng, specs, max_batch=32, batch_window=2.0)
    st = drv.run(300)
    assert st.n_requests == 300
    assert eng.counts.shape == (3, 200)
    per_ingress = eng.counts.sum(axis=1)
    assert per_ingress.sum() == 300
    assert np.count_nonzero(per_ingress) == 3, \
        "multi-ingress demand collapsed into a single ingress row"


def test_stream_rate_validation():
    eng, cfg, cat = make_engine(netduel=False)
    with pytest.raises(ValueError):
        StreamDriver(eng, [StreamSpec(demand=demand_api.zipf(cat),
                                      rate=0.0)])
    with pytest.raises(ValueError):
        StreamDriver(eng, [])


# ===================================================================
# analytic refresh gate (EngineConfig.refresh_min_gain)
# ===================================================================
def test_refresh_gate_skips_stationary_triggers_on_drift():
    """The surrogate-gated control plane: under stationary demand the
    analytic cost barely moves between snapshots, so cadence-triggered
    refresh requests are skipped (no device solve); switching the
    stream population to a flatter demand moves the predicted cost past
    the gate and the background solve fires again."""
    eng, cfg, cat = make_engine(netduel=False, refresh_min_gain=10.0)
    drv = StreamDriver(eng, _streams(cat), max_batch=32,
                       batch_window=2.0)
    drv.run(300)                             # warm the observed window
    eng.refresh_placement()                  # install + gate baseline
    drv.refresh_every = 4                    # cadence on from here
    st1 = drv.run(300)                       # stationary phase
    drv.drain_refresh()
    assert st1.refresh_skipped > 0
    assert st1.refresh_triggered == 0
    assert st1.refreshes_started == 0        # skipped ⇒ never started
    assert eng.swap_count == 0               # and nothing ever swapped
    # drift: replace the zipf population with uniform demand — the
    # observed window flattens, the predicted cost climbs past the gate
    drv.set_streams([StreamSpec(demand=demand_api.uniform(cat),
                                rate=5.0, seed=99)])
    st2 = drv.run(600)
    drv.drain_refresh()
    assert st2.refresh_triggered > 0
    assert st2.refreshes_started == st2.refresh_triggered
    assert eng.swap_count > 0                # the drift solve swapped in
    # engine-level counters aggregate both phases
    assert eng.stats.refresh_skipped >= st1.refresh_skipped
    assert eng.stats.refresh_triggered == st2.refresh_triggered


def test_refresh_gate_off_by_default():
    """refresh_min_gain = 0 keeps the old behavior bit-for-bit: every
    cadence request starts a solve, nothing is skipped, and no
    surrogate is ever evaluated on the request path."""
    eng, cfg, cat = make_engine(netduel=False)
    assert eng.ecfg.refresh_min_gain == 0.0
    drv = StreamDriver(eng, _streams(cat), max_batch=32,
                       batch_window=2.0, refresh_every=4)
    drv.run(64)
    eng.refresh_placement()
    st = drv.run(256)
    drv.drain_refresh()
    assert st.refreshes_started > 0
    assert st.refresh_skipped == 0 and st.refresh_triggered == 0
    assert eng._surrogate_baseline is None


def test_refresh_gate_no_serving_cost_regression():
    """Skipping solves must not cost serving quality: on the same
    stationary trace, the gated engine's mean per-request cost stays
    within 5% of the always-refresh engine's (their placements solve
    the same converging demand window, so skipped solves were
    redundant)."""
    costs = {}
    for gain in (0.0, 10.0):
        eng, cfg, cat = make_engine(netduel=False, refresh_min_gain=gain)
        drv = StreamDriver(eng, _streams(cat), max_batch=32,
                           batch_window=2.0, refresh_every=4)
        drv.run(300)
        eng.refresh_placement()
        drv.run(500)
        drv.drain_refresh()
        costs[gain] = eng.stats.mean_cost
    assert costs[10.0] <= costs[0.0] * 1.05, \
        f"gated serving cost {costs[10.0]:.3f} regressed vs " \
        f"always-refresh {costs[0.0]:.3f}"


# ===================================================================
# bounded latency window
# ===================================================================
def test_latency_ring_is_bounded_with_correct_percentiles():
    """The unbounded-list leak fix: ServeStats / DriverStats keep the
    newest LATENCY_WINDOW batch latencies only, and the percentiles are
    computed over exactly that window (a long run's early samples age
    out instead of accumulating forever)."""
    from repro.serve.engine import LATENCY_WINDOW, ServeStats
    from repro.serve.stream import DriverStats

    for stats in (ServeStats(), DriverStats()):
        ring = stats.batch_latencies_ms
        assert ring.maxlen == LATENCY_WINDOW
        n_extra = 5000
        for v in range(LATENCY_WINDOW + n_extra):   # a very long run
            ring.append(float(v))
        assert len(ring) == LATENCY_WINDOW          # memory stays O(1)
        # the window holds [n_extra, LATENCY_WINDOW + n_extra): the
        # percentiles must reflect the survivors, not the aged-out head
        assert stats.latency_percentile(0) == float(n_extra)
        assert stats.p50_ms == pytest.approx(
            n_extra + (LATENCY_WINDOW - 1) / 2.0)
        assert stats.latency_percentile(100) \
            == float(LATENCY_WINDOW + n_extra - 1)
        assert stats.p99_ms <= stats.latency_percentile(100)


def test_latency_window_served_engine_appends_bounded():
    """End to end: every served batch appends one latency sample into
    the bounded ring (same count as before the fix on short runs)."""
    eng, cfg, cat = make_engine(netduel=False)
    batches = mixed_batches(cat, cfg, [16] * 6)
    for ids, prompts in batches:
        eng.serve(ids, prompts)
    assert len(eng.stats.batch_latencies_ms) == 6
    assert eng.stats.p99_ms >= eng.stats.p50_ms > 0.0
