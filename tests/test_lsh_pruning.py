"""Differential suite for LSH / k-means candidate pruning in front of
the fused segmented-1-NN lookup (kernels/knn/lsh.py).

Three requirements, mirroring test_sharded_lookup.py's structure:

  * **recall** — at default table parameters the pruned lookup (no
    verification) finds the exact winner for ≥ 99% of queries drawn
    from the paper's Gaussian-grid and Zipf demands;
  * **exactness** — with ``verify=True`` the pruned path re-scans every
    query whose pruned cost reaches the un-scanned-h bound and must be
    **bit-identical** to the exact fused path (and to the looped
    per-level reference) on every covered configuration: both policies,
    all metrics, γ ≠ 1, empty levels, B = 1 and multi-tile batches,
    single-device and sharded;
  * **composition** — pruning only ever shrinks a shard's scan: the
    per-shard candidate mask must not disturb ``reduce_shard_minima``
    or the cross-shard tie-break order, and empty-level sentinels /
    shard padding must never be selected as candidates.

Staleness is *stricter* than the fused layout's documented
serve-stale-verbatim contract: a pruned lookup against mutated but not
invalidated ``levels`` must raise, not return stale candidates.

The 10⁶-key recall test is marked ``slow`` and gated on CI_FULL=1 — it
runs only in the nightly/full pass (scripts/ci.sh).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import assert_results_equal, make_net

from benchmarks.common import lookup_recall
from repro.core import catalog as catalog_api
from repro.core import demand as demand_api
from repro.core.simcache import REPO_LEVEL, CacheLevel, SimCacheNetwork
from repro.kernels.knn import (KMeansPolicy, SimHashPolicy, pad_to_shards,
                               pruned_fused_lookup, pruned_fused_lookup_ref,
                               sharded_pruned_fused_lookup_ref)
from repro.launch.mesh import make_mesh

EIGHT = jax.device_count() >= 8
FULL = bool(os.environ.get("CI_FULL"))

# probes both buckets of every 1-bit table → all valid keys are
# candidates; pruning becomes a pure re-indexing of the exact scan, the
# right instrument for deterministic tie-break tests
COVER_ALL = SimHashPolicy(n_tables=2, n_bits=1, n_probes=2)


# ------------------------------------------------------------- exactness
@pytest.mark.parametrize("prune", ["lsh", "kmeans"])
@pytest.mark.parametrize("metric,gamma", [("l2", 1.0), ("l1", 1.0),
                                          ("l2sq", 1.0), ("l2", 2.0)])
def test_pruned_verify_bit_identical(prune, metric, gamma):
    """verify=True must reproduce the exact fused path bit-for-bit (and
    the looped reference), whatever the candidate tables missed —
    covering B=1 and a 700-query multi-tile batch."""
    for seed, sizes, hs, h_repo, nq in [
        (0, [5, 9, 3], [0.0, 0.5, 1.0], 2.0, 23),
        (1, [17, 2, 31, 8], [0.0, 0.2, 0.7, 1.3], 3.0, 1),      # B=1
        (5, [200, 150, 250], [0.0, 0.4, 0.8], 2.5, 700),  # 3 query tiles
    ]:
        net, rng = make_net(seed, sizes, hs, h_repo, metric, gamma)
        q = jnp.asarray((rng.standard_normal((nq, 6)) * 2)
                        .astype(np.float32))
        res = net.lookup(q, prune=prune, verify=True)
        assert_results_equal(res, net._lookup_fused(q),
                             exact_cost=gamma == 1.0)
        assert_results_equal(res, net._lookup_looped(q),
                             exact_cost=gamma == 1.0)


@pytest.mark.parametrize("prune", ["lsh", "kmeans"])
def test_pruned_verify_bit_identical_sharded(prune):
    """Same contract through the mesh-sharded data plane (per-shard
    tables + fold_repo=False launches + untouched reduction)."""
    mesh = make_mesh((1,), ("data",))
    net, rng = make_net(1, [17, 2, 31, 8], [0.0, 0.2, 0.7, 1.3], 3.0)
    snet, _ = make_net(1, [17, 2, 31, 8], [0.0, 0.2, 0.7, 1.3], 3.0,
                       sharded=True, mesh=mesh)
    q = jnp.asarray((rng.standard_normal((23, 6)) * 2).astype(np.float32))
    res = snet.lookup(q, prune=prune, verify=True)
    assert_results_equal(res, net._lookup_fused(q))
    assert_results_equal(res, snet.lookup(q))


def test_pruned_full_coverage_equals_exact_without_verify():
    """A policy whose probes cover every bucket makes pruning a pure
    ascending re-indexing of the full scan: bit-identical even with
    verify=False, and the bound is +INF (nothing un-scanned)."""
    net, rng = make_net(2, [64, 64], [0.0, 1.0], 5.0,
                        candidate_policy=COVER_ALL)
    q = jnp.asarray((rng.standard_normal((23, 6)) * 2).astype(np.float32))
    assert_results_equal(net.lookup(q, prune="lsh"), net._lookup_fused(q))
    keys, h_key, meta = net.fused_layout()
    t = COVER_ALL.build(np.asarray(keys), np.asarray(meta)[3] > 0)
    *_, bound = pruned_fused_lookup_ref(q, keys, h_key, meta, t,
                                        cap_union=keys.shape[0],
                                        h_repo=5.0)
    assert float(bound) >= 1e38


# --------------------------------------------------------------- recall
@pytest.mark.parametrize("prune", ["lsh", "kmeans"])
@pytest.mark.parametrize("workload", ["gauss", "zipf"])
def test_recall_on_paper_demands(prune, workload):
    """Default table parameters reach recall ≥ 0.99 on queries drawn
    from the paper's Gaussian-grid (§6.1) and Zipf-embedding (§6.2)
    demand models."""
    rng = np.random.default_rng(7)
    if workload == "gauss":
        cat = catalog_api.grid(L=40)                     # 1600 objects
        dem = demand_api.gaussian_grid(cat, sigma=8.0)
        metric = "l1"
    else:
        cat = catalog_api.embedding_catalog(n=2000, dim=16, seed=3)
        dem = demand_api.zipf(cat, alpha=0.8, seed=4)
        metric = "l2"
    stored = rng.choice(cat.n, 600, replace=False)
    levels = [CacheLevel(
        keys=jnp.asarray(cat.coords[idx]),
        values=jnp.asarray(idx.astype(np.int32)), h=float(h))
        for idx, h in ((stored[:400], 0.0), (stored[400:], 0.5))]
    net = SimCacheNetwork(levels=levels, h_repo=1e9, metric=metric)
    obj, _ = dem.sample(512, rng)
    q = jnp.asarray(cat.coords[obj])
    pruned = net.lookup(q, prune=prune)
    exact = net._lookup_fused(q)
    r = lookup_recall(pruned, exact)
    assert r >= 0.99, (prune, workload, r)
    # admissibility rides along: pruning can only raise the cost
    assert np.all(np.asarray(pruned.cost) >= np.asarray(exact.cost))


# ----------------------------------------------------- sentinel masking
@pytest.mark.parametrize("prune", ["lsh", "kmeans"])
def test_empty_level_sentinels_never_candidates(prune):
    """Sentinel keys of empty levels carry valid == 0 and must be
    excluded at table-build time (never in any bucket) and never be
    served; an all-empty network still answers from the repository."""
    net, rng = make_net(3, [4, 1, 4], [0.0, 0.1, 0.4], 2.5, "l2sq",
                        empty=(1,))
    keys, _, meta = net.fused_layout()
    sentinel_row = 4                      # level 1's single sentinel slot
    assert int(np.asarray(meta)[3, sentinel_row]) == 0
    for policy in (SimHashPolicy(), KMeansPolicy()):
        t = policy.build(np.asarray(keys), np.asarray(meta)[3] > 0)
        assert not np.any(t.buckets == sentinel_row)
    q = jnp.asarray(rng.standard_normal((11, 6)).astype(np.float32))
    for verify in (False, True):
        res = net.lookup(q, prune=prune, verify=verify)
        assert not np.any(np.asarray(res.level) == 1)
        assert np.all(np.isfinite(np.asarray(res.cost)))
    assert_results_equal(net.lookup(q, prune=prune, verify=True),
                         net._lookup_fused(q))

    net_all, rng = make_net(4, [1, 1], [0.0, 0.3], 7.5, "l2",
                            empty=(0, 1))
    q = jnp.asarray(rng.standard_normal((5, 6)).astype(np.float32))
    res = net_all.lookup(q, prune=prune, verify=True)
    np.testing.assert_array_equal(np.asarray(res.level), REPO_LEVEL)
    np.testing.assert_allclose(np.asarray(res.cost), 7.5)
    np.testing.assert_array_equal(np.asarray(res.payload), -1)


def test_no_levels_at_all_pruned():
    net = SimCacheNetwork(levels=[], h_repo=4.5, metric="l2")
    q = jnp.asarray(np.random.default_rng(0)
                    .standard_normal((6, 5)).astype(np.float32))
    res = net.lookup(q, prune="lsh", verify=True)
    np.testing.assert_array_equal(np.asarray(res.level), REPO_LEVEL)
    np.testing.assert_allclose(np.asarray(res.cost), 4.5)


# ------------------------------------------- cross-shard tie determinism
def _tie_instance(**kw):
    """Two 8-key levels with equal h and an identical key planted at
    slot 5 of both — concatenated indices 5 and 13 land in different
    shards of an 8-way split, so the winner must be the lower shard
    (= lower level) even when both duplicates survive pruning."""
    rng = np.random.default_rng(42)
    dup = np.ones((1, 6), np.float32)
    mk = lambda: np.concatenate(                      # noqa: E731
        [(rng.standard_normal((5, 6)) * 9 + 20).astype(np.float32), dup,
         (rng.standard_normal((2, 6)) * 9 + 20).astype(np.float32)])
    levels = [CacheLevel(keys=jnp.asarray(mk()),
                         values=jnp.asarray(
                             np.arange(8 * j, 8 * j + 8, dtype=np.int32)),
                         h=0.5) for j in range(2)]
    net = SimCacheNetwork(levels=list(levels), h_repo=9.0,
                          candidate_policy=COVER_ALL, **kw)
    return net, jnp.asarray(np.broadcast_to(dup, (3, 6)).copy())


def test_pruned_tie_break_oracle_eight_shards():
    """The chunked per-shard oracle with full-coverage tables: pruning
    must not perturb the cross-shard exact-cost tie (lower shard wins),
    at shard counts that do and don't divide the key count."""
    net, q = _tie_instance()
    keys, h_key, meta = net.fused_layout()
    ref = net._lookup_fused(q)
    for n_shards in (2, 3, 8):
        kp, hp, mp = pad_to_shards(keys, h_key, meta, n_shards)
        S = kp.shape[0] // n_shards
        ts = [COVER_ALL.for_shard(s).build(
            np.asarray(kp)[s * S:(s + 1) * S],
            np.asarray(mp)[3, s * S:(s + 1) * S] > 0)
            for s in range(n_shards)]
        out = sharded_pruned_fused_lookup_ref(q, kp, hp, mp, ts,
                                              cap_union=S, h_repo=9.0)
        np.testing.assert_array_equal(np.asarray(out[2]), 0)     # level
        np.testing.assert_array_equal(np.asarray(out[3]), 5)     # slot
        np.testing.assert_array_equal(np.asarray(out[0]),
                                      np.asarray(ref.cost))


def test_pruned_tie_break_one_device_mesh():
    net, q = _tie_instance()
    snet, _ = _tie_instance(sharded=True,
                            mesh=make_mesh((1,), ("data",)))
    for verify in (False, True):
        res = snet.lookup(q, prune="lsh", verify=verify)
        assert_results_equal(res, net._lookup_fused(q))
        np.testing.assert_array_equal(np.asarray(res.level), 0)
        np.testing.assert_array_equal(np.asarray(res.slot), 5)


@pytest.mark.skipif(not EIGHT, reason="needs 8 devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
def test_pruned_tie_break_eight_way_mesh():
    """The real 8-way shard_map path: the duplicate keys sit in shards 2
    and 6 (2 keys per shard); the candidate mask only shrinks each
    shard's scan, so reduce_shard_minima still breaks the tie to the
    lower shard."""
    snet, q = _tie_instance(sharded=True,
                            mesh=make_mesh((8,), ("data",)))
    net, _ = _tie_instance()
    for prune in ("lsh", "kmeans"):
        for verify in (False, True):
            res = snet.lookup(q, prune=prune, verify=verify)
            if prune == "lsh":        # full-coverage tables: bit-exact
                assert_results_equal(res, net._lookup_fused(q))
            np.testing.assert_array_equal(np.asarray(res.level), 0)
            np.testing.assert_array_equal(np.asarray(res.slot), 5)


@pytest.mark.skipif(not EIGHT, reason="needs 8 devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
@pytest.mark.parametrize("prune", ["lsh", "kmeans"])
def test_pruned_eight_way_differential(prune):
    mesh = make_mesh((8,), ("data",))
    for seed, sizes, hs, h_repo, empty, nq in [
        (0, [5, 9, 3], [0.0, 0.5, 1.0], 2.0, (), 23),
        (3, [4, 1, 4], [0.0, 0.1, 0.4], 2.5, (1,), 11),
        (5, [200, 150, 250], [0.0, 0.4, 0.8], 2.5, (), 300),
    ]:
        net, rng = make_net(seed, sizes, hs, h_repo, empty=empty)
        snet, _ = make_net(seed, sizes, hs, h_repo, empty=empty,
                           sharded=True, mesh=mesh)
        q = jnp.asarray((rng.standard_normal((nq, 6)) * 2)
                        .astype(np.float32))
        res = snet.lookup(q, prune=prune, verify=True)
        assert_results_equal(res, net._lookup_fused(q))
        if empty:
            for e in empty:
                assert not np.any(np.asarray(res.level) == e)


# ------------------------------------------------------------ staleness
@pytest.mark.parametrize("sharded", [False, True])
def test_stale_tables_fail_loudly(sharded):
    """Stricter than the layout's serve-stale-verbatim contract: a
    pruned lookup after mutating ``levels`` without invalidate_layout()
    must raise, not return candidates from the dead layout. After
    invalidation the rebuilt tables agree with the looped path again."""
    kw = dict(sharded=True, mesh=make_mesh((1,), ("data",))) \
        if sharded else {}
    net, rng = make_net(10, [4, 4], [0.0, 0.5], 3.0, "l2", **kw)
    q = jnp.asarray(rng.standard_normal((8, 6)).astype(np.float32))
    net.lookup(q, prune="lsh")                   # builds layout + tables
    net.levels[0] = CacheLevel(
        keys=jnp.asarray(rng.standard_normal((5, 6)).astype(np.float32)),
        values=jnp.asarray(np.arange(100, 105, dtype=np.int32)), h=0.0)
    with pytest.raises(RuntimeError, match="stale candidate tables"):
        net.lookup(q, prune="lsh")
    # the un-pruned path keeps its documented stale-serve behaviour
    net.lookup(q)
    net.invalidate_layout()
    assert not net._tables
    assert_results_equal(net.lookup(q, prune="lsh", verify=True),
                         net._lookup_looped(q))


def test_invalidate_layout_clears_tables_memo():
    net, rng = make_net(11, [6, 3], [0.0, 0.4], 2.0, "l2")
    q = jnp.asarray(rng.standard_normal((4, 6)).astype(np.float32))
    net.lookup(q, prune="lsh")
    net.lookup(q, prune="kmeans")
    assert len(net._tables) == 2           # memoized per (policy, shards)
    net.lookup(q, prune="lsh")
    assert len(net._tables) == 2           # hit, not a rebuild
    net.invalidate_layout()
    assert not net._tables and net._layout is None


# ------------------------------------------------------ ops — ref oracle
def test_pruned_ops_matches_ref_oracle():
    """Same tables through the jitted gather entry (Pallas kernel) and
    the pure-jnp oracle: same winners, costs to 1e-6, same bound."""
    net, rng = make_net(7, [40, 25], [0.0, 0.4], 2.0, "l2", gamma=2.0)
    q = jnp.asarray(rng.standard_normal((19, 6)).astype(np.float32))
    keys, h_key, meta = net.fused_layout()
    pol = SimHashPolicy(n_tables=2, n_bits=3, n_probes=2)
    t = pol.build(np.asarray(keys), np.asarray(meta)[3] > 0)
    cap = pol.resolve_cap(keys.shape[0])
    out_k = pruned_fused_lookup(q, keys, h_key, meta,
                                jnp.asarray(t.proj), jnp.asarray(t.buckets),
                                kind=t.kind, n_probes=t.n_probes,
                                cap_union=cap, metric="l2", gamma=2.0,
                                h_repo=2.0)
    out_r = pruned_fused_lookup_ref(q, keys, h_key, meta, t, cap,
                                    metric="l2", gamma=2.0, h_repo=2.0)
    for a, b in zip(out_k, out_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)
    # use_pallas=False routes the same pruned path through the oracle
    import dataclasses
    res = net.lookup(q, prune="lsh", verify=True)
    no_pallas = dataclasses.replace(net, use_pallas=False)
    assert_results_equal(res, no_pallas.lookup(q, prune="lsh",
                                               verify=True),
                         exact_cost=False)


def test_hot_bucket_capped_and_verify_still_exact():
    """One bucket of near-duplicate popular keys must not inflate the
    dense table: per-bucket capacity clamps at 8× the mean load, the
    overflow (highest rows) is dropped at build time, and — because
    dropped members are "un-scanned" to the verify bound — verify=True
    stays bit-identical to the exact path regardless of the skew."""
    rng = np.random.default_rng(0)
    hot = np.ones((1, 6), np.float32) + \
        0.001 * rng.standard_normal((500, 6)).astype(np.float32)
    cold = (rng.standard_normal((100, 6)) * 9 + 20).astype(np.float32)
    keys = np.concatenate([hot, cold])
    net = SimCacheNetwork(
        levels=[CacheLevel(keys=jnp.asarray(keys),
                           values=jnp.asarray(np.arange(600,
                                                        dtype=np.int32)),
                           h=0.5)], h_repo=9.0)
    _, _, meta = net.fused_layout()
    pol = SimHashPolicy(n_bits=4)              # 16 buckets, mean load 38
    t = pol.build(keys, np.asarray(meta)[3] > 0)
    assert t.buckets.shape[-1] <= 8 * -(-600 // 16)   # capped, not 500
    q = jnp.asarray(np.concatenate(
        [hot[:3], cold[:3],
         rng.standard_normal((4, 6)).astype(np.float32)]))
    assert_results_equal(net.lookup(q, prune="lsh", verify=True),
                         net._lookup_fused(q))


# --------------------------------------------------- Demand.sample fix
def test_demand_sample_float32_catalog_reproducible():
    """Regression: probabilities normalized at float32 precision (what a
    float32 catalog produces) deviate from 1 by more than rng.choice's
    float64 tolerance (√eps ≈ 1.5e-8) and used to abort with
    "probabilities do not sum to 1"; sample() now casts to float64 and
    renormalizes, returning platform-independent int64 draws,
    reproducible under a fixed seed."""
    # float32-rounded thirds: sum in float64 is 1 + 3e-8, past tolerance
    lam = np.asarray(np.full((1, 3), np.float32(1 / 3)), np.float64)
    assert abs(float(lam.sum()) - 1.0) > 1.5e-8       # the trigger
    with pytest.raises(ValueError):                   # the old code path
        np.random.default_rng(0).choice(3, size=4, p=lam.ravel())
    dem = demand_api.Demand(lam=lam)
    obj, ing = dem.sample(64, np.random.default_rng(123))
    obj2, ing2 = dem.sample(64, np.random.default_rng(123))
    np.testing.assert_array_equal(obj, obj2)
    np.testing.assert_array_equal(ing, ing2)
    assert obj.dtype == np.int64 and ing.dtype == np.int64
    assert obj.min() >= 0 and obj.max() < 3
    assert np.all(ing == 0)
    # a float32 lam matrix works too (the catalog-facing case)
    dem32 = demand_api.Demand(lam=np.full((1, 3), np.float32(1 / 3)))
    o3, _ = dem32.sample(16, np.random.default_rng(5))
    assert o3.dtype == np.int64


# -------------------------------------------------- nightly recall, 10⁶
@pytest.mark.slow
@pytest.mark.skipif(not FULL, reason="slow: nightly/full pass only "
                    "(CI_FULL=1)")
def test_recall_one_million_keys():
    """The catalogs-≫-10⁵ regime the tentpole targets: 10⁶ keys across
    two levels, Zipf-weighted queries, default tables — recall ≥ 0.99
    and the pruned scan covers < ½ of the keys (the bench measures the
    actual speedup; this guards the quality side)."""
    rng = np.random.default_rng(0)
    n, d = 1_000_000, 16
    coords = rng.standard_normal((n, d)).astype(np.float32)
    half = n // 2
    levels = [CacheLevel(keys=jnp.asarray(coords[:half]),
                         values=jnp.asarray(np.arange(half,
                                                      dtype=np.int32)),
                         h=0.0),
              CacheLevel(keys=jnp.asarray(coords[half:]),
                         values=jnp.asarray(np.arange(half, n,
                                                      dtype=np.int32)),
                         h=0.5)]
    net = SimCacheNetwork(levels=levels, h_repo=1e9, metric="l2")
    ranks = rng.permutation(n)[:4096]
    p = 1.0 / (np.arange(1, 4097) ** 0.9)
    ids = ranks[rng.choice(4096, 16, p=p / p.sum())]
    q = jnp.asarray(coords[ids]
                    + 0.05 * rng.standard_normal((16, d)).astype(
                        np.float32))
    pruned = net.lookup(q, prune="lsh")
    exact = net._lookup_fused(q)
    assert lookup_recall(pruned, exact) >= 0.99
    assert np.all(np.asarray(pruned.cost) >= np.asarray(exact.cost))
    pol = SimHashPolicy()
    assert pol.resolve_cap(n) < n // 2
