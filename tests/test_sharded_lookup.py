"""Differential multi-device suite for the mesh-sharded fused lookup.

Three implementations of eq. (1) must agree everywhere:
  * looped  — one KNN kernel per level, minima compared centrally;
  * fused   — one segmented-1-NN pallas_call over the concatenation;
  * sharded — the fused kernel once *per key shard* under shard_map,
    per-shard minima all-gathered and reduced lexicographically (min
    cost, ties to the lowest shard = lowest concatenated index), with
    the repository folded once after the reduction.

The sharded path is required to be **bit-identical** to the fused path
for γ = 1 (identical f32 arithmetic per (query, key) pair; the reduction
is an argmin over exactly the kernel's own running-min values); for
γ ≠ 1 XLA may contract pow/sqrt/add chains differently across kernels,
so costs compare to 1e-6 like the existing fused-vs-looped suite.

Coverage: uneven shard sizes (ΣK_j not divisible by the shard count →
invalid padding keys), empty levels whose sentinel keys straddle shard
boundaries, exact cost ties across shards (tie-break determinism), B=1
and multi-query-tile batches, and the memoized-layout staleness
contract.

Device counts: the pure-jnp chunked oracle (sharded_fused_lookup_ref)
runs in-process at any shard count; real-mesh tests run either on a
1-device mesh in-process, on an 8-way mesh in a subprocess (always), or
in-process when the suite itself runs under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the second CI
pass — see scripts/ci.sh).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import assert_results_equal, make_net

from repro.core.simcache import REPO_LEVEL, CacheLevel, SimCacheNetwork
from repro.kernels.knn import sharded_fused_lookup_ref
from repro.launch.mesh import make_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EIGHT = jax.device_count() >= 8


# --------------------------------------------------------------- oracle
@pytest.mark.parametrize("metric", ["l1", "l2", "l2sq"])
@pytest.mark.parametrize("n_shards", [1, 2, 3, 5, 8, 17])
def test_sharded_oracle_matches_fused(metric, n_shards):
    """The mesh-free chunked oracle reproduces the fused path bit-for-bit
    at every shard count — including counts that don't divide ΣK_j
    (padding) and counts exceeding ΣK_j (some shards entirely padding)."""
    net, rng = make_net(0, [5, 9, 3], [0.0, 0.5, 1.0], 2.0, metric)
    q = jnp.asarray((rng.standard_normal((23, 6)) * 2).astype(np.float32))
    ref = net._lookup_fused(q)
    keys, h_key, meta = net.fused_layout()
    cost, ca, lvl, slot, pay = sharded_fused_lookup_ref(
        q, keys, h_key, meta, n_shards, metric=metric, h_repo=2.0)
    np.testing.assert_array_equal(np.asarray(cost), np.asarray(ref.cost))
    np.testing.assert_array_equal(np.asarray(ca),
                                  np.asarray(ref.approx_cost))
    np.testing.assert_array_equal(np.asarray(lvl), np.asarray(ref.level))
    np.testing.assert_array_equal(np.asarray(slot), np.asarray(ref.slot))
    np.testing.assert_array_equal(np.asarray(pay), np.asarray(ref.payload))


@pytest.mark.parametrize("n_shards", [2, 4, 7])
def test_sharded_oracle_empty_levels_and_repo(n_shards):
    """Sentinel keys of empty levels land in arbitrary shards and must
    stay masked; an all-empty network serves everything from the repo."""
    net, rng = make_net(3, [4, 1, 4], [0.0, 0.1, 0.4], 2.5, "l2sq",
                        empty=(1,))
    q = jnp.asarray(rng.standard_normal((11, 6)).astype(np.float32))
    keys, h_key, meta = net.fused_layout()
    out = sharded_fused_lookup_ref(q, keys, h_key, meta, n_shards,
                                   metric="l2sq", h_repo=2.5)
    assert not np.any(np.asarray(out[2]) == 1)
    assert np.all(np.isfinite(np.asarray(out[0])))
    ref = net._lookup_fused(q)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(ref.cost))

    net_all, rng = make_net(4, [1, 1], [0.0, 0.3], 7.5, "l2",
                            empty=(0, 1))
    q = jnp.asarray(rng.standard_normal((5, 6)).astype(np.float32))
    keys, h_key, meta = net_all.fused_layout()
    cost, ca, lvl, slot, pay = sharded_fused_lookup_ref(
        q, keys, h_key, meta, n_shards, metric="l2", h_repo=7.5)
    np.testing.assert_allclose(np.asarray(cost), 7.5)
    np.testing.assert_array_equal(np.asarray(lvl), REPO_LEVEL)
    np.testing.assert_array_equal(np.asarray(pay), -1)
    np.testing.assert_array_equal(np.asarray(ca), 0.0)


# ------------------------------------------------------- 1-device mesh
def test_sharded_one_device_mesh_bit_identical():
    """The real shard_map path on a trivial 1-device mesh: sharded ==
    fused == looped, bitwise (γ = 1)."""
    mesh = make_mesh((1,), ("data",))
    net, rng = make_net(1, [17, 2, 31, 8], [0.0, 0.2, 0.7, 1.3], 3.0)
    snet, _ = make_net(1, [17, 2, 31, 8], [0.0, 0.2, 0.7, 1.3], 3.0,
                       sharded=True, mesh=mesh)
    q = jnp.asarray((rng.standard_normal((23, 6)) * 2).astype(np.float32))
    assert_results_equal(snet.lookup(q), net._lookup_fused(q))
    assert_results_equal(snet.lookup(q), net._lookup_looped(q))


def test_sharded_no_levels_serves_repo():
    mesh = make_mesh((1,), ("data",))
    net = SimCacheNetwork(levels=[], h_repo=4.5, metric="l2",
                          sharded=True, mesh=mesh)
    q = jnp.asarray(np.random.default_rng(0)
                    .standard_normal((6, 5)).astype(np.float32))
    res = net.lookup(q)
    np.testing.assert_array_equal(np.asarray(res.level), REPO_LEVEL)
    np.testing.assert_allclose(np.asarray(res.cost), 4.5)
    assert not np.any(np.asarray(res.hit))


# -------------------------------------------------- staleness contract
@pytest.mark.parametrize("sharded", [False, True])
def test_stale_layout_then_invalidate(sharded):
    """Documented memoization contract: mutating ``levels`` without
    invalidate_layout() keeps serving the *stale* concatenation (old
    results, verbatim); invalidate_layout() restores agreement with the
    looped path — for both the fused and the sharded data plane."""
    kw = dict(sharded=True, mesh=make_mesh((1,), ("data",))) \
        if sharded else {}
    net, rng = make_net(10, [4, 4], [0.0, 0.5], 3.0, "l2", **kw)
    q = jnp.asarray(rng.standard_normal((8, 6)).astype(np.float32))
    before = net.lookup(q)                       # memoizes the layout
    new_keys = jnp.asarray(rng.standard_normal((5, 6)).astype(np.float32))
    net.levels[0] = CacheLevel(
        keys=new_keys,
        values=jnp.asarray(np.arange(100, 105, dtype=np.int32)), h=0.0)
    stale = net.lookup(q)                        # no invalidate yet
    assert_results_equal(stale, before)          # serves the old layout
    # the looped path reads `levels` directly, so it already disagrees
    # (the mutation moved level 0's keys under the queries)
    assert not np.array_equal(np.asarray(stale.payload),
                              np.asarray(net._lookup_looped(q).payload))
    net.invalidate_layout()
    assert_results_equal(net.lookup(q), net._lookup_looped(q))


def test_invalidate_layout_clears_sharded_memo():
    mesh = make_mesh((1,), ("data",))
    net, rng = make_net(11, [6, 3], [0.0, 0.4], 2.0, "l2",
                        sharded=True, mesh=mesh)
    q = jnp.asarray(rng.standard_normal((4, 6)).astype(np.float32))
    net.lookup(q)
    assert net._sharded_layout          # memoized per shard count
    net.invalidate_layout()
    assert not net._sharded_layout and net._layout is None


# ------------------------------------------------------- shard policy
def test_lookup_shard_policy_contract():
    """LookupShardPolicy resolves shard axes from the mesh (preference:
    model → data → pod, falling back to all axes for unrecognised
    meshes); n_shards is the product of the chosen axis sizes."""
    from repro.launch.sharding import LookupShardPolicy

    pol = LookupShardPolicy.create(make_mesh((1,), ("data",)))
    assert pol.axes == ("data",) and pol.n_shards == 1

    pol2 = LookupShardPolicy.create(make_mesh((1, 1),
                                                  ("data", "model")))
    assert pol2.axes == ("model", "data")        # model preferred first
    # unrecognised axis names: shard over whatever the mesh has
    pol3 = LookupShardPolicy.create(make_mesh((1,), ("lookup",)))
    assert pol3.axes == ("lookup",)

    # shard-count arithmetic at a multi-device count (mesh shape is the
    # only thing n_shards consults, so a stub suffices on 1 device)
    class _Mesh:
        shape = {"model": 4, "data": 2}
    pol4 = LookupShardPolicy(mesh=_Mesh(), axes=("model", "data"))
    assert pol4.n_shards == 8


def test_make_mesh_builds_auto_axes():
    """Every repository mesh has Auto axes: the cross-shard reduction's
    take_along_axis and the model's gathers are written for shardings
    the compiler propagates (jax.make_mesh alone builds Explicit axes)."""
    from jax.sharding import AxisType

    from repro.launch.mesh import make_debug_mesh, make_lookup_mesh
    for mesh in (make_mesh((1, 1), ("data", "model")), make_lookup_mesh(),
                 make_debug_mesh(1, 1)):
        assert all(t == AxisType.Auto for t in mesh.axis_types), mesh


# ------------------------------------------------------- dtype contract
def test_from_placement_sentinel_values_dtype():
    """Empty levels must build their sentinel ``values`` as int32
    directly (the old path built int64 then downcast), and occupied
    levels likewise store int32 payloads end to end."""
    rng = np.random.default_rng(9)
    coords = rng.standard_normal((40, 5)).astype(np.float32)
    slot_cache = np.array([0] * 4 + [1] * 4)
    slots = np.concatenate([rng.choice(40, 4, replace=False),
                            np.full(4, -1)]).astype(np.int64)
    net = SimCacheNetwork.from_placement(coords, slots, slot_cache,
                                         hs=[0.0, 0.5], h_repo=2.0)
    for lv in net.levels:
        assert lv.values.dtype == jnp.int32, lv.values.dtype
        assert lv.keys.dtype == jnp.float32
    assert int(net.levels[1].values[0]) == -1       # sentinel payload


# ------------------------------------------- in-process 8-way (CI pass 2)
@pytest.mark.skipif(not EIGHT, reason="needs 8 devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
@pytest.mark.parametrize("metric,gamma", [("l2", 1.0), ("l1", 1.0),
                                          ("l2sq", 2.0)])
def test_sharded_eight_way_differential(metric, gamma):
    mesh = make_mesh((8,), ("data",))
    for seed, sizes, hs, h_repo, nq in [
        (0, [5, 9, 3], [0.0, 0.5, 1.0], 2.0, 23),      # K=17: pad to 24
        (1, [17, 2, 31, 8], [0.0, 0.2, 0.7, 1.3], 3.0, 1),   # B=1
        (3, [200, 150, 250], [0.0, 0.4, 0.8], 2.5, 300),     # multi-tile
    ]:
        net, rng = make_net(seed, sizes, hs, h_repo, metric, gamma)
        snet, _ = make_net(seed, sizes, hs, h_repo, metric, gamma,
                           sharded=True, mesh=mesh)
        q = jnp.asarray((rng.standard_normal((nq, 6)) * 2)
                        .astype(np.float32))
        assert_results_equal(snet.lookup(q), net._lookup_fused(q),
                             exact_cost=gamma == 1.0)
        assert_results_equal(snet.lookup(q), net._lookup_looped(q),
                             exact_cost=gamma == 1.0)


@pytest.mark.skipif(not EIGHT, reason="needs 8 devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
def test_sharded_eight_way_tie_break():
    mesh = make_mesh((8,), ("data",))
    net, snet, q = _tie_instance(mesh)
    rf, rs = net._lookup_fused(q), snet.lookup(q)
    assert_results_equal(rs, rf)
    # the duplicate key tying across levels resolves to the lower level
    np.testing.assert_array_equal(np.asarray(rs.level), 0)
    np.testing.assert_array_equal(np.asarray(rs.slot), 5)


def _tie_instance(mesh):
    """Two 8-key levels with equal h and an identical key planted at
    slot 5 of both — concatenated indices 5 and 13 land in *different*
    shards of an 8-way mesh (2 keys per shard), so the cross-shard
    reduction must break the exact cost tie toward the lower shard."""
    rng = np.random.default_rng(42)
    dup = np.ones((1, 6), np.float32)
    mk = lambda: np.concatenate(                      # noqa: E731
        [(rng.standard_normal((5, 6)) * 9 + 20).astype(np.float32), dup,
         (rng.standard_normal((2, 6)) * 9 + 20).astype(np.float32)])
    levels = [CacheLevel(keys=jnp.asarray(mk()),
                         values=jnp.asarray(
                             np.arange(8 * j, 8 * j + 8, dtype=np.int32)),
                         h=0.5) for j in range(2)]
    net = SimCacheNetwork(levels=list(levels), h_repo=9.0)
    snet = SimCacheNetwork(levels=list(levels), h_repo=9.0, sharded=True,
                           mesh=mesh)
    return net, snet, jnp.asarray(np.broadcast_to(dup, (3, 6)).copy())


def test_sharded_tie_break_oracle_any_devices():
    """Same tie instance, via the chunked oracle (no mesh needed)."""
    net, _, q = _tie_instance(make_mesh((1,), ("data",)))
    keys, h_key, meta = net.fused_layout()
    out = sharded_fused_lookup_ref(q, keys, h_key, meta, 8, h_repo=9.0)
    np.testing.assert_array_equal(np.asarray(out[2]), 0)    # level
    np.testing.assert_array_equal(np.asarray(out[3]), 5)    # slot
    ref = net._lookup_fused(q)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(ref.cost))


# ---------------------------------------------------- 8-way subprocess
def run_in_subprocess(body: str):
    """8 forced host devices in a fresh interpreter, independent of the
    parent's device count (XLA_FLAGS is popped from the env and re-set
    in-script), so these tests give real 8-way mesh coverage even in the
    default single-device tier-1 pass. ci.sh's 8-device pass 2 deselects
    them (-k "not _subprocess") — rerunning them there adds nothing."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        import jax.numpy as jnp
        import numpy as np
        assert jax.device_count() == 8
        from repro.core.simcache import (REPO_LEVEL, SENTINEL_COORD,
                                         CacheLevel, SimCacheNetwork)
        from repro.launch.mesh import make_mesh

        def make_net(seed, sizes, hs, h_repo, metric="l2", gamma=1.0,
                     d=6, empty=(), **kw):
            rng = np.random.default_rng(seed)
            levels = []
            for j, (k, h) in enumerate(zip(sizes, hs)):
                if j in empty:
                    keys = np.full((1, d), SENTINEL_COORD, np.float32)
                    vals = np.full((1,), -1, np.int32)
                else:
                    keys = (rng.standard_normal((k, d)) * 2).astype(
                        np.float32)
                    vals = rng.integers(0, 10_000, k).astype(np.int32)
                levels.append(CacheLevel(keys=jnp.asarray(keys),
                                         values=jnp.asarray(vals),
                                         h=float(h)))
            return SimCacheNetwork(levels=levels, h_repo=float(h_repo),
                                   metric=metric, gamma=gamma, **kw), rng

        def check(a, b, exact=True):
            for n in ("level", "slot", "payload", "hit"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(a, n)), np.asarray(getattr(b, n)),
                    err_msg=n)
            for n in ("cost", "approx_cost"):
                x = np.asarray(getattr(a, n))
                y = np.asarray(getattr(b, n))
                if exact:
                    np.testing.assert_array_equal(x, y, err_msg=n)
                else:
                    np.testing.assert_allclose(x, y, rtol=1e-6,
                                               atol=1e-6, err_msg=n)
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, \
        f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_eight_way_mesh_differential_subprocess():
    """The acceptance-criterion run: sharded == fused == looped on a real
    8-way host-device mesh, covering uneven shard sizes (padding), empty
    levels with sentinels split across shards, B=1, and a multi-tile
    batch."""
    run_in_subprocess("""
        mesh = make_mesh((8,), ("data",))
        cases = [
            # uneven: K=17 pads to 24, shards hold 3 keys, 7 of them pad
            (0, [5, 9, 3], [0.0, 0.5, 1.0], 2.0, "l2", 1.0, (), 23),
            # B=1 and a 4-level chain
            (1, [17, 2, 31, 8], [0.0, 0.2, 0.7, 1.3], 3.0, "l2", 1.0,
             (), 1),
            # empty middle level: its sentinel is one of K=9 keys spread
            # over 8 shards — masking must survive the shard split
            (3, [4, 1, 4], [0.0, 0.1, 0.4], 2.5, "l2sq", 1.0, (1,), 11),
            # all levels empty: everything from the repository
            (4, [1, 1], [0.0, 0.3], 7.5, "l1", 1.0, (0, 1), 5),
            # large batch: 700 queries = 3 query tiles at BQ=256
            (5, [200, 150, 250], [0.0, 0.4, 0.8], 2.5, "l2", 1.0,
             (), 700),
            # gamma != 1 compares costs to 1e-6 (FMA contraction)
            (6, [64, 64], [0.0, 1.0], 5.0, "l2", 2.0, (), 23),
        ]
        for (seed, sizes, hs, h_repo, metric, gamma, empty, nq) in cases:
            net, rng = make_net(seed, sizes, hs, h_repo, metric, gamma,
                                empty=empty)
            snet, _ = make_net(seed, sizes, hs, h_repo, metric, gamma,
                               empty=empty, sharded=True, mesh=mesh)
            q = jnp.asarray((rng.standard_normal((nq, 6)) * 2)
                            .astype(np.float32))
            rs = snet.lookup(q)
            check(rs, net._lookup_fused(q), exact=gamma == 1.0)
            check(rs, net._lookup_looped(q), exact=gamma == 1.0)
            if empty:
                for e in empty:
                    assert not np.any(np.asarray(rs.level) == e)
        print("8-way differential ok:", len(cases), "cases")
    """)


def test_eight_way_ties_and_staleness_subprocess():
    run_in_subprocess("""
        mesh = make_mesh((8,), ("data",))
        # exact tie across shards: identical key at slot 5 of two levels
        # with equal h (concatenated indices 5 and 13 → shards 2 and 6);
        # deterministic winner = lower shard = lower level
        rng = np.random.default_rng(42)
        dup = np.ones((1, 6), np.float32)
        mk = lambda: np.concatenate(
            [(rng.standard_normal((5, 6)) * 9 + 20).astype(np.float32),
             dup,
             (rng.standard_normal((2, 6)) * 9 + 20).astype(np.float32)])
        levels = [CacheLevel(keys=jnp.asarray(mk()),
                             values=jnp.asarray(np.arange(
                                 8 * j, 8 * j + 8, dtype=np.int32)),
                             h=0.5) for j in range(2)]
        net = SimCacheNetwork(levels=list(levels), h_repo=9.0)
        snet = SimCacheNetwork(levels=list(levels), h_repo=9.0,
                               sharded=True, mesh=mesh)
        q = jnp.asarray(np.broadcast_to(dup, (3, 6)).copy())
        rs = snet.lookup(q)
        check(rs, net._lookup_fused(q))
        assert np.all(np.asarray(rs.level) == 0), np.asarray(rs.level)
        assert np.all(np.asarray(rs.slot) == 5), np.asarray(rs.slot)
        # repo tie on the sharded path: h level == h_repo → cache serves
        key = np.ones((1, 6), np.float32)
        tie = SimCacheNetwork(
            levels=[CacheLevel(keys=jnp.asarray(key),
                               values=jnp.asarray(
                                   np.array([7], np.int32)), h=2.0)],
            h_repo=2.0, sharded=True, mesh=mesh)
        r = tie.lookup(jnp.asarray(key))
        assert int(r.level[0]) == 0 and int(r.payload[0]) == 7
        # staleness on a real mesh: stale sharded layout serves the old
        # keys until invalidate_layout()
        snet2 = SimCacheNetwork(levels=list(levels), h_repo=9.0,
                                sharded=True, mesh=mesh)
        before = snet2.lookup(q)
        snet2.levels[0] = CacheLevel(
            keys=jnp.asarray(np.full((4, 6), 50.0, np.float32)),
            values=jnp.asarray(np.arange(4, dtype=np.int32)), h=0.5)
        stale = snet2.lookup(q)
        np.testing.assert_array_equal(np.asarray(stale.payload),
                                      np.asarray(before.payload))
        snet2.invalidate_layout()
        ref = SimCacheNetwork(levels=list(snet2.levels), h_repo=9.0)
        check(snet2.lookup(q), ref._lookup_fused(q))
        print("8-way ties + staleness ok")
    """)
