"""Differential tests of the device-resident placement control plane.

Device GREEDY / LOCALSWAP (core/placement/device.py, driven by the
batched gain oracle of kernels/knn/gains.py) must return allocations
**bit-identical** to the host NumPy oracles (greedy.py / localswap.py)
— same lowest-(o', j) and lowest-slot tie-breaks — on Gaussian-grid and
Zipf-embedding instances, in both C_a modes (materialized matrix /
streamed distance tiles), through both oracle backends (blocked jnp /
Pallas-interpret), and at any shard count (the in-process mesh tests
run 1-way in the default tier-1 pass and 8-way in scripts/ci.sh's
second pass).

The Gaussian grid demand is jittered deterministically: the exact grid
symmetry otherwise produces *exactly tied* gains whose f32-vs-f64
summation noise would make "bit-identical" depend on accumulation
order rather than on the tie-break contract. Genuine tie handling is
covered separately by the duplicate-object and gain_tol tests.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import catalog, demand, topology
from repro.core.objective import DeviceInstance, Instance, random_slots
from repro.core.placement import (device_greedy,
                                  device_greedy_then_localswap,
                                  device_localswap,
                                  device_localswap_polish, greedy,
                                  greedy_then_localswap, localswap,
                                  localswap_polish)
from repro.kernels.knn import (placement_gains, placement_gains_ref,
                               sharded_placement_gains)
from repro.launch.mesh import make_lookup_mesh

TOL = 1e-5          # one decision margin for host and device swap paths


def gauss_instance(L=8, k=(3, 4), sigma=2.0, seed=0):
    """§6.1 grid/Gaussian instance, demand jittered to break the grid's
    exact gain ties (see module docstring)."""
    cat = catalog.grid(L=L)
    net = topology.tandem(k_leaf=k[0], k_parent=k[1], h=2.0, h_repo=10.0)
    dem0 = demand.gaussian_grid(cat, sigma=sigma)
    rng = np.random.default_rng(seed)
    lam = dem0.lam * (1.0 + 1e-3 * rng.random(dem0.lam.shape))
    return Instance(net=net, cat=cat,
                    dem=demand.Demand(lam=lam / lam.sum()))


def zipf_instance(n=180, dim=6, k=(8, 12), seed=1):
    """§6.2 embedding/Zipf instance (tandem)."""
    cat = catalog.embedding_catalog(n=n, dim=dim, seed=seed)
    net = topology.tandem(k_leaf=k[0], k_parent=k[1], h=50.0, h_repo=400.0)
    return Instance(net=net, cat=cat,
                    dem=demand.zipf(cat, alpha=0.8, seed=seed + 1))


def tree_instance(seed=3):
    """Multi-ingress instance: 2-leaf equi-depth tree (§4.3) — exercises
    the gain oracle's ingress-segment axis."""
    cat = catalog.embedding_catalog(n=150, dim=4, seed=seed)
    net = topology.equi_depth_tree(2, 1, [4, 6], [0.0, 30.0], 300.0)
    dem = demand.zipf(cat, alpha=0.7, n_ingress=net.n_ingress, seed=seed)
    return Instance(net=net, cat=cat, dem=dem)


ALL_INSTANCES = [("gauss", gauss_instance), ("zipf", zipf_instance),
                 ("tree", tree_instance)]


# ------------------------------------------------------------- gain oracle
@pytest.mark.parametrize("metric", ["l1", "l2"])
def test_gain_kernel_matches_ref_and_host(metric):
    """Pallas kernel == jnp oracle == blocked jnp path == host
    add_gain_all, on a multi-ingress request matrix (the segment axis
    the kernels/gain kernel lacks)."""
    rng = np.random.default_rng(5)
    R, O, D, I, J = 117, 83, 5, 2, 3
    x = jnp.asarray(rng.standard_normal((R, D)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal((O, D)).astype(np.float32))
    lam = jnp.asarray(rng.random((I, R)).astype(np.float32))
    cur = jnp.asarray((rng.random((I, R)) * 4).astype(np.float32))
    h = rng.random((I, J)).astype(np.float32)
    h[1, 0] = np.inf                                   # off-path entry
    hj = jnp.asarray(h)
    ref = placement_gains_ref(x, y, lam, cur,
                              jnp.where(jnp.isfinite(hj), hj, 1e30), metric)
    g_pl = placement_gains(x, y, lam, cur, hj, metric=metric,
                           use_pallas=True, interpret=True, br=32, bo=32)
    g_jnp = placement_gains(x, y, lam, cur, hj, metric=metric,
                            use_pallas=False, bo=32)
    np.testing.assert_allclose(g_pl, ref, rtol=5e-5, atol=5e-5)
    np.testing.assert_allclose(g_jnp, ref, rtol=5e-5, atol=5e-5)
    assert np.all(np.asarray(g_pl) >= 0.0)


def test_gain_oracle_matches_host_on_instance():
    inst = tree_instance()
    cur = np.repeat(inst.net.h_repo[:, None].astype(np.float64),
                    inst.cat.n, axis=1)
    ref = inst.add_gain_all(cur)                       # (O, J) host f64
    dinst = DeviceInstance.from_instance(inst, materialize_ca=False)
    g = dinst.gains(jnp.asarray(cur, jnp.float32))
    np.testing.assert_allclose(np.asarray(g), ref, rtol=1e-4, atol=1e-4)
    dmat = DeviceInstance.from_instance(inst, materialize_ca=True)
    gm = dmat.gains(jnp.asarray(cur, jnp.float32))
    np.testing.assert_allclose(np.asarray(gm), ref, rtol=1e-4, atol=1e-4)


def test_sharded_gain_oracle_bitwise_equal():
    """Candidate-axis sharding never changes a gain value: every
    candidate's sum is computed with identical request tiling in its
    one owning shard (1-way mesh in the default pass, 8-way in
    scripts/ci.sh pass 2)."""
    inst = zipf_instance(n=133)
    dinst = DeviceInstance.from_instance(inst, materialize_ca=False)
    cur = dinst.initial_costs()
    mesh = make_lookup_mesh(jax.device_count())
    gs = sharded_placement_gains(
        dinst.coords, dinst.coords, dinst.lam, cur, dinst.H, mesh,
        ("data",), metric=dinst.metric, gamma=dinst.gamma,
        use_pallas=False)
    gu = dinst.gains(cur)
    np.testing.assert_array_equal(np.asarray(gs), np.asarray(gu))


# ------------------------------------------------------------------ GREEDY
@pytest.mark.parametrize("name,make", ALL_INSTANCES)
@pytest.mark.parametrize("materialize", [True, False])
def test_device_greedy_bit_identical(name, make, materialize):
    inst = make()
    host_lazy = greedy(inst, lazy=True)
    host_eager = greedy(inst, lazy=False)
    np.testing.assert_array_equal(host_lazy, host_eager)
    dinst = DeviceInstance.from_instance(inst, materialize_ca=materialize)
    dev = device_greedy(dinst)
    np.testing.assert_array_equal(dev, host_lazy)


def test_device_greedy_through_pallas_oracle():
    """Same allocation when the full-gain launch goes through the
    Pallas kernel (interpret mode) instead of the blocked jnp path."""
    inst = zipf_instance(n=140, k=(5, 7))
    dinst = DeviceInstance.from_instance(inst, materialize_ca=False,
                                         use_pallas=True, interpret=True)
    np.testing.assert_array_equal(device_greedy(dinst), greedy(inst))


def test_device_greedy_sharded_bit_identical():
    """Mesh-sharded gain oracle → same allocation (8-way in CI pass 2)."""
    inst = zipf_instance(n=170, k=(6, 9), seed=4)
    mesh = make_lookup_mesh(jax.device_count())
    dinst = DeviceInstance.from_instance(inst, mesh=mesh, axes=("data",),
                                         materialize_ca=False)
    assert dinst.n_shards == jax.device_count()
    np.testing.assert_array_equal(device_greedy(dinst), greedy(inst))


def test_device_greedy_small_topk_still_exact():
    """The stale-refresh batch size is a perf knob, not a semantics
    knob: topk=1 degenerates to classic lazy greedy, same allocation."""
    inst = zipf_instance(n=90, k=(4, 5), seed=9)
    dinst = DeviceInstance.from_instance(inst)
    np.testing.assert_array_equal(device_greedy(dinst, topk=1),
                                  greedy(inst))


def test_device_gains_monotone_along_greedy_trajectory():
    """Submodularity (Prop 3.2) observed by the device oracle: marginal
    gains are monotone non-increasing along the greedy trajectory."""
    inst = gauss_instance(L=6, k=(3, 3))
    dinst = DeviceInstance.from_instance(inst, materialize_ca=False)
    cur = dinst.initial_costs()
    slots = device_greedy(dinst)
    prev = np.asarray(dinst.gains(cur))
    order = [int(s) for s in np.argsort(inst.slot_cache, kind="stable")]
    # replay the allocation pick by pick (per-cache slot order = pick
    # order within a cache; across caches the gain argmax decides, but
    # monotonicity must hold along *any* insertion order)
    for s in order:
        if slots[s] < 0:
            continue
        cur = dinst.apply_pick(cur, int(slots[s]),
                               int(inst.slot_cache[s]))
        g = np.asarray(dinst.gains(cur))
        assert np.all(g <= prev + 1e-4), np.max(g - prev)
        prev = g


# --------------------------------------------------------------- LOCALSWAP
@pytest.mark.parametrize("name,make", [ALL_INSTANCES[0], ALL_INSTANCES[1]])
def test_device_localswap_bit_identical(name, make):
    inst = make()
    dinst = DeviceInstance.from_instance(inst)
    hs = localswap(inst, n_iters=500, seed=7, tol=TOL)
    ds = device_localswap(dinst, n_iters=500, seed=7, tol=TOL)
    np.testing.assert_array_equal(hs.slots, ds.slots_np)
    assert hs.n_swaps == ds.n_swaps


@pytest.mark.parametrize("materialize", [True, False])
def test_device_polish_and_cascade_bit_identical(materialize):
    inst = zipf_instance(n=120, k=(5, 6), seed=2)
    dinst = DeviceInstance.from_instance(inst, materialize_ca=materialize)
    rng = np.random.default_rng(11)
    s0 = random_slots(inst, rng)
    hp = localswap_polish(inst, s0, max_passes=6, tol=TOL)
    dp = device_localswap_polish(dinst, s0, max_passes=6, tol=TOL)
    np.testing.assert_array_equal(hp.slots, dp.slots_np)
    assert hp.n_swaps == dp.n_swaps
    hc = greedy_then_localswap(inst, max_passes=6, tol=TOL)
    dc = device_greedy_then_localswap(dinst, max_passes=6, tol=TOL)
    np.testing.assert_array_equal(hc.slots, dc.slots_np)


def test_device_total_cost_matches_host():
    inst = zipf_instance(n=100, k=(4, 4))
    dinst = DeviceInstance.from_instance(inst, materialize_ca=False)
    slots = greedy(inst)
    slots = np.where(slots < 0, 0, slots)
    assert dinst.total_cost(slots) == pytest.approx(
        inst.total_cost(slots), rel=1e-5)


@pytest.mark.parametrize("name,make", ALL_INSTANCES)
@pytest.mark.parametrize("n_req", [1, 5, 37])
def test_device_total_cost_prices_only_requested_rows(name, make, n_req):
    """An observed window requests a few objects: the device cost prices
    only that support (padded to a power of two with zero-rate rows) and
    still equals the host cost over the whole catalog."""
    inst = make()
    rng = np.random.default_rng(n_req)
    lam = np.zeros_like(inst.dem.lam)
    req = rng.choice(inst.cat.n, n_req, replace=False)
    lam[:, req] = rng.random((lam.shape[0], n_req)) + 0.1
    sparse = Instance(net=inst.net, cat=inst.cat,
                      dem=demand.Demand(lam=lam / lam.sum()))
    slots = random_slots(sparse, rng)
    for materialize in (True, False):
        dinst = DeviceInstance.from_instance(sparse,
                                             materialize_ca=materialize)
        assert dinst.total_cost(slots) == pytest.approx(
            sparse.total_cost(slots), rel=1e-5), (name, materialize)


# ------------------------------------------------------- ties and gain_tol
def test_gain_tol_near_ties_resolve_by_index():
    """gain_tol regression (host oracle honesty): duplicated catalog
    points produce *exactly* tied candidate gains; every path — host
    lazy, host eager, device — must resolve them to the lowest (o', j)
    flat index, and a gain_tol above the best gain must leave all slots
    empty everywhere."""
    rng = np.random.default_rng(0)
    base = rng.uniform(0, 4, size=(12, 3)).astype(np.float32)
    coords = np.concatenate([base, base[:4]])          # exact duplicates
    cat = catalog.Catalog(coords=coords, metric="l2")
    net = topology.tandem(k_leaf=3, k_parent=3, h=0.5, h_repo=5.0)
    lam = np.concatenate([rng.random(12) + 0.05,
                          (rng.random(4) + 0.05)])[None, :]
    inst = Instance(net=net, cat=cat,
                    dem=demand.Demand(lam=lam / lam.sum()))
    lazy = greedy(inst, lazy=True)
    eager = greedy(inst, lazy=False)
    dev = device_greedy(DeviceInstance.from_instance(inst))
    np.testing.assert_array_equal(lazy, eager)
    np.testing.assert_array_equal(lazy, dev)
    placed = lazy[lazy >= 0]
    # a duplicate pair's gains tie exactly → the lower id must win
    assert not np.any(placed >= 12), placed
    # gain_tol above every gain: nothing is ever placed, on any path
    cur = np.repeat(inst.net.h_repo[:, None].astype(np.float64),
                    inst.cat.n, axis=1)
    big = float(inst.add_gain_all(cur).max()) + 1.0
    for slots in (greedy(inst, lazy=True, gain_tol=big),
                  greedy(inst, lazy=False, gain_tol=big),
                  device_greedy(DeviceInstance.from_instance(inst),
                                gain_tol=big)):
        assert np.all(slots == -1)
