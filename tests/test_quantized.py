"""Differential suite for the int8 quantized first-pass lookup
(kernels/quant.py + kernels/knn/ops.quantized_fused_lookup) in front of
the fused segmented-1-NN scan.

Three requirements, mirroring test_lsh_pruning.py's structure:

  * **exactness** — ``lookup(quantize=True, verify=True)`` re-scans
    every query whose winning cost reaches the per-query vT certificate
    and must be **bit-identical** to the exact fused path on every
    covered configuration: all metrics, γ ≠ 1, B = 1 and multi-tile
    batches, tiny and full-width top_t, single-device and sharded, and
    composed with LSH pruning;
  * **admissibility** — the unverified quantized lookup scans exact
    costs only over its top-T candidate union, so its winning cost can
    never be *below* the exact cost, and a top_t covering every key
    makes the first pass a pure re-indexing (bit-exact, bound +INF);
  * **oracle** — the jitted entry and the pure-jnp reference
    (quantized_fused_lookup_ref) agree on winners/costs/bound, one-way
    and shard-chunked.

The 10⁶-key quantized+pruned+sharded differential is CI_FULL-gated
(scripts/ci.sh full pass); the 8-way mesh tests run in ci.sh pass 2
under XLA_FLAGS=--xla_force_host_platform_device_count=8.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import assert_results_equal, make_net

from repro.kernels import quant
from repro.kernels.knn import (SimHashPolicy, quantized_fused_lookup,
                               quantized_fused_lookup_ref,
                               sharded_quantized_fused_lookup_ref)
from repro.launch.mesh import make_mesh

EIGHT = jax.device_count() >= 8

CONFIGS = [
    (0, [5, 9, 3], [0.0, 0.5, 1.0], 2.0, 23),
    (1, [17, 2, 31, 8], [0.0, 0.2, 0.7, 1.3], 3.0, 1),       # B=1
    (5, [200, 150, 250], [0.0, 0.4, 0.8], 2.5, 700),   # 3 query tiles
]


# ------------------------------------------------------------- exactness
@pytest.mark.parametrize("metric,gamma", [("l2", 1.0), ("l1", 1.0),
                                          ("l2sq", 1.0), ("l2", 2.0)])
@pytest.mark.parametrize("top_t", [2, 16])
def test_quantized_verify_bit_identical(metric, gamma, top_t):
    """verify=True must reproduce the exact fused path bit-for-bit,
    whatever the int8 ranks missed at this rescore width — covering B=1
    and a 700-query multi-tile batch."""
    for seed, sizes, hs, h_repo, nq in CONFIGS:
        net, rng = make_net(seed, sizes, hs, h_repo, metric, gamma)
        q = jnp.asarray((rng.standard_normal((nq, 6)) * 2)
                        .astype(np.float32))
        res = net.lookup(q, quantize=True, verify=True, top_t=top_t)
        assert_results_equal(res, net._lookup_fused(q),
                             exact_cost=gamma == 1.0)


def test_quantized_verify_bit_identical_sharded():
    """Same contract through the mesh-sharded data plane (per-shard
    QuantizedRows + fold_repo=False launches + per-query min of the
    per-shard vT bounds)."""
    mesh = make_mesh((1,), ("data",))
    net, rng = make_net(1, [17, 2, 31, 8], [0.0, 0.2, 0.7, 1.3], 3.0)
    snet, _ = make_net(1, [17, 2, 31, 8], [0.0, 0.2, 0.7, 1.3], 3.0,
                       sharded=True, mesh=mesh)
    q = jnp.asarray((rng.standard_normal((23, 6)) * 2).astype(np.float32))
    res = snet.lookup(q, quantize=True, verify=True, top_t=4)
    assert_results_equal(res, net._lookup_fused(q))
    assert_results_equal(res, snet.lookup(q))


def test_quantized_composes_with_lsh_pruning():
    """quantize=True under prune="lsh" sub-cuts the LSH candidate union
    with the int8 ranks; verify=True still closes both gaps to 0."""
    pol = SimHashPolicy(n_tables=2, n_bits=4, n_probes=2)
    net, rng = make_net(9, [100, 300], [0.2, 0.8], 3.0,
                        candidate_policy=pol)
    q = jnp.asarray(rng.standard_normal((32, 6)).astype(np.float32))
    exact = net._lookup_fused(q)
    res = net.lookup(q, prune="lsh", verify=True, quantize=True, top_t=8)
    assert_results_equal(res, exact)
    # unverified composition stays admissible
    got = net.lookup(q, prune="lsh", quantize=True, top_t=8)
    assert np.all(np.asarray(got.cost) >= np.asarray(exact.cost))


def test_quantized_full_width_equals_exact_without_verify():
    """top_t ≥ n_keys keeps every key in the rescore union: the first
    pass is a pure re-indexing of the exact scan — bit-identical even
    with verify=False, and the certificate is +INF (nothing cut)."""
    net, rng = make_net(2, [64, 64], [0.0, 1.0], 5.0)
    q = jnp.asarray((rng.standard_normal((23, 6)) * 2).astype(np.float32))
    assert_results_equal(net.lookup(q, quantize=True, top_t=4096),
                         net._lookup_fused(q))
    keys, h_key, meta = net.fused_layout()
    *_, bound = quantized_fused_lookup_ref(q, keys, h_key, meta,
                                           top_t=int(keys.shape[0]),
                                           h_repo=5.0)
    assert np.all(np.asarray(bound) >= 1e38)


# ---------------------------------------------------------- admissibility
@pytest.mark.parametrize("metric,gamma", [("l2", 1.0), ("l1", 0.7),
                                          ("l2sq", 1.0), ("l2", 2.0)])
def test_quantized_unverified_admissible(metric, gamma):
    """Without verification the quantized winner can only be *worse*
    (cost ≥ exact): the exact rescore runs over a subset of the keys,
    and the lower-bound cut is certified for every pair."""
    net, rng = make_net(3, [80, 120, 60], [0.0, 0.4, 0.9], 2.5, metric,
                        gamma)
    q = jnp.asarray((rng.standard_normal((64, 6)) * 2).astype(np.float32))
    exact = net._lookup_fused(q)
    for tt in (1, 4, 32):
        got = net.lookup(q, quantize=True, top_t=tt)
        assert np.all(np.asarray(got.cost) >= np.asarray(exact.cost)), tt
        assert np.all(np.asarray(got.cost) <= net.h_repo + 1e-6)


def test_quantized_certificate_is_honest():
    """Queries whose unverified cost already beats the vT certificate
    provably hold the exact winner — those rows must be bitwise the
    exact result even with verify=False."""
    net, rng = make_net(4, [150, 90], [0.0, 0.6], 3.0)
    q = jnp.asarray((rng.standard_normal((64, 6)) * 2).astype(np.float32))
    exact = net._lookup_fused(q)
    keys, h_key, meta = net.fused_layout()
    out = quantized_fused_lookup(q, keys, h_key, meta,
                                 net._quant_rows(0), top_t=4,
                                 metric=net.metric, gamma=net.gamma,
                                 h_repo=net.h_repo,
                                 use_pallas=net.use_pallas)
    cost, ac, level, slot, payload, bound = out
    safe = np.asarray(cost) < np.asarray(bound)
    assert safe.any()                 # the cut certifies some rows
    for got, want in [(cost, exact.cost), (ac, exact.approx_cost),
                      (level, exact.level), (slot, exact.slot),
                      (payload, exact.payload)]:
        np.testing.assert_array_equal(np.asarray(got)[safe],
                                      np.asarray(want)[safe])


# ------------------------------------------------------ ops — ref oracle
def test_quantized_ops_matches_ref_oracle():
    """The jitted entry and the pure-jnp oracle run the same first-pass
    selection and the same exact rescore: same winners, costs to 1e-6,
    bounds to 1-ulp (jit CSE can re-associate the lb scores)."""
    net, rng = make_net(7, [40, 25], [0.0, 0.4], 2.0, "l2", gamma=2.0)
    q = jnp.asarray(rng.standard_normal((19, 6)).astype(np.float32))
    keys, h_key, meta = net.fused_layout()
    kq = quant.quantize_rows(keys, "l2")
    out_k = quantized_fused_lookup(q, keys, h_key, meta, kq, top_t=8,
                                   metric="l2", gamma=2.0, h_repo=2.0)
    out_r = quantized_fused_lookup_ref(q, keys, h_key, meta, kq=kq,
                                       top_t=8, metric="l2", gamma=2.0,
                                       h_repo=2.0)
    for a, b in zip(out_k, out_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


def test_quantized_sharded_ref_matches_one_way():
    """Chunked-oracle consistency: per-row quantization makes an S-chunk
    scan of the int8 image equivalent to the one-way scan + per-query
    min of the chunk certificates."""
    net, rng = make_net(8, [60, 45, 30], [0.0, 0.3, 0.9], 2.5)
    q = jnp.asarray(rng.standard_normal((17, 6)).astype(np.float32))
    keys, h_key, meta = net.fused_layout()
    one = quantized_fused_lookup_ref(q, keys, h_key, meta, top_t=6,
                                     h_repo=2.5)
    for s in (2, 4):
        chk = sharded_quantized_fused_lookup_ref(q, keys, h_key, meta, s,
                                                 top_t=6, h_repo=2.5)
        # winners/costs must be admissible vs the one-way oracle: each
        # chunk rescoring its own top-6 can only widen the union
        assert np.all(np.asarray(chk[0]) <= np.asarray(one[0]) + 1e-6)
        assert np.all(np.asarray(chk[0])
                      >= np.asarray(net._lookup_fused(q).cost) - 1e-6)


# --------------------------------------------------------------- plumbing
def test_quant_rows_memo_and_invalidation():
    """The plain quantized path memoizes QuantizedRows per layout;
    invalidate_layout() drops them with the other tables."""
    net, rng = make_net(11, [50, 80], [0.2, 0.8], 3.0)
    q = jnp.asarray(rng.standard_normal((8, 6)).astype(np.float32))
    net.lookup(q, quantize=True)
    assert any(k[0] == "quant_rows" for k in net._tables)
    net.lookup(q, quantize=True)
    assert sum(k[0] == "quant_rows" for k in net._tables) == 1   # a hit
    net.invalidate_layout()
    assert not net._tables


# ------------------------------------------------------------------- mesh
@pytest.mark.skipif(not EIGHT, reason="needs 8 devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
def test_quantized_eight_way_differential():
    mesh = make_mesh((8,), ("data",))
    for seed, sizes, hs, h_repo, nq in CONFIGS:
        net, rng = make_net(seed, sizes, hs, h_repo)
        snet, _ = make_net(seed, sizes, hs, h_repo, sharded=True,
                           mesh=mesh)
        q = jnp.asarray((rng.standard_normal((nq, 6)) * 2)
                        .astype(np.float32))
        res = snet.lookup(q, quantize=True, verify=True, top_t=4)
        assert_results_equal(res, net._lookup_fused(q))


@pytest.mark.skipif(not EIGHT, reason="needs 8 devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
def test_quantized_plus_lsh_eight_way():
    mesh = make_mesh((8,), ("data",))
    net, rng = make_net(5, [200, 150, 250], [0.0, 0.4, 0.8], 2.5)
    snet, _ = make_net(5, [200, 150, 250], [0.0, 0.4, 0.8], 2.5,
                       sharded=True, mesh=mesh)
    q = jnp.asarray((rng.standard_normal((300, 6)) * 2).astype(np.float32))
    res = snet.lookup(q, prune="lsh", quantize=True, verify=True, top_t=8)
    assert_results_equal(res, net._lookup_fused(q))
