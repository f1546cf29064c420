"""Runtime similarity-cache network: lookup → forward → serve.

This is the *online data plane* for an allocation produced by the
placement algorithms (the paper's offline control plane). A
:class:`SimCacheNetwork` holds, per cache level, the stored object
embeddings ("keys") and opaque payload ids ("values" — e.g. a response
blob or a KV-prefix handle in the serving engine).

``lookup`` realizes eq. (1): every request is served by the approximizer
minimizing C_a(o, o') + h(i, j) over the caches on its path plus the
repository — the paper's optimal-forwarding assumption. The default
(``fused=True``) path concatenates every level's keys into one segmented
tensor with per-key additive cost offsets and answers the network-wide
query with a *single* Pallas kernel launch (the repository rides along
as a virtual key), so a batch lookup is one jitted pallas_call with no
per-level Python loop, host-side stack, or argmin. ``fused=False`` keeps
the original per-level probe (one KNN kernel per level, minima compared
centrally) as the differential-testing reference.

``sharded=True`` (with a ``mesh``) is the SPMD variant of the fused
path for catalogs too large for one device: :meth:`sharded_layout` pads
the segmented tensor so the key axis divides the shard count and
shard_map partitions it into contiguous balanced chunks, one per device
along ``shard_axes``. Each shard runs the *same* fused kernel over only
its resident keys (``fold_repo=False``), and the per-shard minima — five
scalars per query per shard — are gathered and reduced lexicographically
(min cost, ties to the lowest shard, i.e. the lowest concatenated index)
with the repository folded once after the reduction, so the result is
bit-identical to the single-device fused lookup. Queries are replicated;
only the O(B·n_shards) minima cross devices, never the key tensor. The
same memoization contract applies: mutating ``levels`` requires
:meth:`invalidate_layout`, which drops both the fused and the sharded
layouts.

``lookup(prune="lsh"|"kmeans")`` puts a candidate pre-filter
(kernels.knn.lsh) in front of the fused scan: the query batch is hashed
against memoized SimHash / k-means-routing tables, the batch union of
candidate rows is gathered into one compact padded index tensor, and
the *same* fused kernel runs over only those rows — per shard of the
balanced contiguous ``sharded_layout`` when ``sharded=True``, with
``reduce_shard_minima`` and the tie-break order untouched.
``verify=True`` re-scans every query whose pruned cost reaches the
returned un-scanned-h bound through the exact path, making the result
bit-identical to the exact fused lookup by construction (the verifier
contract in kernels/knn/lsh.py). Tables are memoized next to the
layouts; unlike the plain fused path, a pruned lookup against mutated
but not invalidated ``levels`` raises instead of serving stale
candidates.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracecount
from repro.kernels.knn import (default_policy, fused_lookup,
                               mesh_axes_size, nearest_approximizer,
                               pad_to_shards, pruned_fused_lookup,
                               quantized_fused_lookup,
                               sharded_fused_lookup,
                               sharded_pruned_fused_lookup,
                               sharded_quantized_fused_lookup,
                               stack_shard_tables)
from repro.kernels.knn.ops import DEFAULT_TOP_T

REPO_LEVEL = -1

# Empty-level sentinel coordinate: far enough that a sentinel can never
# undercut the repository, small enough that its *squared* l2 distance
# (~1e30) stays finite in f32 — the old 1e30 sentinel overflowed l2sq to
# inf (and could reach NaN via inf−inf in the dot-product expansion).
# The fused kernel additionally masks sentinel keys explicitly via the
# valid flag (payload == −1 semantics), so it never relies on magnitude.
SENTINEL_COORD = 1e15


@dataclasses.dataclass
class CacheLevel:
    keys: jax.Array           # (k_j, d) stored object embeddings
    values: jax.Array         # (k_j,) payload ids (int32)
    h: float                  # retrieval cost from the ingress


@dataclasses.dataclass
class LookupResult:
    level: jax.Array          # (B,) serving level per request (−1 = repo)
    slot: jax.Array           # (B,) slot within level (undefined for repo)
    payload: jax.Array        # (B,) payload id (−1 for repo)
    cost: jax.Array           # (B,) total C(r, A) incurred
    approx_cost: jax.Array    # (B,) C_a component only
    hit: jax.Array            # (B,) bool, served by some cache


@dataclasses.dataclass
class SimCacheNetwork:
    """A chain of similarity caches in front of a repository (model).

    ``sharded=True`` serves lookups with the mesh-sharded fused path:
    ``mesh`` must be set and the key axis is partitioned over
    ``shard_axes`` (default: every mesh axis, in order).
    """
    levels: list[CacheLevel]
    h_repo: float
    metric: str = "l2"
    gamma: float = 1.0
    use_pallas: bool = True
    fused: bool = True
    sharded: bool = False
    mesh: jax.sharding.Mesh | None = None
    shard_axes: tuple[str, ...] | None = None
    # CandidatePolicy override, used only when its ``kind`` matches the
    # ``prune=`` argument of lookup(); other kinds fall back to
    # kernels.knn.lsh.default_policy so one network can still serve both
    # pruning families side by side.
    candidate_policy: object | None = None
    _layout: tuple | None = dataclasses.field(
        default=None, init=False, repr=False, compare=False)
    _layout_fp: tuple | None = dataclasses.field(
        default=None, init=False, repr=False, compare=False)
    _sharded_layout: dict = dataclasses.field(
        default_factory=dict, init=False, repr=False, compare=False)
    _tables: dict = dataclasses.field(
        default_factory=dict, init=False, repr=False, compare=False)

    def __post_init__(self):
        if self.sharded and self.mesh is None:
            raise ValueError("sharded=True requires a mesh")

    @classmethod
    def from_placement(cls, coords: np.ndarray, slots: np.ndarray,
                       slot_cache: np.ndarray, hs: Sequence[float],
                       h_repo: float, metric: str = "l2",
                       gamma: float = 1.0, use_pallas: bool = True,
                       fused: bool = True, sharded: bool = False,
                       mesh: jax.sharding.Mesh | None = None,
                       shard_axes: tuple[str, ...] | None = None,
                       candidate_policy: object | None = None
                       ) -> "SimCacheNetwork":
        """Build the runtime network from a placement-algorithm output.

        ``slots``/``slot_cache`` are the flat allocation of
        objective.Instance; ``coords`` the catalog embeddings. Payload id
        = object id (the serving engine maps ids to artifacts).
        """
        levels = []
        for j, h in enumerate(hs):
            idx = slots[slot_cache == j]
            idx = idx[idx >= 0]
            if idx.size == 0:           # empty cache level still valid
                keys = np.full((1, coords.shape[1]), SENTINEL_COORD,
                               np.float32)     # unreachable sentinel key
                vals = np.full((1,), -1, np.int32)
            else:
                keys = coords[idx].astype(np.float32)
                vals = idx.astype(np.int32)
            levels.append(CacheLevel(keys=jnp.asarray(keys),
                                     values=jnp.asarray(vals),
                                     h=float(h)))
        return cls(levels=levels, h_repo=float(h_repo), metric=metric,
                   gamma=gamma, use_pallas=use_pallas, fused=fused,
                   sharded=sharded, mesh=mesh, shard_axes=shard_axes,
                   candidate_policy=candidate_policy)

    # ------------------------------------------------------- fused layout
    def fused_layout(self) -> tuple[jax.Array, jax.Array, jax.Array]:
        """Concatenated (keys, h_key, meta) over all levels, memoized.

        ``meta`` is (4, ΣK_j) i32 with rows (level, slot, payload,
        valid); sentinel entries of empty levels keep payload == −1 and
        valid == 0 so the kernel masks them explicitly.

        Memoized: mutating ``levels`` after the first lookup requires
        :meth:`invalidate_layout`, or the fused path keeps serving the
        stale concatenation.
        """
        if self._layout is None:
            keys, h_key, metas = [], [], []
            for j, lv in enumerate(self.levels):
                kj = lv.keys.shape[0]
                vals = np.asarray(lv.values, np.int32)
                keys.append(np.asarray(lv.keys, np.float32))
                h_key.append(np.full((kj,), lv.h, np.float32))
                metas.append(np.stack([
                    np.full((kj,), j, np.int32),
                    np.arange(kj, dtype=np.int32),
                    vals,
                    (vals >= 0).astype(np.int32),
                ]))
            d = self.levels[0].keys.shape[1] if self.levels else 1
            cat = (np.concatenate(keys, 0) if keys
                   else np.zeros((0, d), np.float32))
            hk = (np.concatenate(h_key) if h_key
                  else np.zeros((0,), np.float32))
            mt = (np.concatenate(metas, 1) if metas
                  else np.zeros((4, 0), np.int32))
            self._layout = (jnp.asarray(cat), jnp.asarray(hk),
                            jnp.asarray(mt))
            self._layout_fp = self._levels_fingerprint()
        return self._layout

    # ----------------------------------------------------- sharded layout
    def resolved_shard_axes(self) -> tuple[str, ...]:
        """Mesh axes the key axis shards over (default: all, in order)."""
        if self.shard_axes is not None:
            return tuple(self.shard_axes)
        return tuple(self.mesh.axis_names)

    def n_shards(self) -> int:
        return mesh_axes_size(self.mesh, self.resolved_shard_axes())

    def sharded_layout(self, n_shards: int
                       ) -> tuple[jax.Array, jax.Array, jax.Array]:
        """Fused layout padded so the key axis divides ``n_shards``.

        Padding keys (kernels.knn.pad_to_shards) are all-zero with
        valid == 0 / payload == −1 — masked explicitly by the kernel, so
        shards stay *balanced* (equal contiguous chunks of the
        level-ordered concatenation) without perturbing any distance.
        Memoized per shard count; the same :meth:`invalidate_layout`
        contract applies.
        """
        if n_shards not in self._sharded_layout:
            self._sharded_layout[n_shards] = pad_to_shards(
                *self.fused_layout(), n_shards)
        return self._sharded_layout[n_shards]

    def invalidate_layout(self) -> None:
        """Drop the memoized fused + sharded layouts (and the candidate
        pruning tables built from them) after mutating ``levels``."""
        self._layout = None
        self._layout_fp = None
        self._sharded_layout = {}
        self._tables = {}

    # -------------------------------------------------- candidate tables
    def _levels_fingerprint(self) -> tuple:
        """Identity of the current ``levels`` content: the array objects
        themselves (strong references — compared with ``is``, and their
        liveness makes id/slot reuse impossible) plus the h costs, so
        pruned lookups can detect a mutation that was not followed by
        :meth:`invalidate_layout`."""
        return tuple((lv.keys, lv.values, float(lv.h))
                     for lv in self.levels)

    @staticmethod
    def _fingerprints_match(a: tuple | None, b: tuple) -> bool:
        return a is not None and len(a) == len(b) and all(
            ak is bk and av is bv and ah == bh
            for (ak, av, ah), (bk, bv, bh) in zip(a, b))

    def _check_layout_fresh(self) -> None:
        if self._layout is not None and not self._fingerprints_match(
                self._layout_fp, self._levels_fingerprint()):
            raise RuntimeError(
                "stale candidate tables: `levels` were mutated after the "
                "fused layout (and the LSH/k-means tables indexing it) "
                "were built — call invalidate_layout() before a pruned "
                "lookup. The un-pruned paths serve the stale layout "
                "verbatim (documented memoization contract); pruning "
                "refuses, rather than returning candidates into a layout "
                "that no longer exists.")

    def _resolve_policy(self, prune: str):
        pol = self.candidate_policy
        if pol is not None and getattr(pol, "kind", None) == prune:
            return pol
        return default_policy(prune)

    def _tables_for(self, policy, n_shards: int
                    ) -> tuple[jax.Array, jax.Array, int]:
        """Memoized (proj, buckets, n_probes) for one policy: built over
        the fused layout (``n_shards == 0``) or per contiguous balanced
        shard chunk, stacked on a leading shard axis (``n_shards ≥ 1``).
        Dropped by :meth:`invalidate_layout` alongside the layouts."""
        memo_key = (policy, n_shards)
        if memo_key not in self._tables:
            if n_shards == 0:
                keys, _, meta = self.fused_layout()
                t = policy.build(np.asarray(keys),
                                 np.asarray(meta)[3] > 0)
                self._tables[memo_key] = (jnp.asarray(t.proj),
                                          jnp.asarray(t.buckets),
                                          t.n_probes)
            else:
                keys, _, meta = self.sharded_layout(n_shards)
                keys_np, meta_np = np.asarray(keys), np.asarray(meta)
                S = keys_np.shape[0] // n_shards
                ts = [policy.for_shard(s).build(
                    keys_np[s * S:(s + 1) * S],
                    meta_np[3, s * S:(s + 1) * S] > 0)
                    for s in range(n_shards)]
                proj_s, buckets_s, n_probes = stack_shard_tables(ts)
                self._tables[memo_key] = (jnp.asarray(proj_s),
                                          jnp.asarray(buckets_s),
                                          n_probes)
        return self._tables[memo_key]

    @tracecount.spanned("simcache.lookup")
    def lookup(self, queries: jax.Array, prune: str | None = None,
               verify: bool = False, quantize: bool = False,
               top_t: int | None = None) -> LookupResult:
        """Serve a batch of query embeddings (B, d) per eq. (1).

        Sharded (``sharded=True`` + mesh): one fused kernel per key
        shard + cross-shard lexicographic reduction — bit-identical to
        the fused path.
        Fused (default): one pallas_call over the segmented key tensor.
        Looped (``fused=False``): one KNN kernel per level + central
        argmin — kept as the reference for differential tests.
        Pruned (``prune="lsh"|"kmeans"``): candidate pre-filter in front
        of the fused/sharded scan; ``verify=True`` re-scans any query
        whose pruned cost reaches the un-scanned-h bound — bit-identical
        to the exact path by construction (kernels/knn/lsh.py).
        Quantized (``quantize=True``): int8 lower-bound first pass over
        the (possibly pruned) key rows selects the ``top_t`` candidates
        per query; only their batch union reaches the exact fused scan.
        The returned cost is exact for every query whose cost beats the
        per-query certificate bound; ``verify=True`` re-scans the rest,
        making the result bit-identical to the exact path by
        construction (kernels/quant.py admissibility). Composes with
        ``prune=`` (LSH gather first, quantized cut second) and with
        sharding.
        """
        if prune is not None:
            return self._lookup_pruned(queries, prune, verify,
                                       quantize=quantize, top_t=top_t)
        if quantize:
            return self._lookup_quantized(queries, verify, top_t)
        if self.sharded:
            return self._lookup_sharded(queries)
        if self.fused:
            return self._lookup_fused(queries)
        return self._lookup_looped(queries)

    def _lookup_fused(self, queries: jax.Array) -> LookupResult:
        keys, h_key, meta = self.fused_layout()
        cost, ca, lvl, slot, pay = fused_lookup(
            queries, keys, h_key, meta, metric=self.metric,
            gamma=self.gamma, h_repo=self.h_repo, repo_level=REPO_LEVEL,
            use_pallas=self.use_pallas)
        return LookupResult(level=lvl, slot=slot, payload=pay, cost=cost,
                            approx_cost=ca, hit=lvl != REPO_LEVEL)

    def _lookup_sharded(self, queries: jax.Array) -> LookupResult:
        if self.fused_layout()[0].shape[0] == 0:   # no keys → repository
            return self._lookup_fused(queries)
        n = self.n_shards()
        keys, h_key, meta = self.sharded_layout(n)
        cost, ca, lvl, slot, pay = sharded_fused_lookup(
            queries, keys, h_key, meta, self.mesh,
            self.resolved_shard_axes(), metric=self.metric,
            gamma=self.gamma, h_repo=self.h_repo, repo_level=REPO_LEVEL,
            use_pallas=self.use_pallas)
        return LookupResult(level=lvl, slot=slot, payload=pay, cost=cost,
                            approx_cost=ca, hit=lvl != REPO_LEVEL)

    def _quant_rows(self, n_shards: int):
        """Memoized int8 image (quant.QuantizedRows) of the fused
        (``n_shards == 0``) or sharded key rows — dropped alongside the
        layouts by :meth:`invalidate_layout`. All-zero padding rows
        quantize to scale 0.0 (the explicit guard in kernels/quant.py)
        and stay masked by their valid == 0 flag."""
        memo_key = ("quant_rows", n_shards)
        if memo_key not in self._tables:
            from repro.kernels import quant
            keys = (self.fused_layout() if n_shards == 0
                    else self.sharded_layout(n_shards))[0]
            self._tables[memo_key] = quant.quantize_rows(keys, self.metric)
        return self._tables[memo_key]

    def _lookup_quantized(self, queries: jax.Array, verify: bool,
                          top_t: int | None) -> LookupResult:
        self._check_layout_fresh()
        if self.fused_layout()[0].shape[0] == 0:   # no keys → repository
            return self._lookup_fused(queries)
        tt = DEFAULT_TOP_T if top_t is None else int(top_t)
        if self.sharded:
            n = self.n_shards()
            keys, h_key, meta = self.sharded_layout(n)
            out = sharded_quantized_fused_lookup(
                queries, keys, h_key, meta, self._quant_rows(n), self.mesh,
                self.resolved_shard_axes(), top_t=tt, metric=self.metric,
                gamma=self.gamma, h_repo=self.h_repo,
                repo_level=REPO_LEVEL, use_pallas=self.use_pallas)
        else:
            keys, h_key, meta = self.fused_layout()
            out = quantized_fused_lookup(
                queries, keys, h_key, meta, self._quant_rows(0), top_t=tt,
                metric=self.metric, gamma=self.gamma, h_repo=self.h_repo,
                repo_level=REPO_LEVEL, use_pallas=self.use_pallas)
        cost, ca, lvl, slot, pay, bound = out
        res = LookupResult(level=lvl, slot=slot, payload=pay, cost=cost,
                           approx_cost=ca, hit=lvl != REPO_LEVEL)
        if not verify:
            return res
        return self._verify_rescan(queries, res, bound)

    def _lookup_pruned(self, queries: jax.Array, prune: str,
                       verify: bool, quantize: bool = False,
                       top_t: int | None = None) -> LookupResult:
        policy = self._resolve_policy(prune)
        self._check_layout_fresh()
        if self.fused_layout()[0].shape[0] == 0:   # no keys → repository
            return self._lookup_fused(queries)
        tt = DEFAULT_TOP_T if top_t is None else int(top_t)
        if self.sharded:
            n = self.n_shards()
            keys, h_key, meta = self.sharded_layout(n)
            proj, buckets, n_probes = self._tables_for(policy, n)
            cost, ca, lvl, slot, pay, bound = sharded_pruned_fused_lookup(
                queries, keys, h_key, meta, proj, buckets, self.mesh,
                self.resolved_shard_axes(), kind=policy.kind,
                n_probes=n_probes,
                cap_union=policy.resolve_cap(keys.shape[0] // n),
                metric=self.metric, gamma=self.gamma, h_repo=self.h_repo,
                repo_level=REPO_LEVEL, use_pallas=self.use_pallas,
                quantize=quantize, top_t=tt)
        else:
            keys, h_key, meta = self.fused_layout()
            proj, buckets, n_probes = self._tables_for(policy, 0)
            cost, ca, lvl, slot, pay, bound = pruned_fused_lookup(
                queries, keys, h_key, meta, proj, buckets,
                kind=policy.kind, n_probes=n_probes,
                cap_union=policy.resolve_cap(keys.shape[0]),
                metric=self.metric, gamma=self.gamma, h_repo=self.h_repo,
                repo_level=REPO_LEVEL, use_pallas=self.use_pallas,
                quantize=quantize, top_t=tt)
        res = LookupResult(level=lvl, slot=slot, payload=pay, cost=cost,
                           approx_cost=ca, hit=lvl != REPO_LEVEL)
        if not verify:
            return res
        return self._verify_rescan(queries, res, bound)

    def _verify_rescan(self, queries: jax.Array, res: LookupResult,
                       bound: jax.Array) -> LookupResult:
        # verifier: cost < bound proves the pruned/quantized winner exact
        # (every un-scanned valid key costs ≥ bound); anything else —
        # including exact ties, whose break could prefer an un-scanned
        # lower index — re-scans through the exact fused/sharded path.
        # Only the flagged queries re-scan (per-query kernel rows are
        # independent, so a sub-batch is bitwise the full batch's rows),
        # padded to a power of two so repeated verify calls reuse a
        # handful of compiled exact-scan shapes instead of one per
        # flagged count. ``bound`` is a scalar for the LSH path (the
        # un-scanned-h floor) and per-query (B,) for the quantized cut
        # (each query's top-T certificate) — the broadcast compare covers
        # both.
        lvl, slot = res.level, res.slot
        pay, cost, ca = res.payload, res.cost, res.approx_cost
        idx = np.nonzero(np.asarray(cost >= bound))[0]
        if idx.size == 0:
            return res
        m = 1
        while m < idx.size:
            m <<= 1
        m = min(m, queries.shape[0])
        pad_idx = np.concatenate(
            [idx, np.zeros(m - idx.size, idx.dtype)]).astype(np.int32)
        exact = (self._lookup_sharded(queries[jnp.asarray(pad_idx)])
                 if self.sharded
                 else self._lookup_fused(queries[jnp.asarray(pad_idx)]))
        jidx = jnp.asarray(idx.astype(np.int32))
        put = lambda dst, src: dst.at[jidx].set(    # noqa: E731
            src[:idx.size])
        lvl2 = put(lvl, exact.level)
        return LookupResult(
            level=lvl2, slot=put(slot, exact.slot),
            payload=put(pay, exact.payload),
            cost=put(cost, exact.cost),
            approx_cost=put(ca, exact.approx_cost),
            hit=lvl2 != REPO_LEVEL)

    def _lookup_looped(self, queries: jax.Array) -> LookupResult:
        B = queries.shape[0]
        costs, slots_, pays, appr = [], [], [], []
        for lv in self.levels:
            ca, idx = nearest_approximizer(
                queries, lv.keys, metric=self.metric, gamma=self.gamma,
                use_pallas=self.use_pallas)
            costs.append(ca + lv.h)
            appr.append(ca)
            slots_.append(idx)
            pays.append(lv.values[idx])
        # repository: zero approximation cost, fixed h_repo
        costs.append(jnp.full((B,), self.h_repo, jnp.float32))
        appr.append(jnp.zeros((B,), jnp.float32))
        slots_.append(jnp.zeros((B,), jnp.int32))
        pays.append(jnp.full((B,), -1, jnp.int32))

        call = jnp.stack(costs)                       # (L+1, B)
        best = jnp.argmin(call, axis=0)               # metadata probe
        n_lv = len(self.levels)
        level = jnp.where(best == n_lv, REPO_LEVEL, best).astype(jnp.int32)
        take = lambda xs: jnp.take_along_axis(          # noqa: E731
            jnp.stack(xs), best[None, :], axis=0)[0]
        return LookupResult(
            level=level, slot=take(slots_), payload=take(pays),
            cost=take(costs), approx_cost=take(appr),
            hit=level != REPO_LEVEL)

    def expected_cost(self, queries: jax.Array,
                      weights: jax.Array | None = None) -> float:
        """Empirical C(A) over a query sample (eq. (2) estimator)."""
        res = self.lookup(queries)
        if weights is None:
            return float(jnp.mean(res.cost))
        return float(jnp.sum(weights * res.cost) / jnp.sum(weights))
