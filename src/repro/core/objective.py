"""Problem instance + vectorized evaluation of the paper's objective.

Implements eqs. (1)–(4):

    C(r, A) = min_{α ∈ A ∪ S} C(r, α)          (1)
    C(A)    = Σ_r λ_r C(r, A)                   (2) discrete case
    G(A)    = C(∅) − C(A)                       caching gain (§3.1)

An *allocation* is a flat int64 vector ``slots`` of length
``net.total_slots`` holding object ids (−1 = empty slot); slot ``s``
belongs to cache ``net.slot_layout()[s]``. This fixed layout makes the
matroid constraint (Prop 3.2 / Appendix A) trivially satisfied by
construction and maps 1:1 onto device-resident cache shards.

Requests are the pairs (ingress i, object o) with rate ``dem.lam[i, o]``;
the request space equals the catalog (O_R = O), as in the paper's
experiments.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

from repro.core.catalog import Catalog
from repro.core.demand import Demand
from repro.core.topology import CacheNetwork

INF = np.float32(np.inf)

# past this catalog size the dense (O, O) C_a matrix is never built:
# the host oracle streams row/column blocks and the device twin streams
# distance tiles (kernels/knn/gains.py)
CA_MATERIALIZE_MAX = 16384


@dataclasses.dataclass(frozen=True)
class Instance:
    """A similarity-caching placement problem instance (discrete case).

    ``ca_matrix`` optionally supplies an explicit approximation-cost
    matrix (the paper's first instance, §2); otherwise C_a is derived
    from catalog coordinates (metric^γ).
    """
    net: CacheNetwork
    cat: Catalog
    dem: Demand
    ca_matrix: np.ndarray | None = None

    def __post_init__(self):
        assert self.dem.n_ingress == self.net.n_ingress
        assert self.dem.n_objects == self.cat.n
        if self.ca_matrix is not None:
            assert self.ca_matrix.shape == (self.cat.n, self.cat.n)

    @functools.cached_property
    def ca(self) -> np.ndarray:
        """Full (O, O) approximation-cost matrix (float32, cached)."""
        return self.cat.ca() if self.ca_matrix is None else self.ca_matrix

    @functools.cached_property
    def slot_cache(self) -> np.ndarray:
        return self.net.slot_layout()

    @functools.cached_property
    def lam(self) -> np.ndarray:
        return self.dem.lam

    # ---------------------------------------------------------------- eval
    def slot_costs(self, slots: np.ndarray) -> np.ndarray:
        """(I, O, K) cost of serving request (i, o) with slot s.

        cost[i, o, s] = C_a[o, slots[s]] + H[i, cache(s)]; +inf for empty
        slots and off-path caches.
        """
        K = slots.shape[0]
        ca_cols = np.where(slots[None, :] >= 0,
                           self.ca[:, np.maximum(slots, 0)], INF)   # (O, K)
        h = self.net.H[:, self.slot_cache]                           # (I, K)
        return ca_cols[None, :, :] + h[:, None, :]

    def best_two(self, slots: np.ndarray):
        """Per-request best/second-best over slots ∪ {repository}.

        Returns (best1, arg1, best2): arg1 is the slot index, or −1 when
        the repository is the best server. best2 likewise includes the
        repository as a candidate. Ties break to the *lowest slot index*
        (argmin semantics) — the contract shared bit-for-bit with the
        device twin (``DeviceInstance.best_two``), so host and device
        LOCALSWAP attribute corrections to the same slot.
        """
        c = self.slot_costs(slots)                                   # (I,O,K)
        a1 = np.argmin(c, axis=2)                                    # lowest s
        b1 = np.take_along_axis(c, a1[:, :, None], axis=2)[:, :, 0]
        masked = c.copy()
        np.put_along_axis(masked, a1[:, :, None], INF, axis=2)
        b2 = masked.min(axis=2)
        repo = self.net.h_repo[:, None].astype(np.float32)
        # fold the repository in as the always-available approximizer S
        best1 = np.minimum(b1, repo)
        arg1 = np.where(repo < b1, -1, a1)
        best2 = np.minimum(np.where(repo < b1, b1, b2), repo)
        return best1, arg1, best2

    def request_costs(self, slots: np.ndarray) -> np.ndarray:
        """C(r, A) for every request (I, O) — eq. (1)."""
        best1, _, _ = self.best_two(slots)
        return best1

    def total_cost(self, slots: np.ndarray) -> float:
        """Expected cost C(A) per unit rate — eq. (2)."""
        return float(np.sum(self.lam * self.request_costs(slots)))

    def empty_cost(self) -> float:
        """C(∅): every request served by its repository."""
        return float(np.sum(self.lam * self.net.h_repo[:, None]))

    def caching_gain(self, slots: np.ndarray) -> float:
        """G(A) = C(∅) − C(A) (§3.1); non-negative, monotone, submodular."""
        return self.empty_cost() - self.total_cost(slots)

    # ------------------------------------------------------------- greedy
    def _ca_col(self, obj: int) -> np.ndarray:
        """(O,) column C_a[:, obj] — cached-matrix view or on-the-fly."""
        if self.ca_matrix is not None or "ca" in self.__dict__ \
                or self.cat.n <= CA_MATERIALIZE_MAX:
            return self.ca[:, obj]
        return self.cat.ca(cols=np.array([obj]))[:, 0]

    def add_gain_single(self, cur: np.ndarray, obj: int, cache: int) -> float:
        """Marginal gain of adding approximizer (obj, cache) given current
        per-request costs ``cur`` (I, O):  Σ_r λ_r·relu(cur_r − C(r, α))."""
        newc = self._ca_col(obj)[None, :] + self.net.H[:, cache][:, None]
        return float(np.sum(self.lam * np.maximum(cur - newc, 0.0)))

    def _ca_rows(self, rows: np.ndarray | slice) -> np.ndarray:
        """(len(rows), O) block of C_a — a view of the cached matrix when
        it exists (or is small enough to build), computed on the fly
        otherwise. ``CA_MATERIALIZE_MAX`` keeps the honest-oracle path
        usable at catalog sizes where a dense (O, O) C_a cannot exist."""
        if self.ca_matrix is not None or "ca" in self.__dict__ \
                or self.cat.n <= CA_MATERIALIZE_MAX:
            return self.ca[rows]
        idx = np.arange(self.cat.n)[rows] if isinstance(rows, slice) else rows
        return self.cat.ca(rows=idx)

    def add_gain_all(self, cur: np.ndarray, block: int = 2048) -> np.ndarray:
        """(O, J) marginal gain for every candidate approximizer.

        gain[o', j] = Σ_{i,o} λ[i,o]·relu(cur[i,o] − H[i,j] − C_a[o, o']),
        computed in O-row blocks to bound the (O×O) temporary; each C_a
        row block is fetched once and reused across every (ingress,
        cache) pair (on-the-fly for catalogs past ``CA_MATERIALIZE_MAX``,
        where the dense matrix cannot be cached). This is the host
        differential oracle of the device gain kernel
        (kernels/knn/gains.py; kernels/gain/ref.py is the single-ingress
        jnp flavor).
        """
        O, J = self.cat.n, self.net.n_caches
        gain = np.zeros((O, J), dtype=np.float64)
        for s in range(0, O, block):
            blk = slice(s, s + block)
            ca_blk = self._ca_rows(blk)
            for i in range(self.net.n_ingress):
                for j in range(J):
                    h = self.net.H[i, j]
                    if not np.isfinite(h):
                        continue
                    a = cur[i, blk] - h                           # (b,)
                    m = np.maximum(a[:, None] - ca_blk, 0.0)
                    gain[:, j] += self.lam[i, blk] @ m
        return gain

    def add_gain_delta(self, cur_old: np.ndarray, cur_new: np.ndarray,
                       block: int = 2048) -> np.ndarray:
        """(O, J) change in :meth:`add_gain_all` when per-request costs
        drop from ``cur_old`` to ``cur_new`` (elementwise ≤).

        Only requests whose cost actually changed contribute, so one
        GREEDY pick (which improves the few requests near the new
        approximizer) updates the whole gain table in O(changed·O·J)
        instead of the eager path's full O(O²·J) recompute — the
        vectorized row-update reuse of ``updated_costs`` applied to the
        gain table itself.
        """
        O, J = self.cat.n, self.net.n_caches
        delta = np.zeros((O, J), dtype=np.float64)
        changed = cur_new < cur_old                               # (I, O)
        for i in range(self.net.n_ingress):
            idx = np.nonzero(changed[i])[0]
            if idx.size == 0:
                continue
            for s in range(0, idx.size, block):
                sel = idx[s:s + block]
                ca_blk = self._ca_rows(sel)
                a_new = cur_new[i, sel][:, None]
                a_old = cur_old[i, sel][:, None]
                lam_i = self.lam[i, sel]
                for j in range(J):
                    h = self.net.H[i, j]
                    if not np.isfinite(h):
                        continue
                    m = (np.maximum(a_new - h - ca_blk, 0.0)
                         - np.maximum(a_old - h - ca_blk, 0.0))
                    delta[:, j] += lam_i @ m
        return delta

    def updated_costs(self, cur: np.ndarray, obj: int, cache: int) -> np.ndarray:
        """cur after adding (obj, cache): min(cur, C_a[:,obj] + H[:,cache])."""
        newc = self._ca_col(obj)[None, :] + self.net.H[:, cache][:, None]
        return np.minimum(cur, newc)


# ===================================================================== device
# Device-resident twin of Instance: the placement control plane's state
# (per-request serving costs, slot layout, C_a access) lives on the
# accelerator and every oracle/update below is a jitted op, so
# GREEDY/LOCALSWAP (core/placement/device.py) never round-trips the
# O(O·J) gain grid through host NumPy. Two C_a modes:
#
#   * materialized — the host (O, O) matrix uploaded once (bit-identical
#     C_a entries to the host oracle; the small-instance fidelity mode);
#   * streaming    — distance tiles computed on the fly by the
#     kernels/knn/gains.py oracle (the only mode possible past
#     CA_MATERIALIZE_MAX, and the one that shards over a mesh).

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("metric", "gamma"))
def _ca_cols_device(coords, objs, metric: str, gamma: float):
    from repro.core import costs
    return costs.approx_cost_stable(coords, coords[objs], metric, gamma)


@functools.partial(jax.jit, static_argnames=("metric", "gamma", "has_ca"))
def _gain_at_device(coords, ca, lam, cur, H, objs, caches,
                    metric: str, gamma: float, has_ca: bool):
    """(k,) exact marginal gains of candidate pairs (objs[c], caches[c])
    given current costs ``cur`` (I, O) — the batched lazy-greedy refresh."""
    if has_ca:
        cac = ca[:, objs]                                      # (O, k)
    else:
        from repro.core import costs
        # shape-stable form: bitwise-consistent with _apply_pick_device,
        # so a candidate already folded into ``cur`` refreshes to an
        # exact-zero gain (no phantom f32 tail gains — see costs.py)
        cac = costs.approx_cost_stable(coords, coords[objs], metric, gamma)
    hsel = H[:, caches]                                        # (I, k)
    slack = cur[:, :, None] - cac[None, :, :] - hsel[:, None, :]
    return jnp.sum(lam[:, :, None] * jnp.maximum(slack, 0.0), axis=(0, 1))


@functools.partial(jax.jit, static_argnames=("metric", "gamma", "has_ca"))
def _apply_pick_device(coords, ca, H, cur, obj, cache,
                       metric: str, gamma: float, has_ca: bool):
    """cur ← min(cur, C_a[:, obj] + H[:, cache]) — incremental update."""
    if has_ca:
        col = ca[:, obj]
    else:
        from repro.core import costs
        col = costs.approx_cost_stable(coords, coords[obj][None, :],
                                       metric, gamma)[:, 0]
    newc = col[None, :] + H[:, cache][:, None]
    return jnp.minimum(cur, newc)


def _stable_ca_cols(x, keys, metric: str, gamma: float,
                    block: int = 16) -> jax.Array:
    """(R, K) shape-stable C_a against the slot keys, lax.map-blocked
    over slot chunks so the (R, block, D) broadcast temporary stays
    bounded at 10⁵-object catalogs. Per-pair values equal
    ``costs.approx_cost_stable`` at any batch shape by construction."""
    from repro.core import costs
    K, D = keys.shape
    pad = (-K) % block
    tiles = jnp.pad(keys, ((0, pad), (0, 0))).reshape(-1, block, D)
    out = jax.lax.map(
        lambda kt: costs.approx_cost_stable(x, kt, metric, gamma), tiles)
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], -1)[:, :K]


def _best_two_rows_pre(rows, keys, slots, slot_cache, H,
                       metric: str, gamma: float, has_ca: bool):
    """Pre-repo-fold best-two for a block of request rows: (b1, a1, b2,
    a2), all over *slots only* (the repo escape is folded separately by
    :func:`_fold_repo_rows`). The slot-index witnesses a1/a2 are what
    the incremental path (:func:`best_two_delta`) keys its dirty-row
    detection on — the fold erases a1 when the repo wins, so deltas must
    carry the pre-fold tables.

    ``rows`` is either a (R, O) block of C_a rows (``has_ca``) or the
    (R, D) request coordinates, with ``keys`` the (K, D) slot-key
    coordinates. Rows are independent, which is exactly what lets
    :func:`sharded_best_two_tables` shard_map this over the request axis
    with bit-identical per-row results. The coords mode uses the
    shape-stable distance form (costs.pairwise_distance_stable), so a
    table entry for pair (r, y) is bitwise the value every other
    incremental op (swap deltas, duel pricing, apply_pick) computes for
    that pair — the streamed control plane has one canonical C_a.
    """
    safe = jnp.maximum(slots, 0)
    if has_ca:
        d = rows[:, safe]                                      # (R, K)
    else:
        d = _stable_ca_cols(rows, keys, metric, gamma)
    ca_cols = jnp.where(slots[None, :] >= 0, d, jnp.inf)
    c = ca_cols[None, :, :] + H[:, slot_cache][:, None, :]     # (I, R, K)
    a1 = jnp.argmin(c, axis=2).astype(jnp.int32)
    b1 = jnp.take_along_axis(c, a1[:, :, None], axis=2)[:, :, 0]
    k_iota = jax.lax.broadcasted_iota(jnp.int32, c.shape, 2)
    masked = jnp.where(k_iota == a1[:, :, None], jnp.inf, c)
    b2 = jnp.min(masked, axis=2)
    a2 = jnp.argmin(masked, axis=2).astype(jnp.int32)
    return b1, a1, b2, a2


def _fold_repo_rows(b1, a1, b2, h_repo):
    """Fold the repo escape (cost h_repo, index -1) into pre-fold slot
    tables — exactly the historical tail of ``_best_two_rows``, so
    fold(pre) is bitwise the old fused computation."""
    repo = h_repo[:, None]
    best1 = jnp.minimum(b1, repo)
    arg1 = jnp.where(repo < b1, -1, a1).astype(jnp.int32)
    best2 = jnp.minimum(jnp.where(repo < b1, b1, b2), repo)
    return best1, arg1, best2


def _best_two_rows(rows, keys, slots, slot_cache, H, h_repo,
                   metric: str, gamma: float, has_ca: bool):
    """best1/arg1/best2 for a block of request rows — pre-fold tables
    (:func:`_best_two_rows_pre`) with the repo escape folded in."""
    b1, a1, b2, _ = _best_two_rows_pre(rows, keys, slots, slot_cache, H,
                                       metric, gamma, has_ca)
    return _fold_repo_rows(b1, a1, b2, h_repo)


@functools.partial(jax.jit, static_argnames=("metric", "gamma", "has_ca"))
def _best_two_device(coords, ca, slots, slot_cache, H, h_repo,
                     metric: str, gamma: float, has_ca: bool):
    """Device mirror of Instance.best_two — identical lowest-slot-index
    tie-break (jnp.argmin keeps the first minimum, like np.argmin)."""
    rows = ca if has_ca else coords
    keys = jnp.zeros((0, 0), jnp.float32) if has_ca \
        else coords[jnp.maximum(slots, 0)]
    return _best_two_rows(rows, keys, slots, slot_cache, H, h_repo,
                          metric, gamma, has_ca)


@functools.partial(jax.jit, static_argnames=("metric", "gamma", "has_ca",
                                             "mesh", "axes"))
def sharded_best_two(coords, ca, slots, slot_cache, H, h_repo, mesh,
                     axes: tuple, metric: str, gamma: float, has_ca: bool):
    """Mesh-sharded best1/arg1/best2: the request axis (the (I, O) cost
    tables' object dimension) is shard_mapped over ``axes`` — the same
    axes the data-plane keys shard over — with slot keys and topology
    replicated. Every request row is computed with the exact ops of
    :func:`_best_two_device`, so results are bit-identical at any shard
    count; this is the refresh kernel the online control plane
    (NETDUEL's promotion re-arm, the scanned LOCALSWAP) runs when a
    ``DeviceInstance`` carries mesh axes.
    """
    from jax.sharding import PartitionSpec as P

    from repro.kernels.knn.ops import _pad_axis, mesh_axes_size
    n_shards = mesh_axes_size(mesh, axes)
    n_obj = coords.shape[0] if not has_ca else ca.shape[0]
    safe = jnp.maximum(slots, 0)
    if has_ca:
        rows = _pad_axis(ca, n_shards, 0, "zero")
        keys = jnp.zeros((0, 0), jnp.float32)
    else:
        rows = _pad_axis(coords, n_shards, 0, "zero")
        keys = coords[safe]

    def shard_fn(rows_s, keys_s, slots_s, slot_cache_s, H_s, h_repo_s):
        return _best_two_rows(rows_s, keys_s, slots_s, slot_cache_s, H_s,
                              h_repo_s, metric, gamma, has_ca)

    best1, arg1, best2 = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(tuple(axes), None), P(), P(), P(), P(), P()),
        out_specs=(P(None, tuple(axes)),) * 3,
        check_vma=False)(rows, keys, slots, slot_cache, H, h_repo)
    return best1[:, :n_obj], arg1[:, :n_obj], best2[:, :n_obj]


def best_two_refresh(coords, ca, slots, slot_cache, H, h_repo,
                     metric: str, gamma: float, has_ca: bool,
                     mesh=None, axes: tuple = ()):
    """The single serving-table refresh every control-plane consumer
    shares (``DeviceInstance.best_two``, the NETDUEL scan's promotion
    re-arm, the scanned LOCALSWAP's post-swap re-arm): static dispatch
    to :func:`sharded_best_two` when mesh axes are configured, else the
    single-device kernel — bit-identical either way. Callers pass
    ``mesh=None`` when the policy resolves to one shard."""
    if mesh is not None:
        return sharded_best_two(coords, ca, slots, slot_cache, H, h_repo,
                                mesh, axes, metric, gamma, has_ca)
    return _best_two_device(coords, ca, slots, slot_cache, H, h_repo,
                            metric, gamma, has_ca)


@functools.partial(jax.jit, static_argnames=("metric", "gamma", "has_ca"))
def _best_two_tables_device(coords, ca, slots, slot_cache, H,
                            metric: str, gamma: float, has_ca: bool):
    rows = ca if has_ca else coords
    keys = jnp.zeros((0, 0), jnp.float32) if has_ca \
        else coords[jnp.maximum(slots, 0)]
    return _best_two_rows_pre(rows, keys, slots, slot_cache, H,
                              metric, gamma, has_ca)


@functools.partial(jax.jit, static_argnames=("metric", "gamma", "has_ca",
                                             "mesh", "axes"))
def sharded_best_two_tables(coords, ca, slots, slot_cache, H, mesh,
                            axes: tuple, metric: str, gamma: float,
                            has_ca: bool):
    """Mesh-sharded pre-fold tables (b1, a1, b2, a2): the request axis is
    shard_mapped over ``axes`` exactly like :func:`sharded_best_two`, so
    per-row results are bit-identical at any shard count."""
    from jax.sharding import PartitionSpec as P

    from repro.kernels.knn.ops import _pad_axis, mesh_axes_size
    n_shards = mesh_axes_size(mesh, axes)
    n_obj = coords.shape[0] if not has_ca else ca.shape[0]
    safe = jnp.maximum(slots, 0)
    if has_ca:
        rows = _pad_axis(ca, n_shards, 0, "zero")
        keys = jnp.zeros((0, 0), jnp.float32)
    else:
        rows = _pad_axis(coords, n_shards, 0, "zero")
        keys = coords[safe]

    def shard_fn(rows_s, keys_s, slots_s, slot_cache_s, H_s):
        return _best_two_rows_pre(rows_s, keys_s, slots_s, slot_cache_s,
                                  H_s, metric, gamma, has_ca)

    b1, a1, b2, a2 = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(tuple(axes), None), P(), P(), P(), P()),
        out_specs=(P(None, tuple(axes)),) * 4,
        check_vma=False)(rows, keys, slots, slot_cache, H)
    return b1[:, :n_obj], a1[:, :n_obj], b2[:, :n_obj], a2[:, :n_obj]


def best_two_tables(coords, ca, slots, slot_cache, H,
                    metric: str, gamma: float, has_ca: bool,
                    mesh=None, axes: tuple = ()):
    """Pre-fold best-two tables (b1, a1, b2, a2) over the slot axis only
    — the carried state of the incremental refresh path. Post-fold
    serving tables are ``_fold_repo_rows(b1, a1, b2, h_repo)``, bitwise
    what :func:`best_two_refresh` returns."""
    if mesh is not None:
        return sharded_best_two_tables(coords, ca, slots, slot_cache, H,
                                       mesh, axes, metric, gamma, has_ca)
    return _best_two_tables_device(coords, ca, slots, slot_cache, H,
                                   metric, gamma, has_ca)


# Public name for folding pre-fold tables into serving tables.
fold_best_two = _fold_repo_rows


def default_delta_cap(n_obj: int) -> int:
    """Static dirty-row budget for :func:`best_two_delta`: generous
    enough that overflow (full rebuild) stays rare along scanned
    LOCALSWAP/NETDUEL trajectories, small enough that the gathered
    recompute is a fraction of a rebuild."""
    return max(64, n_obj // 16)


def best_two_delta(coords, ca, b1, a1, b2, a2, slots_new, ys, slot_cache,
                   H, metric: str, gamma: float, has_ca: bool,
                   cap: int, mesh=None, axes: tuple = ()):
    """Incremental pre-fold best-two refresh after slot writes.

    ``ys`` is a (P,) ascending i32 vector of the slot indices whose
    occupant changed (padded with K = total slots for unused lanes);
    ``slots_new`` is the post-write layout. Only rows whose current
    witness (a1 or a2) references a changed slot can need more than a
    two-candidate insertion: for every other row the changed slots' old
    costs sat strictly above best2 (or tied with a higher index than the
    stored witness — argmin keeps the first minimum), so removing them
    cannot move the tables, and inserting the new costs is an exact
    two-way merge with the same lowest-slot-index tie-break the full
    rebuild's argmin applies. Dirty rows are gathered (up to the static
    ``cap``) and recomputed by the full per-row kernel on the canonical
    shape-stable C_a, so the result is bitwise the full rebuild's; if
    more than ``cap`` rows are dirty the whole table is rebuilt
    (lax.cond — same jitted program either way).
    """
    K = int(slot_cache.shape[0])
    n_obj = ca.shape[0] if has_ca else coords.shape[0]
    return _best_two_delta_jit(coords, ca, b1, a1, b2, a2, slots_new, ys,
                               slot_cache, H, metric=metric, gamma=gamma,
                               has_ca=has_ca, cap=min(cap, n_obj),
                               n_slots=K, mesh=mesh, axes=tuple(axes))


@functools.partial(jax.jit, static_argnames=(
    "metric", "gamma", "has_ca", "cap", "n_slots", "mesh", "axes"))
def _best_two_delta_jit(coords, ca, b1, a1, b2, a2, slots_new, ys,
                        slot_cache, H, metric: str, gamma: float,
                        has_ca: bool, cap: int, n_slots: int,
                        mesh=None, axes: tuple = ()):
    from repro.core import costs
    K = n_slots
    R = b1.shape[1]
    n_pend = ys.shape[0]
    safe_y = jnp.minimum(ys, K - 1)
    valid_y = ys < K                                          # (P,)

    # Canonical C_a columns for the rewritten slots: same per-pair bits
    # as the full rebuild's _stable_ca_cols (shape-stable distance form).
    obj = jnp.maximum(slots_new[safe_y], 0)                   # (P,)
    if has_ca:
        cols = ca[:, obj]                                     # (R, P)
    else:
        cols = costs.approx_cost_stable(coords, coords[obj], metric, gamma)
    cols = jnp.where(slots_new[safe_y][None, :] >= 0, cols, jnp.inf)
    cn_all = cols[None, :, :] + H[:, slot_cache[safe_y]][:, None, :]
    cn_all = jnp.where(valid_y[None, None, :], cn_all, jnp.inf)  # (I,R,P)

    # Dirty rows: any ORIGINAL witness lands on a changed slot.
    hit1 = jnp.any((a1[:, :, None] == ys[None, None, :]) & valid_y, -1)
    hit2 = jnp.any((a2[:, :, None] == ys[None, None, :]) & valid_y, -1)
    dirty_r = jnp.any(hit1 | hit2, axis=0)                    # (R,)
    n_dirty = jnp.sum(dirty_r)

    # Two-candidate insertion of each new column, ascending slot order so
    # ties among the new columns themselves break to the lowest index —
    # exactly argmin's first-minimum rule. Clean rows end exact; dirty
    # rows are overwritten below.
    nb1, na1, nb2, na2 = b1, a1, b2, a2
    for j in range(n_pend):
        cn, yj, vj = cn_all[:, :, j], ys[j], valid_y[j]
        take1 = vj & ((cn < nb1) | ((cn == nb1) & (yj < na1)))
        take2 = (~take1) & vj & ((cn < nb2) | ((cn == nb2) & (yj < na2)))
        nb2 = jnp.where(take1, nb1, jnp.where(take2, cn, nb2))
        na2 = jnp.where(take1, na1,
                        jnp.where(take2, yj, na2)).astype(jnp.int32)
        nb1 = jnp.where(take1, cn, nb1)
        na1 = jnp.where(take1, yj, na1).astype(jnp.int32)

    # Recompute the dirty rows with the full per-row kernel (row
    # independence + canonical C_a make the subset bitwise the rebuild).
    ridx = jnp.nonzero(dirty_r, size=cap, fill_value=R)[0].astype(jnp.int32)
    safe_r = jnp.minimum(ridx, R - 1)
    keys_new = jnp.zeros((0, 0), jnp.float32) if has_ca \
        else coords[jnp.maximum(slots_new, 0)]
    rows_sub = ca[safe_r] if has_ca else coords[safe_r]
    sb1, sa1, sb2, sa2 = _best_two_rows_pre(
        rows_sub, keys_new, slots_new, slot_cache, H, metric, gamma, has_ca)
    nb1 = nb1.at[:, ridx].set(sb1, mode="drop")
    na1 = na1.at[:, ridx].set(sa1, mode="drop")
    nb2 = nb2.at[:, ridx].set(sb2, mode="drop")
    na2 = na2.at[:, ridx].set(sa2, mode="drop")

    def _rebuild(_):
        if mesh is not None:
            return sharded_best_two_tables(coords, ca, slots_new,
                                           slot_cache, H, mesh, axes,
                                           metric, gamma, has_ca)
        rows = ca if has_ca else coords
        return _best_two_rows_pre(rows, keys_new, slots_new, slot_cache,
                                  H, metric, gamma, has_ca)

    return jax.lax.cond(n_dirty > cap, _rebuild,
                        lambda _: (nb1, na1, nb2, na2), operand=None)


@dataclasses.dataclass(frozen=True, eq=False)
class DeviceInstance:
    """Device-resident twin of :class:`Instance`.

    Holds the arrays every control-plane op needs (f32 coords, rates,
    retrieval costs, slot layout) plus an optional materialized C_a, and
    exposes the jitted primitives GREEDY/LOCALSWAP are built from:
    :meth:`gains` (full batched oracle, mesh-sharded when configured),
    :meth:`gain_at` (exact refresh of a candidate batch),
    :meth:`apply_pick` (incremental cost update) and :meth:`best_two`.
    ``host`` keeps the originating NumPy instance for demand sampling
    and differential testing — it is never touched by the jitted ops.
    """
    host: Instance
    coords: jax.Array                  # (O, D) f32
    lam: jax.Array                     # (I, O) f32
    H: jax.Array                       # (I, J) f32, +inf off-path
    h_repo: jax.Array                  # (I,) f32
    slot_cache: jax.Array              # (K,) i32
    ca: jax.Array | None               # (O, O) materialized C_a, or None
    metric: str
    gamma: float
    mesh: object = None
    axes: tuple = ()
    use_pallas: bool | None = None
    interpret: bool | None = None

    @classmethod
    def from_instance(cls, inst: Instance, mesh=None, axes: tuple = (),
                      materialize_ca: bool | None = None,
                      use_pallas: bool | None = None,
                      interpret: bool | None = None) -> "DeviceInstance":
        if materialize_ca is None:
            materialize_ca = (inst.ca_matrix is not None
                              or inst.cat.n <= 4096)
        if inst.ca_matrix is not None and not materialize_ca:
            raise ValueError("explicit ca_matrix instances must materialize")
        return cls(
            host=inst,
            coords=jnp.asarray(inst.cat.coords, jnp.float32),
            lam=jnp.asarray(inst.lam, jnp.float32),
            H=jnp.asarray(inst.net.H, jnp.float32),
            h_repo=jnp.asarray(inst.net.h_repo, jnp.float32),
            slot_cache=jnp.asarray(inst.slot_cache, jnp.int32),
            ca=jnp.asarray(inst.ca, jnp.float32) if materialize_ca else None,
            metric=inst.cat.metric, gamma=inst.cat.gamma,
            mesh=mesh, axes=tuple(axes),
            use_pallas=use_pallas, interpret=interpret)

    # ----------------------------------------------------------- shapes
    @property
    def n_objects(self) -> int:
        return self.coords.shape[0]

    @property
    def n_caches(self) -> int:
        return self.H.shape[1]

    @property
    def n_shards(self) -> int:
        if self.mesh is None or not self.axes:
            return 1
        from repro.kernels.knn import mesh_axes_size
        return mesh_axes_size(self.mesh, self.axes)

    # ------------------------------------------------------------- ops
    def initial_costs(self) -> jax.Array:
        """C(r, ∅) = h_repo, per (ingress, object) — f32 (I, O)."""
        return jnp.broadcast_to(
            self.h_repo[:, None], (self.lam.shape[0], self.n_objects)
        ).astype(jnp.float32)

    def gains(self, cur: jax.Array, quantize: bool = False) -> jax.Array:
        """(O, J) marginal gains of every candidate — one oracle launch
        (one per candidate shard when a mesh is configured). With
        ``quantize`` the oracle runs the int8 lower-bound distance pass,
        returning admissible *upper* bounds on every gain — valid lazy
        priorities, not exact values; callers must re-score before
        acceptance (``device_greedy`` does, through its stale-entry
        refresh)."""
        from repro.kernels.knn import (placement_gains,
                                       placement_gains_matrix,
                                       sharded_placement_gains)
        if self.ca is not None:
            return placement_gains_matrix(self.ca, self.lam, cur, self.H,
                                          quantize=quantize)
        if self.mesh is not None and self.n_shards > 1:
            return sharded_placement_gains(
                self.coords, self.coords, self.lam, cur, self.H,
                self.mesh, self.axes, metric=self.metric, gamma=self.gamma,
                use_pallas=self.use_pallas, interpret=self.interpret,
                quantize=quantize)
        return placement_gains(self.coords, self.coords, self.lam, cur,
                               self.H, metric=self.metric, gamma=self.gamma,
                               use_pallas=self.use_pallas,
                               interpret=self.interpret, quantize=quantize)

    def gain_at(self, cur: jax.Array, objs: jax.Array, caches: jax.Array
                ) -> jax.Array:
        ca = self.ca if self.ca is not None else jnp.zeros((0, 0), jnp.float32)
        return _gain_at_device(self.coords, ca, self.lam, cur, self.H,
                               objs, caches, self.metric, self.gamma,
                               self.ca is not None)

    def apply_pick(self, cur: jax.Array, obj, cache) -> jax.Array:
        ca = self.ca if self.ca is not None else jnp.zeros((0, 0), jnp.float32)
        return _apply_pick_device(self.coords, ca, self.H, cur,
                                  jnp.asarray(obj), jnp.asarray(cache),
                                  self.metric, self.gamma,
                                  self.ca is not None)

    def best_two(self, slots: jax.Array):
        """best1/arg1/best2 serving tables — request-axis mesh-sharded
        (``sharded_best_two``) when the instance carries the data-plane
        shard axes; bit-identical either way."""
        ca = self.ca if self.ca is not None else jnp.zeros((0, 0), jnp.float32)
        sharded = self.mesh is not None and self.n_shards > 1
        return best_two_refresh(self.coords, ca, jnp.asarray(slots),
                                self.slot_cache, self.H, self.h_repo,
                                self.metric, self.gamma, self.ca is not None,
                                mesh=self.mesh if sharded else None,
                                axes=self.axes if sharded else ())

    def best_two_tables(self, slots: jax.Array):
        """Pre-fold (b1, a1, b2, a2) tables over the slot axis — the
        carried state of the incremental refresh; fold with
        ``fold_best_two(b1, a1, b2, h_repo)`` for serving tables."""
        ca = self.ca if self.ca is not None else jnp.zeros((0, 0), jnp.float32)
        sharded = self.mesh is not None and self.n_shards > 1
        return best_two_tables(self.coords, ca, jnp.asarray(slots),
                               self.slot_cache, self.H,
                               self.metric, self.gamma, self.ca is not None,
                               mesh=self.mesh if sharded else None,
                               axes=self.axes if sharded else ())

    def best_two_delta(self, b1, a1, b2, a2, slots_new, ys,
                       cap: int | None = None):
        """Incremental pre-fold refresh after writing slots ``ys`` (see
        :func:`best_two_delta`); bitwise :meth:`best_two_tables` on the
        new layout."""
        ca = self.ca if self.ca is not None else jnp.zeros((0, 0), jnp.float32)
        sharded = self.mesh is not None and self.n_shards > 1
        if cap is None:
            cap = default_delta_cap(self.n_objects)
        return best_two_delta(self.coords, ca, b1, a1, b2, a2,
                              jnp.asarray(slots_new), jnp.asarray(ys),
                              self.slot_cache, self.H,
                              self.metric, self.gamma, self.ca is not None,
                              cap=cap,
                              mesh=self.mesh if sharded else None,
                              axes=self.axes if sharded else ())

    def ca_col(self, obj) -> jax.Array:
        """(O,) column C_a[:, obj] as a device array."""
        if self.ca is not None:
            return self.ca[:, obj]
        return _ca_cols_device(self.coords, jnp.asarray(obj)[None],
                               self.metric, self.gamma)[:, 0]

    def total_cost(self, slots) -> float:
        """C(A) evaluated on device (f32) — the only total-cost path that
        exists for catalogs past CA_MATERIALIZE_MAX.

        Only requested objects are priced: a zero-rate row adds nothing
        to the sum, and dropping it bounds the (rows × slots) cost table
        by the demand's support instead of the catalog (10⁶ objects ×
        86,016 slots would be a 344 GB table). The support is padded to
        a power of two with zero-rate copies of its first row, so
        successive observed windows reuse a few compiled shapes."""
        lam = np.asarray(self.lam)
        supp = np.nonzero(lam.sum(axis=0) > 0)[0]
        if supp.size == 0:
            return 0.0
        n = 1 << int(supp.size - 1).bit_length()
        rows = np.concatenate([supp, np.full(n - supp.size, supp[0])])
        lam_s = np.zeros((lam.shape[0], n), np.float32)
        lam_s[:, :supp.size] = lam[:, supp]
        ca = self.ca if self.ca is not None else jnp.zeros((0, 0), jnp.float32)
        return float(_support_cost_device(
            self.coords, ca, jnp.asarray(rows, jnp.int32),
            jnp.asarray(lam_s), jnp.asarray(slots), self.slot_cache,
            self.H, self.h_repo, self.metric, self.gamma,
            self.ca is not None))


@functools.partial(jax.jit, static_argnames=("metric", "gamma", "has_ca"))
def _support_cost_device(coords, ca, rows, lam_s, slots, slot_cache, H,
                         h_repo, metric: str, gamma: float, has_ca: bool):
    """Σ λ·best1 over the request rows ``rows`` (the demand's support)."""
    r = ca[rows] if has_ca else coords[rows]
    keys = jnp.zeros((0, 0), jnp.float32) if has_ca \
        else coords[jnp.maximum(slots, 0)]
    best1, _, _ = _best_two_rows(r, keys, slots, slot_cache, H, h_repo,
                                 metric, gamma, has_ca)
    return jnp.sum(lam_s * best1)


def random_slots(inst: Instance, rng: np.random.Generator) -> np.ndarray:
    """Random initial allocation (LocalSwap/NetDuel start state, §3.3)."""
    return rng.integers(0, inst.cat.n, size=inst.net.total_slots, dtype=np.int64)


def empty_slots(inst: Instance) -> np.ndarray:
    return np.full(inst.net.total_slots, -1, dtype=np.int64)
