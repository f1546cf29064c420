"""Pallas TPU kernel: blocked nearest-approximizer (1-NN) lookup.

This is the serving-path hot spot of the similarity cache (paper §2: the
"closest stored object" query, which the paper delegates to LSH; DESIGN.md
§6 explains why a blocked exact scan is the TPU-native equivalent).

Layout / tiling:
  * grid = (Q//BQ, K//BK); the key axis is the minor (fastest) grid dim,
    so each query tile sees key tiles sequentially and accumulates a
    running (min cost, argmin index) pair in its output VMEM block.
  * q tile (BQ, D) and k tile (BK, D) live in VMEM; the L2 path computes
    the (BQ, BK) distance block with one MXU matmul via the
    |q|² + |k|² − 2·q·kᵀ identity (f32 accumulation).
  * the L1 path (the paper's norm-1 experiments) has no matmul form; it
    accumulates |q−k| one feature at a time over lane-dense (BQ, BK)
    tiles — VPU work, still VMEM-resident.
  * D is zero-padded to a lane multiple and K is padded by *repeating
    key 0* — ties break to the lower index, so padded duplicates can
    never win over the genuine entry (see ops.py).

Block defaults keep the working set ≲ 2.5 MB ≪ 16 MB VMEM and the MXU
dims 128-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BQ = 256
DEFAULT_BK = 256
_INF = 3.0e38  # python float: jnp scalars would be captured as consts


def _distance_block(q, k, metric: str, n_feat: int | None = None):
    """(BQ, BK) distances between f32 tiles q (BQ, D), k (BK, D).

    ``n_feat`` is the feature count before lane padding: the l1 loop
    stops there, since a zero-padded feature adds |0 − 0| = 0.

    The l2 dot runs at HIGHEST precision: the default f32 matmul on the
    TPU rounds its inputs to bf16, which moves costs by far more than
    the f32 ulp the exact-lookup contracts are stated in."""
    if metric in ("l2", "l2sq"):
        d2 = (jnp.sum(q * q, axis=-1)[:, None]
              + jnp.sum(k * k, axis=-1)[None, :]
              - 2.0 * jnp.dot(q, k.T, preferred_element_type=jnp.float32,
                              precision=jax.lax.Precision.HIGHEST))
        d2 = jnp.maximum(d2, 0.0)
        return d2 if metric == "l2sq" else jnp.sqrt(d2)
    if metric == "l1":
        # one feature per step over lane-dense (BQ, BK) tiles: a query
        # column against a row of the transposed keys, accumulated in
        # feature order
        kt = k.T                                       # (D, BK)
        acc = jnp.zeros((q.shape[0], k.shape[0]), dtype=jnp.float32)
        for c in range(n_feat or q.shape[1]):
            acc = acc + jnp.abs(q[:, c:c + 1] - kt[c:c + 1, :])
        return acc
    raise ValueError(metric)


def _knn_kernel(q_ref, k_ref, mind_ref, argm_ref, *, bk: int, metric: str,
                gamma: float, n_feat: int | None):
    kt = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32)
    k = k_ref[...].astype(jnp.float32)
    cost = _distance_block(q, k, metric, n_feat)
    if gamma != 1.0:
        cost = jnp.power(jnp.maximum(cost, 0.0), gamma)
    local_min = jnp.min(cost, axis=1, keepdims=True)               # (BQ, 1)
    local_arg = jnp.argmin(cost, axis=1).astype(jnp.int32)[:, None]
    local_arg = local_arg + kt * bk

    @pl.when(kt == 0)
    def _init():
        mind_ref[...] = jnp.full_like(mind_ref, _INF)
        argm_ref[...] = jnp.zeros_like(argm_ref)

    better = local_min < mind_ref[...]
    mind_ref[...] = jnp.where(better, local_min, mind_ref[...])
    argm_ref[...] = jnp.where(better, local_arg, argm_ref[...])


def _select_at(idx_col, block, fill):
    """Per-row pick block[i, idx_col[i]] via a one-hot reduce (MXU/VPU
    friendly; no dynamic gather inside the kernel)."""
    onehot = jax.lax.broadcasted_iota(
        jnp.int32, block.shape, 1) == idx_col          # (BQ, BK)
    return jnp.sum(jnp.where(onehot, block, fill), axis=1, keepdims=True)


def _fused_kernel(q_ref, k_ref, hk_ref, meta_ref,
                  cost_ref, ca_ref, lvl_ref, slot_ref, pay_ref,
                  *, nk: int, metric: str, gamma: float, h_repo: float,
                  repo_level: int, fold_repo: bool, n_feat: int | None):
    """Segmented 1-NN over the concatenation of all cache levels.

    Per key tile we get, besides the (BK, D) key block, a (1, BK) f32 row
    of additive level costs h(level(k)) and a (4, BK) i32 metadata block
    (rows: level id, slot within level, payload id, valid flag). Sentinel
    / padding keys carry valid == 0 and are masked to +INF *explicitly* —
    their distances may be inf/NaN (e.g. an f32-overflowing sentinel
    coordinate under l2sq) and must never reach the min.

    The repository is the virtual key folded in on the last key tile:
    cost h_repo, C_a = 0, level = repo_level, slot = 0, payload = −1. It
    wins only on strict improvement, so a cache tying h_repo serves the
    request — the same tie-break as argmin over [levels…, repo].

    ``fold_repo=False`` skips that last-tile fold: the kernel then
    returns the *local* segment minimum only (cost = +INF, level =
    repo_level, payload = −1 when no valid key exists) — the shard-local
    entry of the mesh-sharded lookup, whose caller folds the repository
    once after the cross-shard reduction.
    """
    kt = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32)
    k = k_ref[...].astype(jnp.float32)
    ca = _distance_block(q, k, metric, n_feat)
    if gamma != 1.0:
        ca = jnp.power(jnp.maximum(ca, 0.0), gamma)
    meta = meta_ref[...]                               # (4, BK) int32
    valid = (meta[3, :] > 0)[None, :]                  # (1, BK)
    cost = jnp.where(valid, ca + hk_ref[...], _INF)    # (BQ, BK)
    local_min = jnp.min(cost, axis=1, keepdims=True)   # (BQ, 1)
    local_arg = jnp.argmin(cost, axis=1).astype(jnp.int32)[:, None]

    @pl.when(kt == 0)
    def _init():
        cost_ref[...] = jnp.full_like(cost_ref, _INF)
        ca_ref[...] = jnp.zeros_like(ca_ref)
        lvl_ref[...] = jnp.full_like(lvl_ref, repo_level)
        slot_ref[...] = jnp.zeros_like(slot_ref)
        pay_ref[...] = jnp.full_like(pay_ref, -1)

    bcast = jnp.zeros(local_arg.shape, jnp.int32)      # (BQ, 1) index col
    better = local_min < cost_ref[...]
    cost_ref[...] = jnp.where(better, local_min, cost_ref[...])
    ca_ref[...] = jnp.where(
        better, _select_at(local_arg, jnp.where(valid, ca, 0.0), 0.0),
        ca_ref[...])
    lvl_ref[...] = jnp.where(
        better, _select_at(local_arg, meta[0:1, :] + bcast, 0), lvl_ref[...])
    slot_ref[...] = jnp.where(
        better, _select_at(local_arg, meta[1:2, :] + bcast, 0), slot_ref[...])
    pay_ref[...] = jnp.where(
        better, _select_at(local_arg, meta[2:3, :] + bcast, 0), pay_ref[...])

    if fold_repo:
        @pl.when(kt == nk - 1)
        def _repo():
            use_repo = h_repo < cost_ref[...]
            cost_ref[...] = jnp.where(use_repo, h_repo, cost_ref[...])
            ca_ref[...] = jnp.where(use_repo, 0.0, ca_ref[...])
            lvl_ref[...] = jnp.where(use_repo, repo_level, lvl_ref[...])
            slot_ref[...] = jnp.where(use_repo, 0, slot_ref[...])
            pay_ref[...] = jnp.where(use_repo, -1, pay_ref[...])


@functools.partial(jax.jit, static_argnames=(
    "metric", "gamma", "h_repo", "repo_level", "bq", "bk", "interpret",
    "fold_repo", "n_feat"))
def fused_lookup_pallas(queries: jax.Array, keys: jax.Array,
                        h_key: jax.Array, meta: jax.Array,
                        metric: str = "l2", gamma: float = 1.0,
                        h_repo: float = 0.0, repo_level: int = -1,
                        bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                        interpret: bool = True, fold_repo: bool = True,
                        n_feat: int | None = None
                        ) -> tuple[jax.Array, ...]:
    """Fused multi-level 1-NN: one pallas_call over ΣK_j concatenated
    keys, minimizing C_a(q, k)^γ + h(level(k)) with the repository folded
    in as a virtual key. Inputs must be pre-padded (Q % bq == 0,
    K % bk == 0; padding keys carry meta valid == 0).

    ``h_key`` is (1, K) f32; ``meta`` is (4, K) i32 with rows
    (level, slot, payload, valid). Returns per query (cost, approx_cost,
    level, slot, payload). ``fold_repo=False`` is the shard-local entry:
    segment minima only, no repository fold (see _fused_kernel).
    ``n_feat`` is the feature count before lane padding (None: all D).
    """
    Q, D = queries.shape
    K, _ = keys.shape
    assert Q % bq == 0 and K % bk == 0, (Q, K, bq, bk)
    assert h_key.shape == (1, K) and meta.shape == (4, K), \
        (h_key.shape, meta.shape, K)
    grid = (Q // bq, K // bk)
    kernel = functools.partial(
        _fused_kernel, nk=K // bk, metric=metric, gamma=gamma,
        h_repo=h_repo, repo_level=repo_level, fold_repo=fold_repo,
        n_feat=n_feat)
    out_block = pl.BlockSpec((bq, 1), lambda qt, kt: (qt, 0))
    cost, ca, lvl, slot, pay = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, D), lambda qt, kt: (qt, 0)),
            pl.BlockSpec((bk, D), lambda qt, kt: (kt, 0)),
            pl.BlockSpec((1, bk), lambda qt, kt: (0, kt)),
            pl.BlockSpec((4, bk), lambda qt, kt: (0, kt)),
        ],
        out_specs=[out_block] * 5,
        out_shape=[
            jax.ShapeDtypeStruct((Q, 1), jnp.float32),
            jax.ShapeDtypeStruct((Q, 1), jnp.float32),
            jax.ShapeDtypeStruct((Q, 1), jnp.int32),
            jax.ShapeDtypeStruct((Q, 1), jnp.int32),
            jax.ShapeDtypeStruct((Q, 1), jnp.int32),
        ],
        interpret=interpret,
    )(queries, keys, h_key, meta)
    return cost[:, 0], ca[:, 0], lvl[:, 0], slot[:, 0], pay[:, 0]


@functools.partial(jax.jit, static_argnames=(
    "metric", "gamma", "bq", "bk", "interpret", "n_feat"))
def knn_pallas(queries: jax.Array, keys: jax.Array, metric: str = "l2",
               gamma: float = 1.0, bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
               interpret: bool = True, n_feat: int | None = None
               ) -> tuple[jax.Array, jax.Array]:
    """Blocked 1-NN. Inputs must be pre-padded: Q % bq == 0, K % bk == 0,
    with key padding = repeats of keys[0] (see ops.pad_for_knn)."""
    Q, D = queries.shape
    K, _ = keys.shape
    assert Q % bq == 0 and K % bk == 0, (Q, K, bq, bk)
    grid = (Q // bq, K // bk)
    kernel = functools.partial(_knn_kernel, bk=bk, metric=metric, gamma=gamma,
                               n_feat=n_feat)
    mind, argm = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, D), lambda qt, kt: (qt, 0)),
            pl.BlockSpec((bk, D), lambda qt, kt: (kt, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bq, 1), lambda qt, kt: (qt, 0)),
            pl.BlockSpec((bq, 1), lambda qt, kt: (qt, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Q, 1), jnp.float32),
            jax.ShapeDtypeStruct((Q, 1), jnp.int32),
        ],
        interpret=interpret,
    )(queries, keys)
    return mind[:, 0], argm[:, 0]
