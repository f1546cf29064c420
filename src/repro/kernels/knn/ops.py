"""Jitted public wrapper around the KNN Pallas kernel.

Handles padding (queries → BQ multiple with zeros, keys → BK multiple by
repeating key 0 so ties break to the genuine lower index, feature dim →
lane multiple with zeros, which preserves both L1 and L2 distances), and
falls back to the pure-jnp oracle on platforms without Pallas TPU support
unless ``interpret=True`` (the default off-TPU) is requested.

``sharded_fused_lookup`` is the SPMD data-plane entry: the segmented key
tensor lives sharded across a mesh axis, each shard runs the fused
segmented-1-NN kernel locally with ``fold_repo=False``, and the per-shard
(cost, C_a, level, slot, payload) minima — 5 scalars per query per shard,
a tiny fraction of the key tensor — are gathered and reduced
lexicographically by ``reduce_shard_minima``, which also folds the
repository exactly once. Contiguous balanced shards + first-min
tie-breaking make the result bit-identical to the single-device fused
path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import tracecount
from repro.kernels import quant
from repro.kernels.knn.knn import (DEFAULT_BK, DEFAULT_BQ, _INF,
                                   fused_lookup_pallas, knn_pallas)
from repro.kernels.knn.lsh import (candidate_matrix, candidate_union,
                                   gather_candidate_rows, unscanned_h_bound)
from repro.kernels.knn.ref import (fused_lookup_ref, knn_ref,
                                   reduce_shard_minima)
from repro.kernels.quant import QuantizedRows

LANE = 128
DEFAULT_TOP_T = 64        # quantized first pass: exact-rescore width
DEFAULT_QTILE = 8192      # quantized first pass: key-axis tile


def _pad_axis(x: jax.Array, mult: int, axis: int, mode: str) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    if mode == "zero":
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        return jnp.pad(x, widths)
    if mode == "repeat_first":
        first = jax.lax.slice_in_dim(x, 0, 1, axis=axis)
        reps = jnp.concatenate([first] * pad, axis=axis)
        return jnp.concatenate([x, reps], axis=axis)
    raise ValueError(mode)


def pad_for_knn(queries: jax.Array, keys: jax.Array, bq: int, bk: int
                ) -> tuple[jax.Array, jax.Array]:
    queries = _pad_axis(_pad_axis(queries, LANE, 1, "zero"), bq, 0, "zero")
    keys = _pad_axis(_pad_axis(keys, LANE, 1, "zero"), bk, 0, "repeat_first")
    return queries, keys


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("metric", "gamma", "bq", "bk",
                                              "use_pallas", "interpret"))
def nearest_approximizer(queries: jax.Array, keys: jax.Array,
                         metric: str = "l2", gamma: float = 1.0,
                         bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                         use_pallas: bool = True,
                         interpret: bool | None = None
                         ) -> tuple[jax.Array, jax.Array]:
    """min_k C_a(q, key_k) and the argmin index, per query.

    The public lookup primitive of the similarity cache: returns the
    dissimilarity cost d(q, k)^γ of the best stored approximizer and its
    slot index.
    """
    nq = queries.shape[0]
    if not use_pallas:
        return knn_ref(queries, keys, metric, gamma)
    if interpret is None:
        interpret = not _on_tpu()
    qp, kp = pad_for_knn(queries.astype(jnp.float32),
                         keys.astype(jnp.float32), bq, bk)
    mind, argm = knn_pallas(qp, kp, metric=metric, gamma=gamma, bq=bq, bk=bk,
                            interpret=interpret, n_feat=queries.shape[1])
    return mind[:nq], argm[:nq]


@functools.partial(jax.jit, static_argnames=(
    "metric", "gamma", "h_repo", "repo_level", "bq", "bk", "use_pallas",
    "interpret", "fold_repo"))
def fused_lookup(queries: jax.Array, keys: jax.Array, h_key: jax.Array,
                 meta: jax.Array, metric: str = "l2", gamma: float = 1.0,
                 h_repo: float = 0.0, repo_level: int = -1,
                 bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                 use_pallas: bool = True, interpret: bool | None = None,
                 fold_repo: bool = True) -> tuple[jax.Array, ...]:
    """Network-wide nearest-approximizer query, fused.

    ``keys`` (K, d) is the concatenation of every cache level's stored
    embeddings; ``h_key`` (K,) the per-key retrieval cost h(level(k));
    ``meta`` (4, K) i32 rows (level, slot, payload, valid). A single
    blocked scan returns, per query, argmin over all keys *and* the
    repository (a virtual key with C_a = 0, h = h_repo) of
    C_a(q, k)^γ + h — eq. (1) as one kernel launch. Returns
    (cost, approx_cost, level, slot, payload), each (B,).

    ``fold_repo=False`` returns the segment-local minimum only (the
    shard-local half of ``sharded_fused_lookup``); with no valid key the
    result is (+INF, 0, repo_level, 0, −1).
    """
    tracecount.bump("fused_lookup")          # once per trace, not per call
    nq = queries.shape[0]
    if keys.shape[0] == 0:          # no cache keys at all → repository
        cost0 = h_repo if fold_repo else _INF
        return (jnp.full((nq,), cost0, jnp.float32),
                jnp.zeros((nq,), jnp.float32),
                jnp.full((nq,), repo_level, jnp.int32),
                jnp.zeros((nq,), jnp.int32),
                jnp.full((nq,), -1, jnp.int32))
    h_row = h_key.reshape(1, -1).astype(jnp.float32)
    if not use_pallas:
        return fused_lookup_ref(queries, keys, h_row[0], meta, metric=metric,
                                gamma=gamma, h_repo=h_repo,
                                repo_level=repo_level, fold_repo=fold_repo)
    if interpret is None:
        interpret = not _on_tpu()
    qp = _pad_axis(_pad_axis(queries.astype(jnp.float32), LANE, 1, "zero"),
                   bq, 0, "zero")
    kp = _pad_axis(_pad_axis(keys.astype(jnp.float32), LANE, 1, "zero"),
                   bk, 0, "zero")
    hp = _pad_axis(h_row, bk, 1, "zero")
    # padded keys get valid == 0, payload == −1 — masked inside the kernel
    kpad = kp.shape[0] - keys.shape[0]
    mp = jnp.pad(meta.astype(jnp.int32), ((0, 0), (0, kpad)),
                 constant_values=0)
    if kpad:
        mp = mp.at[2, keys.shape[0]:].set(-1)
    cost, ca, lvl, slot, pay = fused_lookup_pallas(
        qp, kp, hp, mp, metric=metric, gamma=gamma, h_repo=h_repo,
        repo_level=repo_level, bq=bq, bk=bk, interpret=interpret,
        fold_repo=fold_repo, n_feat=queries.shape[1])
    return cost[:nq], ca[:nq], lvl[:nq], slot[:nq], pay[:nq]


def mesh_axes_size(mesh, axes: tuple[str, ...]) -> int:
    """Product of the given mesh axis sizes — the lookup shard count.

    The single definition shared by the shard_map entry below,
    SimCacheNetwork.n_shards, and LookupShardPolicy.n_shards, so the
    padding contract (key axis % shard count == 0) can never drift
    between layout and dispatch.
    """
    n = 1
    for ax in axes:
        n *= mesh.shape[ax]
    return n


@functools.partial(jax.jit, static_argnames=(
    "mesh", "axes", "metric", "gamma", "h_repo", "repo_level", "bq", "bk",
    "use_pallas", "interpret"))
def sharded_fused_lookup(queries: jax.Array, keys: jax.Array,
                         h_key: jax.Array, meta: jax.Array, mesh,
                         axes: tuple[str, ...], metric: str = "l2",
                         gamma: float = 1.0, h_repo: float = 0.0,
                         repo_level: int = -1, bq: int = DEFAULT_BQ,
                         bk: int = DEFAULT_BK, use_pallas: bool = True,
                         interpret: bool | None = None
                         ) -> tuple[jax.Array, ...]:
    """Mesh-sharded fused lookup: one fused kernel launch *per shard*.

    ``keys``/``h_key``/``meta`` must already be padded so the key axis
    divides the shard count (product of the ``axes`` sizes in ``mesh``;
    padding keys carry valid == 0 — see SimCacheNetwork.sharded_layout).
    shard_map partitions the key axis into contiguous balanced chunks,
    each device scans only its resident chunk (queries replicated), and
    the per-shard minima come back stacked on a leading shard axis — the
    "tiny all-gather": 2 f32 + 3 i32 scalars per (query, shard), however
    large the catalog. ``reduce_shard_minima`` then picks the global
    winner and folds the repository, bit-identical to the single-device
    fused path.
    """
    tracecount.bump("sharded_fused_lookup")
    n_shards = mesh_axes_size(mesh, axes)
    K = keys.shape[0]
    assert K % n_shards == 0, (K, n_shards)
    spec = P(tuple(axes))

    def shard_fn(q, k, hk, m):
        cost, ca, lvl, slot, pay = fused_lookup(
            q, k, hk, m, metric=metric, gamma=gamma, h_repo=h_repo,
            repo_level=repo_level, bq=bq, bk=bk, use_pallas=use_pallas,
            interpret=interpret, fold_repo=False)
        return (cost[None], ca[None], lvl[None], slot[None], pay[None])

    parts = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(), spec, spec, P(None, tuple(axes))),
        out_specs=(spec,) * 5,
        check_vma=False)(queries, keys, h_key, meta)
    return reduce_shard_minima(*parts, h_repo=h_repo,
                               repo_level=repo_level)


def _quantized_select(queries: jax.Array, h_key: jax.Array,
                      valid: jax.Array, kq: QuantizedRows, top_t: int,
                      tile: int, metric: str, gamma: float
                      ) -> tuple[jax.Array, jax.Array]:
    """Compressed first pass: per-query top-T candidates + a sound bound.

    Scores every key with the certified lower bound lb_C_a + h (quant.
    lb_approx_cost_block over the int8 images; invalid keys → +INF),
    tiled along the key axis so the 4×-compressed table streams through
    a cheap dense XLA matmul however large the catalog. Returns

        cand  (B, T) i32 — per-query indices of the T smallest scores
                           (−1 where the score is +INF), and
        vT    (B,)   f32 — the T-th smallest score per query.

    ``vT`` bounds every *un-selected* key's exact cost from below: a key
    cut at the tile level scores ≥ its tile's T-th smallest, whose whole
    tile-top-T (all ≤ it) reaches the merge, so ≥ T merged entries sit
    under the cut key and the merged T-th smallest vT is below it; a key
    cut at the merge level scores ≥ vT by definition; and every score is
    ≤ the exact cost by quant.py's admissibility. Hence rescoring only
    ``cand`` in exact f32 and verifying ``cost < vT`` proves the winner
    equals the full exact scan's — the same verifier contract as LSH,
    but per query. When T covers every key the bound is +INF (nothing
    is un-scanned).
    """
    nq, dim = queries.shape
    n_keys = kq.q.shape[0]
    T = min(top_t, n_keys)
    tile = max(T, min(tile, n_keys))
    qq, qs = quant.quantize_int8(queries.astype(jnp.float32))
    qd = quant.dequantize_int8(qq, qs)
    rq = quant.quant_row_radius(qs[:, 0], dim, metric)
    q_sq = jnp.sum(qd * qd, axis=-1) if metric in ("l2", "l2sq") else None

    qk = _pad_axis(kq.q, tile, 0, "zero")
    sk = _pad_axis(kq.scale, tile, 0, "zero")
    rk = _pad_axis(kq.radius, tile, 0, "zero")
    nk = _pad_axis(kq.sq_norm, tile, 0, "zero")
    hv = _pad_axis(h_key.astype(jnp.float32), tile, 0, "zero")
    vv = _pad_axis(valid, tile, 0, "zero")          # pads to False
    nt = qk.shape[0] // tile
    offs = jnp.arange(nt, dtype=jnp.int32) * tile

    def tile_scores(args):
        qt, st, rt, sqt, ht, vt, off = args
        kd = quant.dequantize_int8(qt, st)
        lb = quant.lb_approx_cost_block(qd, kd, rq, rt, metric, gamma,
                                        q_sq=q_sq, k_sq=sqt)
        score = jnp.where(vt[None, :], lb + ht[None, :], _INF)
        neg, li = jax.lax.top_k(-score, T)
        return neg, off + li.astype(jnp.int32)

    neg, gidx = jax.lax.map(tile_scores, (
        qk.reshape(nt, tile, -1), sk.reshape(nt, tile, 1),
        rk.reshape(nt, tile), nk.reshape(nt, tile),
        hv.reshape(nt, tile), vv.reshape(nt, tile), offs))
    neg = jnp.moveaxis(neg, 0, 1).reshape(nq, nt * T)
    gidx = jnp.moveaxis(gidx, 0, 1).reshape(nq, nt * T)
    neg2, sel = jax.lax.top_k(neg, T)
    cand = jnp.take_along_axis(gidx, sel, axis=1)
    cand = jnp.where(neg2 > -_INF, cand, -1)        # +INF slots: no key
    if T >= n_keys:
        return cand, jnp.full((nq,), _INF, jnp.float32)
    return cand, -neg2[:, -1]


def _quant_union_cap(n_keys: int, nq: int, top_t: int) -> int:
    """Static batch-union capacity of the rescore gather: the union of nq
    per-query top-T sets can never exceed nq·T distinct rows, so unlike
    the LSH union this one can never overflow (no dropped candidates to
    account for — vT alone is the whole bound)."""
    return max(1, min(n_keys, nq * min(top_t, n_keys)))


@functools.partial(jax.jit, static_argnames=(
    "top_t", "tile", "metric", "gamma", "h_repo", "repo_level", "bq", "bk",
    "use_pallas", "interpret", "fold_repo"))
def quantized_fused_lookup(queries: jax.Array, keys: jax.Array,
                           h_key: jax.Array, meta: jax.Array,
                           kq: QuantizedRows, top_t: int = DEFAULT_TOP_T,
                           tile: int = DEFAULT_QTILE, metric: str = "l2",
                           gamma: float = 1.0, h_repo: float = 0.0,
                           repo_level: int = -1, bq: int = DEFAULT_BQ,
                           bk: int = DEFAULT_BK, use_pallas: bool = True,
                           interpret: bool | None = None,
                           fold_repo: bool = True) -> tuple[jax.Array, ...]:
    """Compressed-first-pass variant of :func:`fused_lookup`.

    ``kq`` is the pre-quantized int8 image of ``keys`` (quant.
    quantize_rows over the *same* rows — SimCacheNetwork memoizes it
    next to the fused layout). The certified-lower-bound first pass
    selects the top ``top_t`` candidates per query, their batch union is
    compacted ascending (same helper, hence same tie-break order, as the
    LSH gather) and rescored through the exact fused kernel. Returns
    (cost, approx_cost, level, slot, payload, bound) with ``bound`` a
    **per-query** (B,) verify threshold — ``cost < bound`` proves the
    result bit-identical to the exact scan (see _quantized_select);
    unlike LSH this holds *by construction of the bound*, not merely
    with high recall, so verified rescans are rare rather than load-
    bearing.
    """
    tracecount.bump("quantized_fused_lookup")
    nq = queries.shape[0]
    if keys.shape[0] == 0:          # no cache keys at all → repository
        out = fused_lookup(queries, keys, h_key, meta, metric=metric,
                           gamma=gamma, h_repo=h_repo,
                           repo_level=repo_level, bq=bq, bk=bk,
                           use_pallas=use_pallas, interpret=interpret,
                           fold_repo=fold_repo)
        return (*out, jnp.full((nq,), _INF, jnp.float32))
    cand, bound = _quantized_select(queries, h_key, meta[3, :] > 0, kq,
                                    top_t, tile, metric, gamma)
    cap = _quant_union_cap(keys.shape[0], nq, top_t)
    kept, _ = candidate_union(cand, keys.shape[0], cap)
    gk, gh, gm = gather_candidate_rows(keys, h_key, meta, kept)
    out = fused_lookup(queries, gk, gh, gm, metric=metric, gamma=gamma,
                       h_repo=h_repo, repo_level=repo_level, bq=bq, bk=bk,
                       use_pallas=use_pallas, interpret=interpret,
                       fold_repo=fold_repo)
    return (*out, bound)


@functools.partial(jax.jit, static_argnames=(
    "mesh", "axes", "top_t", "tile", "metric", "gamma", "h_repo",
    "repo_level", "bq", "bk", "use_pallas", "interpret"))
def sharded_quantized_fused_lookup(queries: jax.Array, keys: jax.Array,
                                   h_key: jax.Array, meta: jax.Array,
                                   kq: QuantizedRows, mesh,
                                   axes: tuple[str, ...],
                                   top_t: int = DEFAULT_TOP_T,
                                   tile: int = DEFAULT_QTILE,
                                   metric: str = "l2", gamma: float = 1.0,
                                   h_repo: float = 0.0,
                                   repo_level: int = -1,
                                   bq: int = DEFAULT_BQ,
                                   bk: int = DEFAULT_BK,
                                   use_pallas: bool = True,
                                   interpret: bool | None = None
                                   ) -> tuple[jax.Array, ...]:
    """Mesh-sharded compressed lookup. ``kq`` is the flat quantized image
    of the (shard-padded) key tensor — quantization is per-row, so the
    same contiguous balanced chunking that partitions ``keys`` partitions
    it; each shard runs the first pass + exact rescore on its resident
    chunk (``fold_repo=False``) and ``reduce_shard_minima`` is untouched.
    The returned per-query bound is the min over shards of each shard's
    vT: any un-scanned key lives in some shard and costs at least that
    shard's vT ≥ the min. Padding rows (valid == 0) score +INF and are
    never selected.
    """
    tracecount.bump("sharded_quantized_fused_lookup")
    n_shards = mesh_axes_size(mesh, axes)
    K = keys.shape[0]
    assert K % n_shards == 0, (K, n_shards)
    spec = P(tuple(axes))

    def shard_fn(q, k, hk, m, kqq, kqs, kqr, kqn):
        cost, ca, lvl, slot, pay, bound = quantized_fused_lookup(
            q, k, hk, m, QuantizedRows(kqq, kqs, kqr, kqn), top_t=top_t,
            tile=tile, metric=metric, gamma=gamma, h_repo=h_repo,
            repo_level=repo_level, bq=bq, bk=bk, use_pallas=use_pallas,
            interpret=interpret, fold_repo=False)
        return (cost[None], ca[None], lvl[None], slot[None], pay[None],
                bound[None])

    parts = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(), spec, spec, P(None, tuple(axes)),
                  spec, spec, spec, spec),
        out_specs=(spec,) * 6,
        check_vma=False)(queries, keys, h_key, meta,
                         kq.q, kq.scale, kq.radius, kq.sq_norm)
    *minima, bounds = parts
    red = reduce_shard_minima(*minima, h_repo=h_repo,
                              repo_level=repo_level)
    return (*red, jnp.min(bounds, axis=0))


@functools.partial(jax.jit, static_argnames=(
    "kind", "n_probes", "cap_union", "metric", "gamma", "h_repo",
    "repo_level", "bq", "bk", "use_pallas", "interpret", "fold_repo",
    "quantize", "top_t"))
def pruned_fused_lookup(queries: jax.Array, keys: jax.Array,
                        h_key: jax.Array, meta: jax.Array, proj: jax.Array,
                        buckets: jax.Array, kind: str = "lsh",
                        n_probes: int = 1, cap_union: int = 512,
                        metric: str = "l2", gamma: float = 1.0,
                        h_repo: float = 0.0, repo_level: int = -1,
                        bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                        use_pallas: bool = True,
                        interpret: bool | None = None,
                        fold_repo: bool = True, quantize: bool = False,
                        top_t: int = DEFAULT_TOP_T
                        ) -> tuple[jax.Array, ...]:
    """Gather-variant entry: LSH/k-means candidate pre-filter in front of
    the *existing* fused kernel (see kernels.knn.lsh).

    The query batch is hashed against ``proj``/``buckets`` (one
    CandidatePolicy's built tables over this key segment), the batch
    union of candidate rows is compacted into one ascending padded index
    tensor of static size ``cap_union``, and :func:`fused_lookup` runs
    over only the gathered (keys, h_key, meta) rows — same arithmetic,
    same masking, same tie-break order as the exact scan, on a fraction
    of the keys. Returns (cost, approx_cost, level, slot, payload,
    bound): ``bound`` is the min h over valid *un-scanned* keys (+INF if
    none), the verifier's accept threshold (``cost < bound`` proves the
    pruned result exact — lsh.py's verifier contract).

    ``quantize=True`` composes the compressed first pass *inside* the
    LSH union: the gathered rows are quantized on the fly, the top
    ``top_t`` per query survive to the exact rescore, and the returned
    bound becomes per-query (B,): min(h bound over rows outside the LSH
    union, vT over rows inside it that the first pass cut) — a key is
    either outside the union (exact cost ≥ its h ≥ the h bound) or cut
    by the first pass (exact cost ≥ its lb score ≥ vT). The exact-scan
    subunion keeps ascending global order (an ascending sub-selection of
    an ascending union), so the tie-break contract is untouched.
    """
    nq = queries.shape[0]
    if keys.shape[0] == 0:          # no cache keys at all → repository
        out = fused_lookup(queries, keys, h_key, meta, metric=metric,
                           gamma=gamma, h_repo=h_repo,
                           repo_level=repo_level, bq=bq, bk=bk,
                           use_pallas=use_pallas, interpret=interpret,
                           fold_repo=fold_repo)
        if quantize:
            return (*out, jnp.full((nq,), _INF, jnp.float32))
        return (*out, jnp.float32(_INF))
    cand = candidate_matrix(kind, proj, buckets, queries, n_probes)
    kept, kept_mask = candidate_union(cand, keys.shape[0], cap_union)
    gk, gh, gm = gather_candidate_rows(keys, h_key, meta, kept)
    bound = unscanned_h_bound(h_key, meta, kept_mask)
    if quantize:
        kq_u = quant.quantize_rows(gk, metric)
        cand2, vt = _quantized_select(queries, gh, gm[3, :] > 0, kq_u,
                                      top_t, DEFAULT_QTILE, metric, gamma)
        cap2 = _quant_union_cap(gk.shape[0], nq, top_t)
        kept2, _ = candidate_union(cand2, gk.shape[0], cap2)
        gk, gh, gm = gather_candidate_rows(gk, gh, gm, kept2)
        bound = jnp.minimum(bound, vt)
    out = fused_lookup(queries, gk, gh, gm, metric=metric, gamma=gamma,
                       h_repo=h_repo, repo_level=repo_level, bq=bq, bk=bk,
                       use_pallas=use_pallas, interpret=interpret,
                       fold_repo=fold_repo)
    return (*out, bound)


@functools.partial(jax.jit, static_argnames=(
    "mesh", "axes", "kind", "n_probes", "cap_union", "metric", "gamma",
    "h_repo", "repo_level", "bq", "bk", "use_pallas", "interpret",
    "quantize", "top_t"))
def sharded_pruned_fused_lookup(queries: jax.Array, keys: jax.Array,
                                h_key: jax.Array, meta: jax.Array,
                                proj_s: jax.Array, buckets_s: jax.Array,
                                mesh, axes: tuple[str, ...],
                                kind: str = "lsh", n_probes: int = 1,
                                cap_union: int = 512, metric: str = "l2",
                                gamma: float = 1.0, h_repo: float = 0.0,
                                repo_level: int = -1, bq: int = DEFAULT_BQ,
                                bk: int = DEFAULT_BK,
                                use_pallas: bool = True,
                                interpret: bool | None = None,
                                quantize: bool = False,
                                top_t: int = DEFAULT_TOP_T
                                ) -> tuple[jax.Array, ...]:
    """Mesh-sharded pruned lookup: per-shard tables prune each shard's
    resident chunk before its ``fold_repo=False`` fused-kernel launch.

    ``proj_s``/``buckets_s`` carry a leading (n_shards, …) axis (built
    via lsh.stack_shard_tables) that shard_map partitions together with
    the key tensor, so every shard hashes the replicated queries against
    its *own* tables and scans only its local candidate union.
    ``reduce_shard_minima`` and the tie-break order are untouched — the
    candidate mask only shrinks a shard's scan. The returned ``bound``
    is the min over shards of each shard's un-scanned-h bound, sound for
    the same verify contract as the single-device entry.
    ``quantize=True`` composes the compressed first pass inside each
    shard's LSH union (see pruned_fused_lookup) and the bound becomes
    per-query: min over shards of each shard's min(h bound, vT).
    """
    n_shards = mesh_axes_size(mesh, axes)
    K = keys.shape[0]
    assert K % n_shards == 0, (K, n_shards)
    spec = P(tuple(axes))

    def shard_fn(q, k, hk, m, pj, bks):
        cost, ca, lvl, slot, pay, bound = pruned_fused_lookup(
            q, k, hk, m, pj[0], bks[0], kind=kind, n_probes=n_probes,
            cap_union=cap_union, metric=metric, gamma=gamma, h_repo=h_repo,
            repo_level=repo_level, bq=bq, bk=bk, use_pallas=use_pallas,
            interpret=interpret, fold_repo=False, quantize=quantize,
            top_t=top_t)
        return (cost[None], ca[None], lvl[None], slot[None], pay[None],
                bound[None])

    parts = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(), spec, spec, P(None, tuple(axes)),
                  P(tuple(axes)), P(tuple(axes))),
        out_specs=(spec,) * 6,
        check_vma=False)(queries, keys, h_key, meta, proj_s, buckets_s)
    *minima, bounds = parts
    red = reduce_shard_minima(*minima, h_repo=h_repo,
                              repo_level=repo_level)
    bound = jnp.min(bounds, axis=0) if quantize else jnp.min(bounds)
    return (*red, bound)
