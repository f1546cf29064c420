"""Pallas TPU kernel: batched placement gain oracle for the control plane.

GREEDY/LOCALSWAP (paper §3.2–3.3) are driven entirely by marginal gains

    gain[o', j] = Σ_i Σ_r λ[i, r] · relu(cur[i, r] − C_a(x_r, y_{o'})
                                          − H[i, j])

over *all* candidate (object o', cache j) pairs, where ``cur`` is the
current per-(ingress, object) serving cost matrix C(r, A).  This module
computes the whole (O, J) gain matrix in one launch, reusing the
segmented distance machinery of the fused lookup (``_distance_block``,
the padding contracts of ops.py): each grid step computes one (BR, BO)
C_a tile on the MXU **once** and folds it into the (J, BO) accumulator
for every (ingress, cache) pair — the ingress axis is the segment axis,
carried as extra sublane rows of the λ/cur blocks instead of flattened
request copies (the kernels/gain kernel's layout), so the dominant
distance work is shared across the whole network.

Entries:

* :func:`placement_gains` — public jitted wrapper (padding + sentinel
  mapping + transpose).  ``use_pallas=None`` resolves to the Pallas
  kernel on TPU and to :func:`_gains_tiles_jnp` (a lax.map-blocked jnp
  path that never materializes the (R, O) distance matrix) elsewhere —
  the same auto-dispatch convention as kernels/knn/ops.py.
* :func:`placement_gains_matrix` — explicit-C_a-matrix variant (the
  paper's first instance, §2): tiles columns of a device-resident
  (R, O) matrix instead of computing distances.
* :func:`sharded_placement_gains` — SPMD entry: the candidate axis is
  shard_mapped over mesh axes (launch.sharding.LookupShardPolicy picks
  them), every shard computes the gains of its resident candidate chunk
  against the replicated request stream, and the (O, J) output comes
  back sharded.  Per-candidate sums are computed with identical request
  tiling whatever the shard count, so the result is bit-identical to
  the single-device oracle by construction.

Padding contracts (mirroring kernels/gain): request rows pad with
λ = 0 (their contribution vanishes), candidate rows pad with zeros and
are sliced off, D zero-pads to a lane multiple (distance-preserving),
off-path +inf entries of H map to the finite ``H_SENTINEL`` (relu
clamps them to zero gain; inf − inf would breed NaNs).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from repro.kernels import quant
from repro.kernels.knn.knn import _distance_block
from repro.kernels.knn.ops import LANE, _on_tpu, _pad_axis, mesh_axes_size

DEFAULT_BR = 256
DEFAULT_BO = 256
H_SENTINEL = 1.0e30      # finite stand-in for +inf (off-path) retrieval cost


def _ca_block(x, y, metric: str, gamma: float, n_feat: int | None = None):
    """(BR, BO) approximation-cost tile C_a = d(x, y)^γ (f32)."""
    ca = _distance_block(x.astype(jnp.float32), y.astype(jnp.float32), metric,
                         n_feat)
    if gamma != 1.0:
        ca = jnp.power(jnp.maximum(ca, 0.0), gamma)
    return ca


def duel_virtual_costs(coords, ca, obj, virt_safe, h_slots,
                       metric: str, gamma: float, has_ca: bool):
    """(K,) virtual serving cost C_a(x_o, y_v[k]) + h(i, j(k)) for one
    request — NETDUEL's per-step pricing tile (paper §5), the 1-row
    special case of the gain oracle's C_a tiling. On materialized-C_a
    instances the row gather reproduces the host policy's
    ``ca[o, virt]`` bit-for-bit; past ``objective.CA_MATERIALIZE_MAX``
    the tile is computed on the fly by the same :func:`_ca_block` the
    gain kernels use. Traced inside the NETDUEL scan
    (core/placement/netduel.py), so ``has_ca`` must be static there.
    """
    if has_ca:
        cac = ca[obj, virt_safe]
    else:
        from repro.core import costs
        cac = costs.approx_cost_stable(coords[obj][None, :],
                                       coords[virt_safe], metric, gamma)[0]
    return cac + h_slots


def _gains_kernel(x_ref, y_ref, lam_ref, cur_ref, h_ref, out_ref, *,
                  metric: str, gamma: float, n_ingress: int, n_caches: int,
                  n_feat: int | None):
    rt = pl.program_id(1)
    x = x_ref[...]                              # (BR, D) request coords
    y = y_ref[...]                              # (BO, D) candidate coords
    lam = lam_ref[...].astype(jnp.float32)      # (I, BR)
    cur = cur_ref[...].astype(jnp.float32)      # (I, BR)
    h = h_ref[...].astype(jnp.float32)          # (I, J)

    ca = _ca_block(x, y, metric, gamma, n_feat)  # (BR, BO) — computed once

    @pl.when(rt == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    for i in range(n_ingress):                  # static unroll: segments
        slack_i = cur[i, :][:, None] - ca       # (BR, BO)
        lam_i = lam[i, :][:, None]              # (BR, 1)
        for j in range(n_caches):               # static unroll: J small
            contrib = jnp.maximum(slack_i - h[i, j], 0.0)
            out_ref[j, :] += jnp.sum(lam_i * contrib, axis=0)


@functools.partial(jax.jit, static_argnames=(
    "metric", "gamma", "br", "bo", "interpret", "n_feat"))
def _gains_pallas(x, y, lam, cur, hreq, metric: str, gamma: float,
                  br: int, bo: int, interpret: bool,
                  n_feat: int | None = None) -> jax.Array:
    """Pre-padded inputs: R % br == 0, O % bo == 0. Returns (J, O) f32.
    ``n_feat`` is the feature count before lane padding (None: all D)."""
    R, D = x.shape
    O, _ = y.shape
    I, J = hreq.shape
    assert R % br == 0 and O % bo == 0, (R, O, br, bo)
    assert lam.shape == cur.shape == (I, R), (lam.shape, cur.shape)
    grid = (O // bo, R // br)
    kernel = functools.partial(_gains_kernel, metric=metric, gamma=gamma,
                               n_ingress=I, n_caches=J, n_feat=n_feat)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, D), lambda ot, rt: (rt, 0)),
            pl.BlockSpec((bo, D), lambda ot, rt: (ot, 0)),
            pl.BlockSpec((I, br), lambda ot, rt: (0, rt)),
            pl.BlockSpec((I, br), lambda ot, rt: (0, rt)),
            pl.BlockSpec((I, J), lambda ot, rt: (0, 0)),
        ],
        out_specs=pl.BlockSpec((J, bo), lambda ot, rt: (0, ot)),
        out_shape=jax.ShapeDtypeStruct((J, O), jnp.float32),
        interpret=interpret,
    )(x, y, lam, cur, hreq)


def _fold_tile(ca_t, lam, cur, h):
    """(T, J) gains of one candidate tile given its (R, T) C_a columns."""
    I, J = h.shape
    cols = []
    for j in range(J):
        acc = jnp.zeros((ca_t.shape[1],), jnp.float32)
        for i in range(I):
            m = jnp.maximum(cur[i, :][:, None] - h[i, j] - ca_t, 0.0)
            acc = acc + lam[i, :] @ m
        cols.append(acc)
    return jnp.stack(cols, axis=1)


def _gains_tiles_jnp(x, y, lam, cur, hreq, metric: str, gamma: float,
                     bo: int) -> jax.Array:
    """Blocked jnp oracle: lax.map over candidate tiles — the (R, O)
    distance matrix never materializes, so it scales to catalogs where
    a dense C_a is impossible. Inputs pre-padded to O % bo == 0;
    returns (O, J) f32."""
    O = y.shape[0]
    tiles = y.reshape(O // bo, bo, y.shape[1])

    def tile_fn(y_t):
        return _fold_tile(_ca_block(x, y_t, metric, gamma), lam, cur, hreq)

    return jax.lax.map(tile_fn, tiles).reshape(O, hreq.shape[1])


def _lb_gains_tiles_jnp(x, yp, lam, cur, hreq, metric: str, gamma: float,
                        bo: int) -> jax.Array:
    """Quantized twin of :func:`_gains_tiles_jnp`: per candidate tile the
    C_a block is replaced by quant.py's *certified lower bound* over the
    int8 images (requests quantized once, candidate tiles on the fly).
    lb ≤ C_a elementwise makes every relu slack — hence every gain — an
    **upper bound** on the exact oracle's, which is exactly the
    admissible direction lazy GREEDY needs: seed the stale upper bounds
    with quantized gains, let the top-k refresh re-score candidates
    exactly before any acceptance, and the picked allocation is
    bit-identical to the all-exact run (see placement.device_greedy).
    """
    qx, sx = quant.quantize_int8(x)
    xd = quant.dequantize_int8(qx, sx)
    rx = quant.quant_row_radius(sx[:, 0], x.shape[1], metric)
    x_sq = jnp.sum(xd * xd, -1) if metric in ("l2", "l2sq") else None
    O = yp.shape[0]
    tiles = yp.reshape(O // bo, bo, yp.shape[1])

    def tile_fn(y_t):
        kq = quant.quantize_rows(y_t, metric)
        kd = quant.dequantize_int8(kq.q, kq.scale)
        lb = quant.lb_approx_cost_block(xd, kd, rx, kq.radius, metric,
                                        gamma, q_sq=x_sq, k_sq=kq.sq_norm)
        return _fold_tile(lb, lam, cur, hreq)

    return jax.lax.map(tile_fn, tiles).reshape(O, hreq.shape[1])


@functools.partial(jax.jit, static_argnames=(
    "metric", "gamma", "br", "bo", "use_pallas", "interpret", "quantize"))
def placement_gains(x: jax.Array, y: jax.Array, lam: jax.Array,
                    cur: jax.Array, hreq: jax.Array, metric: str = "l2",
                    gamma: float = 1.0, br: int = DEFAULT_BR,
                    bo: int = DEFAULT_BO, use_pallas: bool | None = None,
                    interpret: bool | None = None,
                    quantize: bool = False) -> jax.Array:
    """(O, J) marginal gains of every candidate approximizer (o', j).

    x: (R, D) request-object coords; y: (O, D) candidate coords;
    lam, cur: (I, R) per-(ingress, object) rates and current serving
    costs; hreq: (I, J) ingress→cache retrieval costs (+inf allowed:
    mapped to ``H_SENTINEL``). ``use_pallas=None`` → Pallas on TPU,
    blocked jnp elsewhere. ``quantize=True`` computes certified gain
    *upper bounds* over int8 images instead (always the blocked jnp
    path — the compressed tables stream through plain XLA matmuls);
    see :func:`_lb_gains_tiles_jnp` for the admissibility contract.
    """
    n_obj = y.shape[0]
    hreq = jnp.where(jnp.isfinite(hreq), hreq, H_SENTINEL).astype(jnp.float32)
    lam = lam.astype(jnp.float32)
    cur = cur.astype(jnp.float32)
    if quantize:
        yp = _pad_axis(y.astype(jnp.float32), bo, 0, "zero")
        out = _lb_gains_tiles_jnp(x.astype(jnp.float32), yp, lam, cur,
                                  hreq, metric, gamma, bo)
        return out[:n_obj]
    if use_pallas is None:
        use_pallas = _on_tpu()
    if not use_pallas:
        yp = _pad_axis(y.astype(jnp.float32), bo, 0, "zero")
        out = _gains_tiles_jnp(x.astype(jnp.float32), yp, lam, cur, hreq,
                               metric, gamma, bo)
        return out[:n_obj]
    if interpret is None:
        interpret = not _on_tpu()
    xp = _pad_axis(_pad_axis(x.astype(jnp.float32), LANE, 1, "zero"),
                   br, 0, "zero")
    yp = _pad_axis(_pad_axis(y.astype(jnp.float32), LANE, 1, "zero"),
                   bo, 0, "zero")
    lamp = _pad_axis(lam, br, 1, "zero")
    curp = _pad_axis(cur, br, 1, "zero")
    out = _gains_pallas(xp, yp, lamp, curp, hreq, metric=metric, gamma=gamma,
                        br=br, bo=bo, interpret=interpret, n_feat=x.shape[1])
    return out[:, :n_obj].T


@functools.partial(jax.jit, static_argnames=("bo", "quantize"))
def placement_gains_matrix(ca: jax.Array, lam: jax.Array, cur: jax.Array,
                           hreq: jax.Array, bo: int = DEFAULT_BO,
                           quantize: bool = False) -> jax.Array:
    """Gain oracle over an explicit device-resident C_a matrix.

    ca: (R, O) approximation costs C_a[r, o']; lam, cur: (I, R);
    hreq: (I, J). Returns (O, J) f32 — the small-instance twin of
    :func:`placement_gains` for Instances built from a ca_matrix.
    ``quantize=True`` replaces each C_a row by the certified lower bound
    of its int8 image, relu(deq − ELEM_ERR·scale) ≤ ca (the per-element
    error budget of kernels/quant.py, with its safety margin absorbing
    the subtraction's own f32 rounding), making the returned gains
    admissible upper bounds exactly like :func:`placement_gains`'s.
    """
    n_obj = ca.shape[1]
    hreq = jnp.where(jnp.isfinite(hreq), hreq, H_SENTINEL).astype(jnp.float32)
    lam = lam.astype(jnp.float32)
    cur = cur.astype(jnp.float32)
    if quantize:
        qc, sc = quant.quantize_int8(ca.astype(jnp.float32))
        ca = jnp.maximum(quant.dequantize_int8(qc, sc)
                         - quant.ELEM_ERR * sc, 0.0)
    cat = _pad_axis(ca.astype(jnp.float32), bo, 1, "zero").T  # (O_pad, R)
    tiles = cat.reshape(cat.shape[0] // bo, bo, cat.shape[1])

    def tile_fn(ca_t):
        return _fold_tile(ca_t.T, lam, cur, hreq)

    out = jax.lax.map(tile_fn, tiles).reshape(cat.shape[0], hreq.shape[1])
    return out[:n_obj]


@functools.partial(jax.jit, static_argnames=(
    "mesh", "axes", "metric", "gamma", "br", "bo", "use_pallas",
    "interpret", "quantize"))
def sharded_placement_gains(x: jax.Array, y: jax.Array, lam: jax.Array,
                            cur: jax.Array, hreq: jax.Array, mesh,
                            axes: tuple[str, ...], metric: str = "l2",
                            gamma: float = 1.0, br: int = DEFAULT_BR,
                            bo: int = DEFAULT_BO,
                            use_pallas: bool | None = None,
                            interpret: bool | None = None,
                            quantize: bool = False) -> jax.Array:
    """Mesh-sharded gain oracle: one local oracle launch per candidate
    shard.

    The candidate tensor ``y`` is partitioned into contiguous balanced
    chunks over the product of the ``axes`` sizes (requests, rates and
    costs replicated — they are O(I·R) scalars, tiny next to the O×R
    tile stream), each shard folds its own chunk, and the (O, J) gain
    matrix comes back sharded on the candidate axis. Every candidate's
    sum is computed with the same request tiling as the single-device
    entry, so values are bit-identical shard-count-independently — the
    control-plane mirror of ``sharded_fused_lookup``'s contract.
    """
    n_shards = mesh_axes_size(mesh, axes)
    n_obj = y.shape[0]
    yp = _pad_axis(y.astype(jnp.float32), n_shards * bo, 0, "zero")
    spec = P(tuple(axes))

    def shard_fn(xs, ys, lams, curs, hs):
        return placement_gains(xs, ys, lams, curs, hs, metric=metric,
                               gamma=gamma, br=br, bo=bo,
                               use_pallas=use_pallas, interpret=interpret,
                               quantize=quantize)

    out = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(), spec, P(), P(), P()),
        out_specs=P(tuple(axes), None),
        check_vma=False)(x.astype(jnp.float32), yp, lam, cur, hreq)
    return out[:n_obj]
