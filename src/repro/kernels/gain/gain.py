"""Pallas TPU kernel: fused GREEDY marginal-gain reduction.

Computes gain[j, o'] = Σ_r λ_r · relu(cur_r − C_a(x_r, y_{o'}) − H[r, j])
without materializing the (R, O) distance matrix in HBM: each grid step
computes one (BR, BO) distance tile on the MXU and immediately folds it
into the (J, BO) accumulator tile, turning GREEDY's dominant cost (§3.2:
O_R·N·O·K evaluations) into a stream of fused matmul+reduce tiles.

  * grid = (O//BO, R//BR); the request axis is minor, so each candidate
    tile accumulates over request tiles sequentially in its VMEM output
    block (same accumulation idiom as kernels/knn).
  * outputs are (J, O) — J (number of caches, small) in sublanes, O in
    lanes — transposed back by ops.py.
  * the per-cache loop over j is a static unroll (J ≤ 16 in practice).

Padding contracts (enforced by ops.py): R padded with λ = 0 rows (their
contribution vanishes), O padded and sliced off afterwards, D zero-padded
(distance-preserving), off-path entries of H use a large finite sentinel
(relu clamps them to zero gain; +inf would generate NaNs via inf−inf).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.knn.knn import _distance_block

DEFAULT_BR = 256
DEFAULT_BO = 256
H_SENTINEL = 1.0e30      # "off-path" finite stand-in for +inf


def _gain_kernel(x_ref, y_ref, lam_ref, cur_ref, h_ref, out_ref, *,
                 metric: str, gamma: float, n_caches: int,
                 n_feat: int | None):
    rt = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)          # (BR, D)
    y = y_ref[...].astype(jnp.float32)          # (BO, D)
    lam = lam_ref[...].astype(jnp.float32)      # (BR, 1)
    cur = cur_ref[...].astype(jnp.float32)      # (BR, 1)
    h = h_ref[...].astype(jnp.float32)          # (BR, J)

    ca = _distance_block(x, y, metric, n_feat)  # (BR, BO)
    if gamma != 1.0:
        ca = jnp.power(jnp.maximum(ca, 0.0), gamma)
    slack = cur - ca                            # (BR, BO)

    @pl.when(rt == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    for j in range(n_caches):                   # static unroll, J small
        contrib = jnp.maximum(slack - h[:, j:j + 1], 0.0)     # (BR, BO)
        out_ref[j, :] += jnp.sum(lam * contrib, axis=0)


@functools.partial(jax.jit, static_argnames=(
    "metric", "gamma", "br", "bo", "interpret", "n_feat"))
def gain_pallas(x: jax.Array, y: jax.Array, lam: jax.Array, cur: jax.Array,
                hreq: jax.Array, metric: str = "l2", gamma: float = 1.0,
                br: int = DEFAULT_BR, bo: int = DEFAULT_BO,
                interpret: bool = True, n_feat: int | None = None
                ) -> jax.Array:
    """Pre-padded inputs: R % br == 0, O % bo == 0. Returns (J, O) f32.
    ``n_feat`` is the feature count before lane padding (None: all D)."""
    R, D = x.shape
    O, _ = y.shape
    J = hreq.shape[1]
    assert R % br == 0 and O % bo == 0, (R, O, br, bo)
    grid = (O // bo, R // br)
    kernel = functools.partial(_gain_kernel, metric=metric, gamma=gamma,
                               n_caches=J, n_feat=n_feat)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, D), lambda ot, rt: (rt, 0)),
            pl.BlockSpec((bo, D), lambda ot, rt: (ot, 0)),
            pl.BlockSpec((br, 1), lambda ot, rt: (rt, 0)),
            pl.BlockSpec((br, 1), lambda ot, rt: (rt, 0)),
            pl.BlockSpec((br, J), lambda ot, rt: (rt, 0)),
        ],
        out_specs=pl.BlockSpec((J, bo), lambda ot, rt: (0, ot)),
        out_shape=jax.ShapeDtypeStruct((J, O), jnp.float32),
        interpret=interpret,
    )(x, y, lam, cur, hreq)
    return out
