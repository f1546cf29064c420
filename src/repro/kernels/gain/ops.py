"""Jitted public wrapper for the GREEDY gain kernel (padding + transpose)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.gain.gain import (DEFAULT_BO, DEFAULT_BR, H_SENTINEL,
                                     gain_pallas)
from repro.kernels.gain.ref import gain_ref
from repro.kernels.knn.ops import LANE, _on_tpu, _pad_axis


@functools.partial(jax.jit, static_argnames=("metric", "gamma", "br", "bo",
                                              "use_pallas", "interpret"))
def greedy_gain(x: jax.Array, y: jax.Array, lam: jax.Array, cur: jax.Array,
                hreq: jax.Array, metric: str = "l2", gamma: float = 1.0,
                br: int = DEFAULT_BR, bo: int = DEFAULT_BO,
                use_pallas: bool = True, interpret: bool | None = None
                ) -> jax.Array:
    """(O, J) marginal gains for all candidate approximizers.

    x: (R, D) request embeddings; y: (O, D) candidate objects; lam, cur:
    (R,) rates and current serving costs; hreq: (R, J) ingress→cache
    retrieval costs (+inf allowed: mapped to a finite sentinel).
    """
    n_obj = y.shape[0]
    hreq = jnp.where(jnp.isfinite(hreq), hreq, H_SENTINEL)
    if not use_pallas:
        return gain_ref(x, y, lam, cur, hreq, metric, gamma)
    if interpret is None:
        interpret = not _on_tpu()
    xp = _pad_axis(_pad_axis(x.astype(jnp.float32), LANE, 1, "zero"),
                   br, 0, "zero")
    yp = _pad_axis(_pad_axis(y.astype(jnp.float32), LANE, 1, "zero"),
                   bo, 0, "zero")
    lamp = _pad_axis(lam.astype(jnp.float32)[:, None], br, 0, "zero")
    curp = _pad_axis(cur.astype(jnp.float32)[:, None], br, 0, "zero")
    hp = _pad_axis(hreq.astype(jnp.float32), br, 0, "zero")
    out = gain_pallas(xp, yp, lamp, curp, hp, metric=metric, gamma=gamma,
                      br=br, bo=bo, interpret=interpret, n_feat=x.shape[1])
    return out[:, :n_obj].T
