"""Plain reference of the similarity-cache lookup, eq. (1) of the paper:
each request is served by the approximizer that minimises
C_a(q, k)**gamma + h(level of k) over every cached key k, or by the
repository at cost h_repo, whichever is cheaper.

The keys are rebuilt from the allocation (which object sits in which
slot of which level) and the benchmark's own catalog: level j holds the
objects of its slots in slot order, and a key's payload is its object
id. Candidates are ordered level by level, slot by slot, the repository
last, and ties go to the first, which is the order the program states.

``scan_f64`` is the reference, on the host in float64, and ``pair``
prices one given candidate per query. ``scan_device`` is the same scan
in float32 on the device at a stated matmul precision; at ``high``
(three bf16 passes) it is the lookup's control.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

F32_EPS = 2.0 ** -23


@dataclasses.dataclass
class Keys:
    coords: np.ndarray        # (K, D) float32
    h: np.ndarray             # (K,) level cost of each key
    level: np.ndarray         # (K,)
    slot: np.ndarray          # (K,) position within its level
    payload: np.ndarray       # (K,) object id
    offsets: np.ndarray       # (levels,) first key of each level
    h_repo: float

    @property
    def n(self) -> int:
        return len(self.h)


def keys(catalog: np.ndarray, slots: np.ndarray, slot_cache: np.ndarray,
         hs, h_repo: float) -> Keys:
    objs, lv = [], []
    for j in range(len(hs)):
        o = np.asarray(slots)[np.asarray(slot_cache) == j]
        objs.append(o[o >= 0])
        lv.append(np.full(len(objs[-1]), j))
    sizes = np.array([len(o) for o in objs])
    obj = np.concatenate(objs)
    level = np.concatenate(lv)
    offsets = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    return Keys(coords=catalog[obj], h=np.asarray(hs, np.float64)[level],
                level=level, slot=np.arange(len(obj)) - offsets[level],
                payload=obj, offsets=offsets, h_repo=float(h_repo))


def scan_f64(queries: np.ndarray, k: Keys, gamma: float = 1.0,
             block: int = 64) -> tuple[np.ndarray, np.ndarray]:
    """(best candidate, its cost) per query under the l2 metric, in
    float64; candidate ``k.n`` is the repository, which wins only where
    it is strictly cheaper."""
    kc = k.coords.astype(np.float64)
    k2 = np.einsum("kd,kd->k", kc, kc)
    best, cost = [], []
    for s in range(0, len(queries), block):
        q = queries[s:s + block].astype(np.float64)
        c = q @ kc.T
        c *= -2.0
        c += k2[None, :]
        c += np.einsum("qd,qd->q", q, q)[:, None]
        np.maximum(c, 0.0, out=c)
        np.sqrt(c, out=c)
        if gamma != 1.0:
            c **= gamma
        c += k.h[None, :]
        j = np.argmin(c, 1)
        cj = c[np.arange(len(q)), j]
        repo = k.h_repo < cj
        best.append(np.where(repo, k.n, j))
        cost.append(np.where(repo, k.h_repo, cj))
    return np.concatenate(best), np.concatenate(cost)


def pair(queries: np.ndarray, k: Keys, cand: np.ndarray,
         gamma: float = 1.0) -> tuple[np.ndarray, np.ndarray]:
    """(cost, f32 error bound) of candidate ``cand`` for each query, in
    float64. The bound is how far a float32 evaluation of
    |q|² + |k|² − 2q·k may move the cost: d² is off by at most
    8·eps·(|q|² + |k|²), which moves d by at most min(√δ, δ/d). The
    repository's cost is exact."""
    repo = cand >= k.n
    kc = k.coords[np.minimum(cand, k.n - 1)].astype(np.float64)
    q = queries.astype(np.float64)
    d = np.sqrt(np.sum((q - kc) ** 2, 1))
    delta = 8.0 * F32_EPS * (np.sum(q * q, 1) + np.sum(kc * kc, 1))
    err = np.minimum(np.sqrt(delta), delta / np.maximum(d, 1e-300))
    h = k.h[np.minimum(cand, k.n - 1)]
    return (np.where(repo, k.h_repo, d ** gamma + h),
            np.where(repo, 0.0, err))


def scan_device(queries: np.ndarray, k: Keys, precision: str,
                gamma: float = 1.0, block: int = 64):
    """(cost, best) of the float32 scan on the device at ``precision``
    ("highest" | "high" | "default")."""
    prec = {"highest": jax.lax.Precision.HIGHEST,
            "high": jax.lax.Precision.HIGH,
            "default": jax.lax.Precision.DEFAULT}[precision]
    kc = jnp.asarray(k.coords, jnp.float32)
    h = jnp.asarray(np.append(k.h, k.h_repo), jnp.float32)

    @jax.jit
    def one(q, kc, h):
        d2 = (jnp.sum(q * q, 1)[:, None] + jnp.sum(kc * kc, 1)[None, :]
              - 2.0 * jnp.matmul(q, kc.T, precision=prec))
        d = jnp.sqrt(jnp.maximum(d2, 0.0)) ** gamma
        c = jnp.concatenate([d, jnp.zeros((q.shape[0], 1))], 1) + h[None, :]
        b = jnp.argmin(c, 1)
        return jnp.take_along_axis(c, b[:, None], 1)[:, 0], b

    n = len(queries)
    pad = -n % block
    q = np.concatenate([queries, np.repeat(queries[:1], pad, 0)])
    outs = [one(jnp.asarray(q[i:i + block], jnp.float32), kc, h)
            for i in range(0, n + pad, block)]
    cost = np.concatenate([np.asarray(c) for c, _ in outs])[:n]
    best = np.concatenate([np.asarray(b) for _, b in outs])[:n]
    return cost.astype(np.float64), best
