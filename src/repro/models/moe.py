"""Mixture-of-Experts layer: top-k routing with grouped einsum dispatch.

GShard-style static-capacity dispatch, adapted for TPU SPMD:

  * tokens are split into groups of ``group_size`` so the one-hot
    dispatch/combine tensors stay (G, Tg, E, Cg) with Tg small — memory
    O(T·E·Cg/G) instead of O(T·E·C) (the classic GShard memory cliff);
  * experts run as one stacked einsum over the expert axis, which shards
    cleanly over the mesh "model"/"experts" axis (expert parallelism);
    the combine einsum contracts the expert axis → one all-reduce, the
    canonical EP collective;
  * capacity C_g = ceil(Tg · k · capacity_factor / E); overflow tokens
    are dropped (their residual passes through — standard behaviour);
  * the router computes in f32 and returns the Switch-style load-balance
    auxiliary loss.

Expert-axis sharding requires E % mesh_model == 0; the resolver
(launch/sharding.py) otherwise falls back to within-expert d_ff sharding
(granite-moe: 40 experts, d_ff=512).

Serving (prefill and decode) drops nothing: ``moe_dropless`` sorts the
token-expert assignments by expert and runs the experts as grouped
matmuls (``jax.lax.ragged_dot``) over per-expert row counts, so a
token's output never depends on which other tokens shared its batch.
Its layer holds a contiguous range of the router's experts, as a chip
of an expert-parallel deployment does: it routes over all of them and
adds only the held experts' part; assignments to the others add
nothing.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _capacity(tg: int, k: int, e: int, cf: float) -> int:
    if cf <= 0:                        # no-drop mode (decode): worst case
        return tg * k
    c = int(tg * k * cf / e) + 1
    return max(c, 1)


def route(xt_2d: jax.Array, router: jax.Array, topk: int,
          rule: str = "softmax", bias: jax.Array | None = None,
          scale: float = 1.0):
    """Top-k gates + Switch aux loss. xt_2d: (T, D) → (weights (T, K)
    f32, expert ids (T, K), aux). ``softmax``: the top-k of the softmax,
    renormalised. ``sigmoid`` (DeepSeek-V3): sigmoid scores; the choice
    is the top-k of score + ``bias``, the weights the chosen scores
    normalised to one; both rules then × ``scale``. The router's matmul
    runs in f32 at full precision, so bf16 rounding of the product does
    not move a choice."""
    E = router.shape[1]
    logits = jnp.einsum("td,de->te", xt_2d.astype(jnp.float32),
                        router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    if rule == "sigmoid":
        probs = jax.nn.sigmoid(logits)                      # (T, E)
        _, gate_idx = jax.lax.top_k(probs + bias.astype(jnp.float32), topk)
        gate_vals = jnp.take_along_axis(probs, gate_idx, -1)
        gate_vals = gate_vals / (jnp.sum(gate_vals, -1, keepdims=True)
                                 + 1e-20)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, topk)    # (T, K)
        gate_vals = gate_vals / (jnp.sum(gate_vals, -1, keepdims=True)
                                 + 1e-9)
    me = jnp.mean(probs / jnp.sum(probs, -1, keepdims=True), axis=0)
    fe = jnp.mean(jax.nn.one_hot(gate_idx[..., 0], E), axis=0)
    aux = E * jnp.sum(me * fe)
    return gate_vals * scale, gate_idx, aux


def _positions_in_expert(gate_idx: jax.Array, E: int, cap: int):
    """Capacity assignment, sequential over the K choices.
    gate_idx: (..., T, K) → (pos_in_expert (..., T, K), keep mask)."""
    T_axis = -2
    counts = None
    poss, keeps = [], []
    K = gate_idx.shape[-1]
    for k in range(K):
        idx_k = gate_idx[..., k]
        mask_k = jax.nn.one_hot(idx_k, E, dtype=jnp.int32)   # (..., T, E)
        base = jnp.cumsum(mask_k, axis=T_axis) - mask_k
        if counts is not None:
            base = base + counts[..., None, :]
        pos_k = jnp.sum(base * mask_k, axis=-1)              # (..., T)
        poss.append(pos_k)
        keeps.append(pos_k < cap)
        counts = (0 if counts is None else counts) + \
            jnp.sum(mask_k, axis=T_axis)
    return jnp.stack(poss, -1), jnp.stack(keeps, -1)


def _moe_einsum(x, router, we_gate, we_up, we_down, topk, capacity_factor,
                group_size, shard, rule):
    """GShard-style grouped one-hot dispatch (the TPU-classic baseline).

    The dispatch/combine einsums cost T·E_loc·Cg·D each — under expert
    sharding this does NOT shrink with E, so it can exceed the expert
    matmuls themselves (the known GShard dispatch tax; quantified in
    EXPERIMENTS.md §Perf, where the gather path removes it)."""
    B, S, D = x.shape
    E = router.shape[1]
    T = B * S
    g = min(group_size, T)
    while T % g:                       # group size must divide tokens
        g -= 1
    G, Tg = T // g, g
    Cg = _capacity(Tg, topk, E, capacity_factor)
    xt = x.reshape(G, Tg, D)

    gate_vals, gate_idx, aux = route(x.reshape(T, D), router, topk,
                                     **rule)
    gate_vals = gate_vals.reshape(G, Tg, -1)
    gate_idx = gate_idx.reshape(G, Tg, -1)
    pos, keep = _positions_in_expert(gate_idx, E, Cg)        # (G, Tg, K)

    dispatch = jnp.zeros((G, Tg, E, Cg), x.dtype)
    combine = jnp.zeros((G, Tg, E, Cg), jnp.float32)
    for k in range(gate_idx.shape[-1]):
        mask_k = jax.nn.one_hot(gate_idx[..., k], E, dtype=x.dtype)
        oh_pos = jax.nn.one_hot(jnp.where(keep[..., k], pos[..., k], Cg),
                                Cg, dtype=x.dtype)
        sel = mask_k[..., None] * oh_pos[..., None, :]
        dispatch = dispatch + sel
        combine = combine + sel.astype(jnp.float32) * \
            (gate_vals[..., k] * keep[..., k])[..., None, None]

    xe = jnp.einsum("gtec,gtd->egcd", dispatch, xt)          # (E, G, Cg, D)
    if shard is not None:
        xe = shard(xe, ("experts", "moe_group", None, "embed"))
    h = jax.nn.silu(jnp.einsum("egcd,edf->egcf", xe,
                               we_gate.astype(x.dtype)))
    h = h * jnp.einsum("egcd,edf->egcf", xe, we_up.astype(x.dtype))
    ye = jnp.einsum("egcf,efd->egcd", h, we_down.astype(x.dtype))
    if shard is not None:
        ye = shard(ye, ("experts", "moe_group", None, "embed"))
    y = jnp.einsum("gtec,egcd->gtd", combine.astype(x.dtype), ye)
    return y.reshape(B, S, D), aux


def _moe_gather(x, router, we_gate, we_up, we_down, topk, capacity_factor,
                group_size, shard, rule):
    """Grouped gather/scatter dispatch (beyond-paper optimization, §Perf).

    Replaces the O(T·E·Cg·D) one-hot dispatch/combine einsums with
    O(slots·D) batched gathers. Groups follow the token (batch) sharding,
    so every scatter/gather stays shard-local under SPMD — the only MoE
    collective left is the expert-contraction all-reduce. Dispatch FLOPs
    ≈ 0 (pure data movement); capacity/drop semantics identical to the
    einsum path (same _positions_in_expert)."""
    B, S, D = x.shape
    E = router.shape[1]
    T = B * S
    g = min(group_size, T)
    while T % g:
        g -= 1
    G, Tg = T // g, g
    Cg = _capacity(Tg, topk, E, capacity_factor)
    xt = x.reshape(G, Tg, D)

    gate_vals, gate_idx, aux = route(x.reshape(T, D), router, topk,
                                     **rule)
    gate_vals = gate_vals.reshape(G, Tg, -1)                 # (G, Tg, K)
    gate_idx = gate_idx.reshape(G, Tg, -1)
    pos, keep = _positions_in_expert(gate_idx, E, Cg)        # (G, Tg, K)

    slot = gate_idx * Cg + pos                               # (G, Tg, K)
    slot = jnp.where(keep, slot, E * Cg)                     # overflow slot
    tok_ids = jnp.broadcast_to(
        jnp.arange(Tg, dtype=jnp.int32)[None, :, None], slot.shape)
    token_of_slot = jnp.zeros((G, E * Cg), jnp.int32)
    token_of_slot = token_of_slot.at[
        jnp.arange(G, dtype=jnp.int32)[:, None],
        slot.reshape(G, -1)].set(tok_ids.reshape(G, -1), mode="drop")

    xe = jnp.take_along_axis(xt, token_of_slot[..., None], axis=1)
    xe = xe.reshape(G, E, Cg, D)
    if shard is not None:
        xe = shard(xe, ("moe_group", "experts", None, "embed"))
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", xe,
                               we_gate.astype(x.dtype)))
    h = h * jnp.einsum("gecd,edf->gecf", xe, we_up.astype(x.dtype))
    ye = jnp.einsum("gecf,efd->gecd", h, we_down.astype(x.dtype))
    if shard is not None:
        ye = shard(ye, ("moe_group", "experts", None, "embed"))
    ye_flat = ye.reshape(G, E * Cg, D)
    picked = jnp.take_along_axis(
        ye_flat, jnp.minimum(slot.reshape(G, -1), E * Cg - 1)[..., None],
        axis=1).reshape(G, Tg, -1, D)
    picked = jnp.where(keep[..., None], picked, 0.0)
    y = jnp.sum(picked * gate_vals[..., None].astype(x.dtype), axis=2)
    return y.reshape(B, S, D), aux


def moe_mlp(x: jax.Array, router: jax.Array, we_gate: jax.Array,
            we_up: jax.Array, we_down: jax.Array, topk: int,
            capacity_factor: float = 1.25, group_size: int = 512,
            dispatch: str = "einsum", shard=None, **rule
            ) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, D) → (y, aux_loss). Expert weights: (E, D, F)/(E, F, D),
    one per router output. ``rule``: ``route``'s rule, bias and scale."""
    if we_gate.shape[0] != router.shape[1]:
        raise ValueError("the capacity dispatch needs every expert held; "
                         "a chip's share of the experts runs dropless")
    if dispatch == "gather":
        return _moe_gather(x, router, we_gate, we_up, we_down, topk,
                           capacity_factor, group_size, shard, rule)
    return _moe_einsum(x, router, we_gate, we_up, we_down, topk,
                       capacity_factor, group_size, shard, rule)


def moe_dropless(x: jax.Array, router: jax.Array, we_gate: jax.Array,
                 we_up: jax.Array, we_down: jax.Array, topk: int,
                 first: int = 0, **rule) -> tuple[jax.Array, jax.Array]:
    """Every token's routed output from the experts held here, with no
    capacity. x: (T, D); the expert weights hold experts
    [first, first + n) of the router's. Returns (y (T, D), rows (n,)
    int32: the assignments each held expert computed).

    The T·K assignments are sorted by held expert (the others last),
    their tokens gathered into that order, and each held expert's rows
    run as one group of the grouped matmuls; rows past the held ones
    belong to no group and are never read. Each token's k-th row is
    gathered back (zero where its k-th choice is not held) and the rows
    summed weighted by their gates, in f32, with the top-k axis leading
    so that no (T, K, D) relayout is made. The shapes depend on T alone,
    so one program serves every routing."""
    T, D = x.shape
    n = we_gate.shape[0]
    gate, idx, _ = route(x, router, topk, **rule)
    local = idx - first                                      # (T, K)
    held = (local >= 0) & (local < n)
    key = jnp.where(held, local, n).reshape(-1)              # (T·K,)
    order = jnp.argsort(key, stable=True)
    rows = jnp.bincount(key, length=n + 1)[:n].astype(jnp.int32)
    xs = x[order // topk]                                    # (T·K, D)
    dt = x.dtype
    h = jax.nn.silu(jax.lax.ragged_dot(xs, we_gate.astype(dt), rows)) \
        * jax.lax.ragged_dot(xs, we_up.astype(dt), rows)
    ys = jax.lax.ragged_dot(h, we_down.astype(dt), rows)     # (T·K, D)
    # where each assignment's row went, (K, T); not held: out of range
    at = jnp.where(held, jnp.argsort(order).reshape(T, topk), T * topk).T
    yk = ys.at[at].get(mode="fill", fill_value=0)            # (K, T, D)
    g = jnp.where(held, gate, 0.0).T                         # (K, T)
    y = jnp.sum(g[:, :, None] * yk.astype(jnp.float32), axis=0)
    return y.astype(dt), rows
