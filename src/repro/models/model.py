"""Public model API: build (init, loss, train-forward, serve-step) from an
ArchConfig. This is the single entry point used by the trainer, the
serving engine, the dry-run and the smoke tests.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import encdec, schema, transformer
from repro.models.sharding_api import NO_SHARD, ShardPolicy

AUX_LOSS_WEIGHT = 0.01
Z_LOSS_WEIGHT = 1e-4


def init_params(cfg: ArchConfig, seed: int = 0) -> dict:
    return schema.init_params(cfg, jax.random.PRNGKey(seed))


def _forward(cfg, params, batch, *, mode, caches, pos, shard):
    if cfg.is_encdec:
        return encdec.encdec_forward(cfg, params, batch, mode=mode,
                                     caches=caches, pos=pos, shard=shard)
    return transformer.forward(cfg, params, batch, mode=mode, caches=caches,
                               pos=pos, shard=shard)


def loss_fn(cfg: ArchConfig, params: dict, batch: dict,
            shard: ShardPolicy = NO_SHARD) -> tuple[jax.Array, dict]:
    """Token cross-entropy (+ MoE aux loss + z-loss). ``batch`` needs
    ``tokens`` (B, S) and ``labels`` (B, S_lab); an optional ``loss_mask``
    zeroes out positions (padding / image prefix / prompt)."""
    logits, _, aux = _forward(cfg, params, batch, mode="train", caches=None,
                              pos=0, shard=shard)
    labels = batch["labels"]
    # logits cover the full input sequence; score the last S_lab positions
    # (vlm: image prefix is unscored by construction)
    S_lab = labels.shape[1]
    logits = logits[:, -S_lab:, :]
    logits_f = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits_f, axis=-1)
    ll = jnp.take_along_axis(logits_f, labels[..., None], axis=-1)[..., 0]
    nll = logz - ll
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.ones_like(nll)
    else:
        mask = mask.astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    ce = jnp.sum(nll * mask) / denom
    zloss = jnp.sum((logz ** 2) * mask) / denom
    total = ce + AUX_LOSS_WEIGHT * aux + Z_LOSS_WEIGHT * zloss
    return total, {"ce": ce, "aux": aux, "zloss": zloss}


def make_train_forward(cfg: ArchConfig, shard: ShardPolicy = NO_SHARD
                       ) -> Callable:
    """(params, batch) → (loss, metrics); jit/pjit-able."""
    return functools.partial(loss_fn, cfg, shard=shard)


def make_prefill(cfg: ArchConfig, shard: ShardPolicy = NO_SHARD) -> Callable:
    def prefill(params, batch):
        logits, caches, _ = _forward(cfg, params, batch, mode="prefill",
                                     caches=None, pos=0, shard=shard)
        return logits, caches
    return prefill


def make_prefill_last(cfg: ArchConfig, shard: ShardPolicy = NO_SHARD
                      ) -> Callable:
    """(params, batch) → ((B, vocab) logits of the last position, MoE row
    counts (MoE layers, held experts) or None): a miss's prefill, with
    no caches and the head at one position (``transformer.forward_last``).
    Jitted, it is the program ``jit_prefill``."""
    def prefill(params, batch):
        return transformer.forward_last(cfg, params, batch, shard)
    return prefill


def make_serve_step(cfg: ArchConfig, shard: ShardPolicy = NO_SHARD
                    ) -> Callable:
    """One decode step: (params, tokens (B,1), caches, pos) →
    (logits (B, 1, V), new caches). ``pos`` is the current sequence
    length (the new token's position)."""
    def serve_step(params, tokens, caches, pos):
        B = tokens.shape[0]
        batch = {"tokens": tokens,
                 "positions": jnp.full((B, 1), pos, jnp.int32)}
        if cfg.mrope:
            batch["mrope_positions"] = jnp.full((3, B, 1), pos, jnp.int32)
        logits, new_caches, _ = _forward(cfg, params, batch, mode="decode",
                                         caches=caches, pos=pos, shard=shard)
        return logits, new_caches
    return serve_step


def init_cache(cfg: ArchConfig, batch_size: int, max_len: int) -> Any:
    return transformer.init_cache(cfg, batch_size, max_len)


def greedy_generate(cfg: ArchConfig, params: dict, prompt: jax.Array,
                    n_steps: int, max_len: int | None = None,
                    shard: ShardPolicy = NO_SHARD) -> jax.Array:
    """Tiny reference sampler (greedy argmax) used by examples/tests."""
    B, S = prompt.shape
    max_len = max_len or (S + n_steps)
    prefill = jax.jit(make_prefill(cfg, shard))
    step = jax.jit(make_serve_step(cfg, shard))
    batch = {"tokens": prompt}
    if cfg.mrope:
        pos = jnp.broadcast_to(jnp.arange(S)[None, None, :], (3, B, S))
        batch["mrope_positions"] = pos
    if cfg.is_encdec:
        raise NotImplementedError("use the serving engine for enc-dec")
    logits, caches = prefill(params, batch)
    # pad the prefill cache out to max_len so decode can extend it
    caches = _pad_caches(cfg, caches, max_len)
    tok = jnp.argmax(logits[:, -1:, :], axis=-1).astype(jnp.int32)
    out = [tok]
    for t in range(n_steps - 1):
        logits, caches = step(params, tok, caches, S + t)
        tok = jnp.argmax(logits[:, -1:, :], axis=-1).astype(jnp.int32)
        out.append(tok)
    return jnp.concatenate(out, axis=1)


def _pad_caches(cfg: ArchConfig, caches: Any, max_len: int) -> Any:
    """Pad prefill KV caches along the sequence axis to ``max_len``.
    Only the self-attention "k"/"v" (latent attention: "ckv"/"kpe")
    leaves grow; SSM/xLSTM states and cross-attention caches are
    fixed-size."""
    from repro.models.transformer import _kv_quant

    def pad_entry(block_cache: dict) -> dict:
        out = dict(block_cache)
        for key in ("ckv", "kpe"):         # latent attention: (n, B, S, w)
            if key in out and out[key].shape[2] < max_len:
                out[key] = jnp.pad(out[key], ((0, 0), (0, 0), (
                    0, max_len - out[key].shape[2]), (0, 0)))
        for key in ("k", "v"):
            if key not in out:
                continue
            x = out[key]
            if cfg.kv_cache_dtype == "int8" and x.dtype != jnp.int8:
                q, sc = _kv_quant(x)
                out[key], out[key + "_s"] = q, sc
                x = q
            if x.shape[2] < max_len:
                pad = ((0, 0), (0, 0), (0, max_len - x.shape[2]),
                       (0, 0), (0, 0))
                out[key] = jnp.pad(out[key], pad)
                if key + "_s" in out:
                    out[key + "_s"] = jnp.pad(out[key + "_s"], pad)
        return out
    return {bk: pad_entry(bc) for bk, bc in caches.items()}
