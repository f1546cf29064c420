"""Decoder-only LM stack covering the dense / moe / hybrid / ssm / vlm
families: scan-over-super-blocks (after an optional group of leading
dense layers), GQA or multi-head latent attention with pluggable
sharding strategies, MoE, Mamba, xLSTM mixers, RoPE / M-RoPE.

Modes:
  * "train"   — full-sequence teacher forcing, optional remat per block;
  * "prefill" — like train but returns the serving cache (KV / SSM state);
  * "decode"  — one token per call against a statically-shaped cache.

The cache is a dict keyed like params["blocks"] (and "lead_blocks") with
per-kind leaves stacked on the scanned layer axis (see init_cache).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers
from repro.models.moe import moe_dropless, moe_mlp
from repro.models.schema import LEAD, block_key, block_pattern
from repro.models.sharding_api import NO_SHARD, ShardPolicy
from repro.models.ssm import mamba_mixer, mlstm_mixer, slstm_mixer

Cache = Any


# ----------------------------------------------------------- sub-layers --
def _kv_quant(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric int8 per-(token, head) quantization for KV caches."""
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-10)
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _attention(cfg: ArchConfig, p: dict, x: jax.Array, positions, mode: str,
               cache: dict | None, pos, shard: ShardPolicy,
               mrope_pos=None, pfx: str = "", cross_src=None,
               causal: bool = True):
    """Attention sublayer (self or cross). Returns (out, new_cache)."""
    dt = x.dtype
    h = layers.rms_norm(x, p[f"{pfx}attn_norm"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhe->bshe", h, p[f"{pfx}wq"].astype(dt))
    if cfg.qkv_bias and f"{pfx}bq" in p:
        q = q + p[f"{pfx}bq"].astype(dt)

    if pfx == "x":
        # cross attention: K/V from the encoder output (cached at prefill,
        # read back from the cache during decode)
        if cross_src is None:
            assert cache is not None and "xk" in cache
            k, v = cache["xk"].astype(dt), cache["xv"].astype(dt)
            new_cache = {"xk": cache["xk"], "xv": cache["xv"]}
        else:
            k = jnp.einsum("bsd,dhe->bshe", cross_src, p[f"{pfx}wk"].astype(dt))
            v = jnp.einsum("bsd,dhe->bshe", cross_src, p[f"{pfx}wv"].astype(dt))
            new_cache = {"xk": k, "xv": v} if mode == "prefill" else {}
        q = shard(q, ("attn_batch", "attn_seq", "heads", "head_dim"))
        out = layers.gqa_attention(q, k, v, causal=False)
    else:
        k = jnp.einsum("bsd,dhe->bshe", h, p[f"{pfx}wk"].astype(dt))
        v = jnp.einsum("bsd,dhe->bshe", h, p[f"{pfx}wv"].astype(dt))
        if cfg.qkv_bias and f"{pfx}bk" in p:
            k = k + p[f"{pfx}bk"].astype(dt)
            v = v + p[f"{pfx}bv"].astype(dt)
        if cfg.mrope and mrope_pos is not None:
            q = layers.apply_mrope(q, mrope_pos, cfg.mrope_sections,
                                   cfg.rope_theta)
            k = layers.apply_mrope(k, mrope_pos, cfg.mrope_sections,
                                   cfg.rope_theta)
        else:
            q = layers.apply_rope(q, positions, cfg.rope_theta)
            k = layers.apply_rope(k, positions, cfg.rope_theta)

        new_cache = {}
        if mode == "decode":
            assert cache is not None
            q8 = cfg.kv_cache_dtype == "int8"
            if q8:
                # quantized KV cache: int8 payload + per-(token, head)
                # f32 scales — halves the decode HBM floor (§Perf)
                kq, ks = _kv_quant(k)
                vq, vs = _kv_quant(v)
                upd = jax.lax.dynamic_update_slice
                kc = upd(cache["k"], kq, (0, pos, 0, 0))
                vc = upd(cache["v"], vq, (0, pos, 0, 0))
                ksc = upd(cache["k_s"], ks, (0, pos, 0, 0))
                vsc = upd(cache["v_s"], vs, (0, pos, 0, 0))
                new_cache = {"k": kc, "v": vc, "k_s": ksc, "v_s": vsc}
                kf = kc.astype(dt) * ksc.astype(dt)
                vf = vc.astype(dt) * vsc.astype(dt)
            else:
                kc = jax.lax.dynamic_update_slice(
                    cache["k"], k.astype(cache["k"].dtype), (0, pos, 0, 0))
                vc = jax.lax.dynamic_update_slice(
                    cache["v"], v.astype(cache["v"].dtype), (0, pos, 0, 0))
                new_cache = {"k": kc, "v": vc}
                kf, vf = kc.astype(dt), vc.astype(dt)
            kf = shard(kf, ("batch", "kv_seq", "kv_heads", "head_dim"))
            vf = shard(vf, ("batch", "kv_seq", "kv_heads", "head_dim"))
            q = shard(q, ("attn_batch", "attn_seq", "heads", "head_dim"))
            out = layers.gqa_attention(q, kf, vf,
                                       causal=False, kv_len=pos + 1)
        else:
            if mode == "prefill":
                new_cache = {"k": k, "v": v}
            if shard.kv_repeat > 1:
                k = jnp.repeat(k, shard.kv_repeat, axis=2)
                v = jnp.repeat(v, shard.kv_repeat, axis=2)
            q = shard(q, ("attn_batch", "attn_seq", "heads", "head_dim"))
            k = shard(k, ("attn_batch", "attn_seq", "rep_kv_heads", "head_dim"))
            v = shard(v, ("attn_batch", "attn_seq", "rep_kv_heads", "head_dim"))
            if cfg.use_flash_attention:
                from repro.kernels.flash_attention import flash_attention
                out = flash_attention(q, k, v, causal=causal)
            else:
                out = layers.gqa_attention(q, k, v, causal=causal)
    out = shard(out, ("attn_batch", "attn_seq", "heads", "head_dim"))
    y = jnp.einsum("bshe,hed->bsd", out, p[f"{pfx}wo"].astype(dt))
    return shard(y, ("batch", "seq", "embed")), new_cache


def _mla_attention(cfg: ArchConfig, p: dict, x: jax.Array, positions,
                   mode: str, cache: dict | None, pos, shard: ShardPolicy):
    """Multi-head latent attention (DeepSeek-V3, no query latent).
    Per position the cache holds the normed latent (``ckv``,
    kv_lora_rank wide) and the roped key that all heads share (``kpe``,
    qk_rope_dim wide); every head's key and value are expanded from the
    latent. A head's query and key are its rope-free part (head_dim)
    then the rotary part, so scores are scaled by that total width."""
    dt = x.dtype
    dh, r = cfg.head_dim, cfg.kv_lora_rank
    h = layers.rms_norm(x, p["attn_norm"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhe->bshe", h, p["wq"].astype(dt))
    q = jnp.concatenate([q[..., :dh], layers.apply_rope_interleaved(
        q[..., dh:], positions, cfg.rope_theta)], axis=-1)
    kv = jnp.einsum("bsd,de->bse", h, p["w_kv_a"].astype(dt))
    ckv = layers.rms_norm(kv[..., :r], p["kv_norm"], cfg.norm_eps)
    kpe = layers.apply_rope_interleaved(kv[..., None, r:], positions,
                                        cfg.rope_theta)[:, :, 0]
    new_cache, kv_len = {}, None
    if mode == "decode":
        upd = jax.lax.dynamic_update_slice
        ckv = upd(cache["ckv"], ckv.astype(cache["ckv"].dtype), (0, pos, 0))
        kpe = upd(cache["kpe"], kpe.astype(cache["kpe"].dtype), (0, pos, 0))
        new_cache = {"ckv": ckv, "kpe": kpe}
        ckv, kpe, kv_len = ckv.astype(dt), kpe.astype(dt), pos + 1
    elif mode == "prefill":
        new_cache = {"ckv": ckv, "kpe": kpe}
    k = jnp.einsum("btr,rhe->bthe", ckv, p["wk_b"].astype(dt))
    v = jnp.einsum("btr,rhe->bthe", ckv, p["wv_b"].astype(dt))
    k = jnp.concatenate([k, jnp.broadcast_to(
        kpe[:, :, None], k.shape[:3] + kpe.shape[-1:])], axis=-1)
    q = shard(q, ("attn_batch", "attn_seq", "heads", "head_dim"))
    out = layers.gqa_attention(q, k, v, causal=mode != "decode",
                               kv_len=kv_len)
    out = shard(out, ("attn_batch", "attn_seq", "heads", "head_dim"))
    y = jnp.einsum("bshe,hed->bsd", out, p["wo"].astype(dt))
    return shard(y, ("batch", "seq", "embed")), new_cache


def _mlp(cfg: ArchConfig, p: dict, x: jax.Array, shard: ShardPolicy):
    h = layers.rms_norm(x, p["mlp_norm"], cfg.norm_eps)
    y = layers.swiglu(h, p["w_gate"], p["w_up"], p["w_down"])
    return shard(y, ("batch", "seq", "embed"))


def _moe(cfg: ArchConfig, p: dict, x: jax.Array, shard: ShardPolicy,
         mode: str):
    """Routed experts plus the shared experts. Returns (y, aux loss,
    rows each held expert computed or None). Training dispatches at the
    configured capacity; serving (prefill, decode) drops nothing, so a
    token's output never depends on the other tokens of its batch."""
    h = layers.rms_norm(x, p["moe_norm"], cfg.norm_eps)
    rule = dict(rule=cfg.moe_router, bias=p.get("router_bias"),
                scale=cfg.moe_routed_scale)
    rows = None
    if mode == "train":
        y, aux = moe_mlp(h, p["router"], p["we_gate"], p["we_up"],
                         p["we_down"], topk=cfg.moe_topk,
                         capacity_factor=cfg.capacity_factor,
                         group_size=cfg.moe_group_size,
                         dispatch=cfg.moe_dispatch, shard=shard, **rule)
    else:
        B, S, D = h.shape
        y, rows = moe_dropless(h.reshape(B * S, D), p["router"],
                               p["we_gate"], p["we_up"], p["we_down"],
                               topk=cfg.moe_topk,
                               first=cfg.held[0], **rule)
        y, aux = y.reshape(B, S, D), jnp.float32(0.0)
    if cfg.shared_ff:
        y = y + layers.swiglu(h, p["ws_gate"], p["ws_up"], p["ws_down"])
    return shard(y, ("batch", "seq", "embed")), aux, rows


def apply_block(cfg: ArchConfig, kind: str, p: dict, x: jax.Array, *,
                positions, mode: str, cache, pos, shard: ShardPolicy,
                mrope_pos=None, cross_src=None):
    """One decoder block (mixer + FFN [+ cross-attn]). Returns
    (x, new_cache, aux_loss, rows of each held expert or None)."""
    aux, rows = jnp.float32(0.0), None
    new_cache: dict = {}
    if kind in ("mlstm", "slstm"):
        mixer = mlstm_mixer if kind == "mlstm" else slstm_mixer
        norm_key = "m_norm" if kind == "mlstm" else "s_norm"
        h = layers.rms_norm(x, p[norm_key], cfg.norm_eps)
        y, st = mixer(h, p, cfg, state=cache if cache else None, mode=mode)
        x = x + shard(y, ("batch", "seq", "embed"))
        if st is not None:
            new_cache.update(st)
        return x, new_cache, aux, rows

    mixer_kind, ffn_kind = kind.split("+")
    if mixer_kind == "attn" and cfg.kv_lora_rank:
        y, kvc = _mla_attention(cfg, p, x, positions, mode, cache, pos,
                                shard)
        x = x + y
        new_cache.update(kvc)
    elif mixer_kind == "attn":
        y, kvc = _attention(cfg, p, x, positions, mode, cache, pos, shard,
                            mrope_pos=mrope_pos)
        x = x + y
        new_cache.update(kvc)
    else:  # mamba
        h = layers.rms_norm(x, p["m_norm"], cfg.norm_eps)
        st_in = {k: cache[k] for k in ("h", "conv")} \
            if (cache and "h" in cache) else None
        y, st = mamba_mixer(h, p, cfg, state=st_in, mode=mode)
        x = x + shard(y, ("batch", "seq", "embed"))
        if st is not None:
            new_cache.update(st)

    if cfg.is_encdec and (cross_src is not None
                          or (cache and "xk" in cache)):
        y, xc = _attention(cfg, p, x, positions, mode, cache, pos, shard,
                           pfx="x", cross_src=cross_src)
        x = x + y
        new_cache.update(xc)

    if ffn_kind == "moe":
        y, aux, rows = _moe(cfg, p, x, shard, mode)
        x = x + y
    elif cfg.d_ff or cfg.dense_ff:
        x = x + _mlp(cfg, p, x, shard)
    return x, new_cache, aux, rows


# ------------------------------------------------------------- the stack -
def _scan_group(cfg: ArchConfig, pattern: list, lead: bool, stacked: dict,
                caches, x, *, positions, mode: str, pos, shard: ShardPolicy,
                mrope_pos=None, cross_src=None):
    """Scan one group of layers, ``pattern`` repeated along the leading
    axis of ``stacked``. Returns (x, new caches, aux loss, rows)."""
    n_super = jax.tree.leaves(stacked)[0].shape[0]
    want_cache = mode in ("prefill", "decode")

    def super_block(x, block_params, block_caches):
        new_caches, rows = {}, {}
        aux_sum = jnp.float32(0.0)
        for bi, kind in enumerate(pattern):
            key = block_key(bi, kind, lead)
            x, nc, aux, r = apply_block(
                cfg, kind, block_params[key], x,
                positions=positions, mode=mode,
                cache=block_caches.get(key) if block_caches else None,
                pos=pos, shard=shard, mrope_pos=mrope_pos,
                cross_src=cross_src)
            new_caches[key] = nc
            if r is not None:
                rows[key] = r
            aux_sum = aux_sum + aux
        return x, new_caches, aux_sum, rows

    if cfg.remat and mode == "train":
        super_block = jax.checkpoint(super_block)

    def scan_body(carry, xs):
        x, aux_acc = carry
        block_params, block_caches = xs
        x, new_caches, aux, rows = super_block(x, block_params, block_caches)
        return (x, aux_acc + aux), (new_caches, rows)

    caches_xs = caches if caches is not None else {k: {} for k in stacked}
    if cfg.scan_layers and n_super > 1:
        (x, aux), (new_caches, rows) = jax.lax.scan(
            scan_body, (x, jnp.float32(0.0)), (stacked, caches_xs))
    else:
        # unrolled (n_super == 1 or scan disabled)
        aux = jnp.float32(0.0)
        outs = []
        for i in range(n_super):
            sl = jax.tree.map(lambda a: a[i], stacked)
            cl = jax.tree.map(lambda a: a[i], caches_xs) if caches else None
            (x, aux), out = scan_body((x, aux), (sl, cl))
            outs.append(out)
        new_caches, rows = jax.tree.map(lambda *zs: jnp.stack(zs), *outs)
    return x, (new_caches if want_cache else None), aux, rows


def decoder_stack(cfg: ArchConfig, params: dict, x: jax.Array, *,
                  positions, mode: str, caches, pos, shard: ShardPolicy,
                  mrope_pos=None, cross_src=None):
    """The leading dense layers, then the super-block pattern, over x.
    caches: dict block_key → pytree stacked on its group's layer axis
    (or None). Returns (x, new caches, aux loss, rows), where rows maps
    each MoE block key to its (layers, held experts) int32 row counts
    (serving modes only)."""
    groups = [(block_pattern(cfg), False, params["blocks"])]
    if cfg.n_dense_layers:
        groups.insert(0, ([LEAD], True, params["lead_blocks"]))
    new_caches, rows, aux = {}, {}, jnp.float32(0.0)
    for pattern, lead, stacked in groups:
        group_caches = None if caches is None else \
            {k: caches[k] for k in stacked}
        x, nc, a, r = _scan_group(
            cfg, pattern, lead, stacked, group_caches, x,
            positions=positions, mode=mode, pos=pos, shard=shard,
            mrope_pos=mrope_pos, cross_src=cross_src)
        if nc is not None:
            new_caches.update(nc)
        rows.update(r)
        aux = aux + a
    return x, (new_caches if mode in ("prefill", "decode") else None), \
        aux, rows


# ------------------------------------------------------------ full model -
def embed_inputs(cfg: ArchConfig, params: dict, batch: dict,
                 shard: ShardPolicy) -> tuple[jax.Array, jax.Array]:
    """Token (+ stub-frontend) embedding. Returns (x, positions)."""
    dt = jnp.dtype(cfg.compute_dtype)
    tokens = batch["tokens"]
    x = params["embed"][tokens].astype(dt)
    if cfg.frontend == "vision_stub" and "image_embeds" in batch:
        img = jnp.einsum("bse,ed->bsd", batch["image_embeds"].astype(dt),
                         params["vision_proj"].astype(dt))
        x = jnp.concatenate([img, x], axis=1)
    B, S = x.shape[:2]
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    x = shard(x, ("batch", "seq", "embed"))
    return x, positions


def lm_head(cfg: ArchConfig, params: dict, x: jax.Array,
            shard: ShardPolicy) -> jax.Array:
    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x, w.astype(x.dtype))
    return shard(logits, ("batch", "seq", "vocab"))


def forward(cfg: ArchConfig, params: dict, batch: dict, *,
            mode: str = "train", caches=None, pos=0,
            shard: ShardPolicy = NO_SHARD, cross_src=None):
    """Full forward. Returns (logits, new_caches, aux)."""
    x, positions = embed_inputs(cfg, params, batch, shard)
    mrope_pos = batch.get("mrope_positions")
    x, new_caches, aux, _ = decoder_stack(
        cfg, params, x, positions=positions, mode=mode, caches=caches,
        pos=pos, shard=shard, mrope_pos=mrope_pos, cross_src=cross_src)
    logits = lm_head(cfg, params, x, shard)
    return logits, new_caches, aux


def forward_last(cfg: ArchConfig, params: dict, batch: dict,
                 shard: ShardPolicy = NO_SHARD):
    """A prefill that keeps only what a miss needs: the final norm and
    head at the last position, (B, vocab) logits, with no caches. Also
    returns each MoE layer's rows per held expert, (MoE layers, held)
    int32, or None for a model without experts."""
    x, positions = embed_inputs(cfg, params, batch, shard)
    x, _, _, rows = decoder_stack(
        cfg, params, x, positions=positions, mode="prefill", caches=None,
        pos=0, shard=shard)
    logits = lm_head(cfg, params, x[:, -1:], shard)[:, 0]
    rows = jnp.concatenate(list(rows.values())) if rows else None
    return logits, rows


# ---------------------------------------------------------------- caches -
def init_cache(cfg: ArchConfig, batch_size: int, max_len: int,
               dtype=None) -> dict:
    """Statically-shaped serving cache for decode."""
    dt = jnp.dtype(dtype or cfg.compute_dtype)
    pattern = block_pattern(cfg)
    n_super = cfg.pattern_layers // len(pattern)
    kh, dh = cfg.n_kv_heads, cfg.head_dim
    di, n, cw = cfg.d_inner, cfg.ssm_state, cfg.ssm_conv
    nh = cfg.n_heads
    caches = {}
    groups = [(bi, kind, False, n_super) for bi, kind in enumerate(pattern)]
    if cfg.n_dense_layers:
        groups.insert(0, (0, LEAD, True, cfg.n_dense_layers))
    for bi, kind, lead, n_super in groups:
        key = block_key(bi, kind, lead)
        if kind == "mlstm":
            dhe = cfg.ssm_expand * cfg.d_model // nh
            caches[key] = {
                "C": jnp.zeros((n_super, batch_size, nh, dhe, dhe),
                               jnp.float32),
                "n": jnp.zeros((n_super, batch_size, nh, dhe), jnp.float32)}
            continue
        if kind == "slstm":
            dhe = cfg.d_model // nh
            z = jnp.zeros((n_super, batch_size, nh, dhe), jnp.float32)
            caches[key] = {"c": z, "n": z, "h": z}
            continue
        mixer, _ = kind.split("+")
        c = {}
        if mixer == "attn" and cfg.kv_lora_rank:
            c["ckv"] = jnp.zeros((n_super, batch_size, max_len,
                                  cfg.kv_lora_rank), dt)
            c["kpe"] = jnp.zeros((n_super, batch_size, max_len,
                                  cfg.qk_rope_dim), dt)
        elif mixer == "attn":
            if cfg.kv_cache_dtype == "int8":
                c["k"] = jnp.zeros((n_super, batch_size, max_len, kh, dh),
                                   jnp.int8)
                c["v"] = jnp.zeros((n_super, batch_size, max_len, kh, dh),
                                   jnp.int8)
                c["k_s"] = jnp.zeros((n_super, batch_size, max_len, kh, 1),
                                     jnp.float32)
                c["v_s"] = jnp.zeros((n_super, batch_size, max_len, kh, 1),
                                     jnp.float32)
            else:
                c["k"] = jnp.zeros((n_super, batch_size, max_len, kh, dh), dt)
                c["v"] = jnp.zeros((n_super, batch_size, max_len, kh, dh), dt)
        else:
            c["h"] = jnp.zeros((n_super, batch_size, di, n), jnp.float32)
            c["conv"] = jnp.zeros((n_super, batch_size, cw - 1, di), dt)
        if cfg.is_encdec:
            c["xk"] = jnp.zeros((n_super, batch_size, cfg.cross_len, kh, dh), dt)
            c["xv"] = jnp.zeros((n_super, batch_size, cfg.cross_len, kh, dh), dt)
        caches[key] = c
    return caches
