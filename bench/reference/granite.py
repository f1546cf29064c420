"""Plain reference of a Granite-3.0 dense decoder, in float32 jax.numpy.

It follows the published architecture (hf:ibm-granite/granite-3.0-2b-base,
``GraniteForCausalLM``): token embedding times ``embedding_multiplier``;
per layer RMSNorm, grouped-query attention with rotary embeddings
(split-half rotation, base ``rope_theta``), scores scaled by
``attention_multiplier`` under a causal mask, the attention output added
with weight ``residual_multiplier``; RMSNorm, a SwiGLU MLP added with
the same weight; a final RMSNorm and the tied embedding as the output
head, logits divided by ``logits_scaling``. The configuration file says
which values are run.

No kernels, caches or batching tricks: each prompt is run whole, every
matmul at ``Precision.HIGHEST``, and only the last position's logits
over the published vocabulary are returned. ``quant="fp8"`` is the
control, the reference one precision step below the configuration's
bfloat16: every matmul operand (weights per output column, activations
per row) and the residual stream after each sub-layer are rounded to
float8 e4m3 with a scale that maps their largest entry to the format's
largest.

The weights are a dict in the serving program's layout: ``embed``
(padded vocab, d), ``final_norm`` (d,), and one stacked block group
under ``blocks`` with ``attn_norm``, ``wq`` (L, d, H, Dh), ``wk``/``wv``
(L, d, KH, Dh), ``wo`` (L, H, Dh, d), ``mlp_norm``, ``w_gate``/``w_up``
(L, d, F) and ``w_down`` (L, F, d).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _fp8(x, axis, quant):
    """x rounded to float8 e4m3, scaled so its largest entry along
    ``axis`` maps to the format's largest; the identity without
    ``quant``."""
    if quant != "fp8":
        return x
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant):
    """x (..., k) @ w (k, n) in float32, from fp8 operands under
    ``quant``."""
    return jnp.matmul(_fp8(x, -1, quant), _fp8(w, 0, quant),
                      precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (B, S, H, Dh): split-half rotary embedding at positions 0..S-1."""
    s, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(cfg, quant, x, p):
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    b, s, d = x.shape
    _, h, dh = p["wq"].shape
    kh = p["wk"].shape[1]
    eps = cfg["rms_norm_eps"]
    y = _rms(x, p["attn_norm"], eps)
    q = _mm(y, p["wq"].reshape(d, h * dh), quant).reshape(b, s, h, dh)
    k = _mm(y, p["wk"].reshape(d, kh * dh), quant).reshape(b, s, kh, dh)
    v = _mm(y, p["wv"].reshape(d, kh * dh), quant).reshape(b, s, kh, dh)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    q = q.reshape(b, s, kh, h // kh, dh)
    att = jnp.einsum("bskgd,btkd->bkgst", q, k,
                     precision=HIGHEST) * cfg["attention_multiplier"]
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
    o = jnp.einsum("bkgst,btkd->bskgd", att, v, precision=HIGHEST)
    o = _mm(o.reshape(b, s, h * dh), p["wo"].reshape(h * dh, d), quant)
    x = _fp8(x + cfg["residual_multiplier"] * o, -1, quant)
    y = _rms(x, p["mlp_norm"], eps)
    g = jax.nn.silu(_mm(y, p["w_gate"], quant)) * _mm(y, p["w_up"], quant)
    x = x + cfg["residual_multiplier"] * _mm(g, p["w_down"], quant)
    return _fp8(x, -1, quant), None


def _logits(weights, tokens, cfg, quant, last_only):
    (blocks,) = weights["blocks"].values()
    emb = weights["embed"][:cfg["vocab_size"]].astype(jnp.float32)
    x = _fp8(emb[tokens] * cfg["embedding_multiplier"], -1, quant)
    x, _ = jax.lax.scan(functools.partial(_layer, cfg, quant), x, blocks)
    if last_only:
        x = x[:, -1]
    x = _rms(x, weights["final_norm"].astype(jnp.float32), cfg["rms_norm_eps"])
    return _mm(x, emb.T, quant) / cfg["logits_scaling"]


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _last_logits(weights, tokens, cfg_items, quant):
    return _logits(weights, tokens, dict(cfg_items), quant, True)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _control_gap(weights, tokens, cfg_items):
    cfg = dict(cfg_items)
    ref = _logits(weights, tokens, cfg, None, False)
    tok = jnp.argmax(_logits(weights, tokens, cfg, "fp8", False), -1)
    got = jnp.take_along_axis(ref, tok[..., None], -1)[..., 0]
    return jnp.max(jnp.max(ref, -1) - got)


KEYS = ("vocab_size", "rms_norm_eps", "rope_theta", "attention_multiplier",
        "embedding_multiplier", "residual_multiplier", "logits_scaling")


def _blocks(tokens, block):
    n = len(tokens)
    pad = -n % block
    tokens = jnp.concatenate([jnp.asarray(tokens),
                              jnp.repeat(jnp.asarray(tokens[:1]), pad, 0)])
    return n, [tokens[i:i + block] for i in range(0, n + pad, block)]


def last_logits(weights, tokens, cfg: dict, quant: str | None = None,
                block: int = 16):
    """(n, vocab) float32 logits of each prompt's last position, computed
    ``block`` prompts at a time."""
    items = tuple((k, cfg[k]) for k in KEYS)
    n, parts = _blocks(tokens, block)
    return jnp.concatenate([_last_logits(weights, t, items, quant)
                            for t in parts], 0)[:n]


def control_gap(weights, tokens, cfg: dict, block: int = 8) -> float:
    """The control's reading: at every position of every prompt, the gap
    by which the token the fp8 model puts first lies below the float32
    model's best logit; the widest of them."""
    items = tuple((k, cfg[k]) for k in KEYS)
    _, parts = _blocks(tokens, block)
    return max(float(_control_gap(weights, t, items)) for t in parts)
