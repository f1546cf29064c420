"""Transformer building blocks: norms, rotary embeddings (RoPE + M-RoPE),
grouped-query attention (train/prefill/decode paths), SwiGLU MLP.

All functions are pure and shape-polymorphic; sharding is applied by the
caller via logical-axis constraints (launch/sharding.py). Softmax and
normalization statistics are computed in f32 regardless of the compute
dtype (bf16 on TPU).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return ((xf * jax.lax.rsqrt(var + eps)) * scale.astype(jnp.float32)).astype(dt)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


# ------------------------------------------------------------------- RoPE
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    """(head_dim/2,) inverse frequencies."""
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array,
               theta: float = 1e4) -> jax.Array:
    """Standard RoPE. x: (B, S, H, Dh); positions: (B, S) int32."""
    dh = x.shape[-1]
    inv = rope_freqs(dh, theta)                          # (Dh/2,)
    ang = positions[..., None].astype(jnp.float32) * inv  # (B, S, Dh/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_rope_interleaved(x: jax.Array, positions: jax.Array,
                           theta: float = 1e4) -> jax.Array:
    """RoPE on DeepSeek-V3's layout: the interleaved pairs (x0, x1),
    (x2, x3), … are de-interleaved to (x0, x2, …, x1, x3, …), then
    rotated as ``apply_rope`` does; the result stays de-interleaved, as
    in the published modelling code (q and k alike, so q·k is that of
    the pairs)."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    return apply_rope(x, positions, theta)


def apply_mrope(x: jax.Array, positions: jax.Array, sections: tuple,
                theta: float = 1e4) -> jax.Array:
    """Multimodal RoPE (Qwen2-VL): ``positions`` is (3, B, S) —
    temporal / height / width ids; the Dh/2 frequency pairs are split
    into ``sections`` (e.g. (16, 24, 24) for Dh=128), each rotated by its
    own position stream."""
    dh = x.shape[-1]
    assert sum(sections) == dh // 2, (sections, dh)
    inv = rope_freqs(dh, theta)                          # (Dh/2,)
    # per-frequency position stream: section s uses positions[s]
    sec_ids = jnp.repeat(jnp.arange(3), jnp.array(sections),
                         total_repeat_length=dh // 2)    # (Dh/2,)
    pos = positions.astype(jnp.float32)                  # (3, B, S)
    pos_per_freq = pos[sec_ids]                          # (Dh/2, B, S)
    ang = jnp.moveaxis(pos_per_freq, 0, -1) * inv        # (B, S, Dh/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# -------------------------------------------------------------- attention
def gqa_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                  causal: bool = True,
                  q_offset: jax.Array | int = 0,
                  kv_len: jax.Array | None = None) -> jax.Array:
    """Grouped-query attention.

    q, k: (B, Sq|Skv, H|KH, Dh); v: (B, Skv, KH, Dv) with H % KH == 0.
    ``q_offset`` positions the query block inside the kv timeline (decode:
    q_offset = current length − Sq). ``kv_len`` masks out cache slots
    beyond the valid length (decode with a statically-shaped cache).
    Returns (B, Sq, H, Dv). Softmax in f32.
    """
    B, Sq, H, Dh = q.shape
    Skv, KH = k.shape[1], k.shape[2]
    G = H // KH
    qg = q.reshape(B, Sq, KH, G, Dh)
    scale = 1.0 / jnp.sqrt(jnp.float32(Dh))
    scores = jnp.einsum("bskgd,btkd->bkgst", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale   # (B,KH,G,Sq,Skv)
    tpos = jnp.arange(Skv)[None, :]
    neg = jnp.float32(-1e30)
    if causal:
        qpos = q_offset + jnp.arange(Sq)[:, None]
        scores = jnp.where(tpos <= qpos, scores, neg)
    if kv_len is not None:
        scores = jnp.where(tpos < kv_len, scores, neg)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, v.shape[-1]).astype(q.dtype)


# ------------------------------------------------------------------- MLP
def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           w_down: jax.Array) -> jax.Array:
    """SwiGLU: (silu(x·Wg) ⊙ (x·Wu))·Wd. Weights: (D, F), (D, F), (F, D)."""
    g = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, w_gate.astype(x.dtype)))
    u = jnp.einsum("bsd,df->bsf", x, w_up.astype(x.dtype))
    return jnp.einsum("bsf,fd->bsd", g * u, w_down.astype(x.dtype))


def gelu_mlp(x: jax.Array, w_up: jax.Array, b_up: jax.Array,
             w_down: jax.Array, b_down: jax.Array) -> jax.Array:
    """Whisper-style GELU MLP with biases."""
    h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, w_up.astype(x.dtype))
                    + b_up.astype(x.dtype))
    return jnp.einsum("bsf,fd->bsd", h, w_down.astype(x.dtype)) \
        + b_down.astype(x.dtype)
