"""Plain reference of a DeepSeek-V3 decoder as Moonlight-16B-A3B publishes
it, in float32 jax.numpy.

It follows the published architecture (hf:moonshotai/Moonlight-16B-A3B,
``model_type: deepseek_v3``, the modelling code of DeepSeek-V3): token
embedding; per layer RMSNorm, multi-head latent attention, RMSNorm, then
a SwiGLU MLP on the first ``first_k_dense_replace`` layers and a mixture
of experts on the rest; a final RMSNorm and an untied output head.

- Attention, with no query latent: per head a query of
  ``qk_nope_head_dim`` + ``qk_rope_head_dim``; from the normed input one
  projection to a ``kv_lora_rank`` latent and one rotary key of
  ``qk_rope_head_dim`` that every head shares; the latent is RMS-normed
  and expanded to each head's rope-free key and its value of
  ``v_head_dim``. The rotary parts take DeepSeek-V3's layout: the
  interleaved pairs are de-interleaved, then rotated by half
  (``rotate_half``) at base ``rope_theta``. Scores are scaled by
  (``qk_nope_head_dim`` + ``qk_rope_head_dim``)^-0.5 under a causal
  mask.
- Experts: router logits from the normed input, sigmoid scores; the
  top ``num_experts_per_tok`` of score + ``e_score_correction_bias``
  (the weights' ``router_bias``) are chosen, their scores (not the
  biased ones) normalised to sum to one and scaled by
  ``routed_scaling_factor``. ``n_group`` = ``topk_group`` = 1, so the
  group limit selects every expert. The shared experts are one SwiGLU
  on the same normed input, added whatever the routing.

The expert layer holds the configuration's share: the weights carry the
routed experts ``program.arch.experts_held`` = [first, count) of the
router's (the one place the configuration states the share); the
router ranks all of its outputs, and a choice of an expert not held
adds nothing, as on the chip that holds this share. Each held expert is
run over every token and weighted by the gate its choice gave (zero
where it was not chosen): dense, with no dispatch.

No kernels, caches or batching tricks: each prompt runs whole, every
matmul at ``Precision.HIGHEST``, weights cast to float32 one layer at a
time inside the layer scan, and only the last position's logits are
returned. ``quant="fp8"`` is the control, the reference one precision
step below the configuration's bfloat16: every matmul operand (weights
per output column, activations per row) and the residual stream after
each sub-layer are rounded to float8 e4m3 with a scale that maps their
largest entry to the format's largest.

The weights are a dict in the serving program's layout: ``embed``
(vocab, d), ``lm_head`` (d, vocab), ``final_norm`` (d,); one stacked
group under ``lead_blocks`` for the dense layers and one under
``blocks`` for the expert layers. Both hold ``attn_norm``, ``wq`` (L, d,
H, dn + dr), ``w_kv_a`` (L, d, r + dr), ``kv_norm`` (L, r), ``wk_b`` (L,
r, H, dn), ``wv_b`` (L, r, H, dv) and ``wo`` (L, H, dv, d); the dense
group ``mlp_norm``, ``w_gate``/``w_up`` (L, d, F), ``w_down`` (L, F, d);
the expert group ``moe_norm``, ``router`` (L, d, E), ``router_bias``
(L, E), ``we_gate``/``we_up`` (L, n, d, f), ``we_down`` (L, n, f, d) and
the shared ``ws_gate``/``ws_up`` (L, d, s), ``ws_down`` (L, s, d).
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
    """x (B, S, H, D) at positions 0..S-1, as DeepSeek-V3 applies it:
    the interleaved pairs de-interleaved, then ``rotate_half``."""
    b, s, h, d = x.shape
    x = x.reshape(b, s, h, d // 2, 2).swapaxes(-1, -2).reshape(b, s, h, d)
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    emb = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(emb) + half * jnp.sin(emb)


def _attention(cfg, quant, y, p):
    b, s, d = y.shape
    _, h, dq = p["wq"].shape
    r = p["wk_b"].shape[0]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    q = _mm(y, p["wq"].reshape(d, h * dq), quant).reshape(b, s, h, dq)
    q_nope, q_pe = q[..., :dn], _rope(q[..., dn:], cfg["rope_theta"])
    kv = _mm(y, p["w_kv_a"], quant)
    latent = _rms(kv[..., :r], p["kv_norm"], cfg["rms_norm_eps"])
    k_pe = _rope(kv[..., None, r:], cfg["rope_theta"])       # (b, s, 1, dr)
    k_nope = _mm(latent, p["wk_b"].reshape(r, h * dn),
                 quant).reshape(b, s, h, dn)
    v = _mm(latent, p["wv_b"].reshape(r, h * dv), quant).reshape(b, s, h, dv)
    att = (jnp.einsum("bshd,bthd->bhst", q_nope, k_nope, precision=HIGHEST)
           + jnp.einsum("bshd,btd->bhst", q_pe, k_pe[:, :, 0],
                        precision=HIGHEST)) * (dn + dr) ** -0.5
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
    o = jnp.einsum("bhst,bthd->bshd", att, v, precision=HIGHEST)
    return _mm(o.reshape(b, s, h * dv), p["wo"].reshape(h * dv, d), quant)


def _swiglu(y, w_gate, w_up, w_down, quant):
    return _mm(jax.nn.silu(_mm(y, w_gate, quant)) * _mm(y, w_up, quant),
               w_down, quant)


def gates(y, router, bias, cfg, quant=None):
    """(T·, E) weight of each expert for each token: the routing
    equations of DeepSeek-V3 with one expert group; zero where an expert
    was not chosen."""
    scores = jax.nn.sigmoid(_mm(y, router, quant))
    _, idx = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    chosen = jnp.take_along_axis(scores, idx, -1)
    if cfg["norm_topk_prob"]:
        chosen = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20)
    chosen = chosen * cfg["routed_scaling_factor"]
    onehot = jax.nn.one_hot(idx, scores.shape[-1], dtype=jnp.float32)
    return jnp.einsum("...k,...ke->...e", chosen, onehot)


def _experts(cfg, quant, y, p):
    """The held routed experts' weighted outputs plus the shared
    experts'."""
    first, n = cfg["experts_held"]
    g = gates(y, p["router"], p["router_bias"], cfg, quant)
    g = g[..., first:first + n]                              # (b, s, n)
    yq = _fp8(y, -1, quant)
    hg = jnp.einsum("bsd,ndf->bsnf", yq, _fp8(p["we_gate"], 1, quant),
                    precision=HIGHEST)
    hu = jnp.einsum("bsd,ndf->bsnf", yq, _fp8(p["we_up"], 1, quant),
                    precision=HIGHEST)
    hx = _fp8(jax.nn.silu(hg) * hu, -1, quant)
    o = jnp.einsum("bsnf,nfd->bsnd", hx, _fp8(p["we_down"], 1, quant),
                   precision=HIGHEST)
    routed = jnp.einsum("bsn,bsnd->bsd", g, o, precision=HIGHEST)
    return routed + _swiglu(y, p["ws_gate"], p["ws_up"], p["ws_down"],
                            quant)


def _layer(cfg, quant, x, p):
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    eps = cfg["rms_norm_eps"]
    x = _fp8(x + _attention(cfg, quant, _rms(x, p["attn_norm"], eps), p),
             -1, quant)
    if "router" in p:
        y = _experts(cfg, quant, _rms(x, p["moe_norm"], eps), p)
    else:
        y = _swiglu(_rms(x, p["mlp_norm"], eps), p["w_gate"], p["w_up"],
                    p["w_down"], quant)
    return _fp8(x + y, -1, quant), None


def _logits(weights, tokens, cfg, quant, last_only):
    v = cfg["vocab_size"]
    x = _fp8(weights["embed"][:v].astype(jnp.float32)[tokens], -1, quant)
    layer = functools.partial(_layer, cfg, quant)
    for group in ("lead_blocks", "blocks"):
        (stacked,) = weights[group].values()
        x, _ = jax.lax.scan(layer, x, stacked)
    if last_only:
        x = x[:, -1]
    x = _rms(x, weights["final_norm"].astype(jnp.float32), cfg["rms_norm_eps"])
    return _mm(x, weights["lm_head"][:, :v].astype(jnp.float32), quant)


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


KEYS = ("vocab_size", "rms_norm_eps", "rope_theta", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "num_experts_per_tok",
        "norm_topk_prob", "routed_scaling_factor")


def _items(cfg: dict) -> tuple:
    held = tuple(cfg["program"]["arch"]["experts_held"])
    return tuple((k, cfg[k]) for k in KEYS) + (("experts_held", held),)


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
    items = _items(cfg)
    n, parts = _blocks(tokens, block)
    return jnp.concatenate([_last_logits(weights, t, items, quant)
                            for t in parts], 0)[:n]


def control_gap(weights, tokens, cfg: dict, block: int = 1) -> float:
    """The control's reading: at every position of every prompt, the gap
    by which the token the fp8 model puts first lies below the float32
    model's best logit; the widest of them. One prompt at a time: the
    logits of every position over the vocabulary are 0.7 GB a prompt of
    1,024 tokens."""
    items = _items(cfg)
    _, parts = _blocks(tokens, block)
    return max(float(_control_gap(weights, t, items)) for t in parts)
