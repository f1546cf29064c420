"""Declarative parameter schema.

Every model is described as a nested dict of :class:`ParamSpec`
(shape, logical sharding axes, initializer). The same schema drives
(1) parameter initialization, (2) pjit PartitionSpecs via
launch/sharding.py, (3) parameter counting, and (4) checkpoint layout —
one source of truth, consistent by construction.

Layer stacking: the decoder is a sequence of *super-blocks* scanned with
``lax.scan``; each super-block is an (unrolled) pattern of heterogeneous
blocks (paper-faithful jamba: [attn, mamba×7] with MoE on every other
layer). Per-block params carry a leading ``n_super`` axis (logical axis
"layers").
"""
from __future__ import annotations

import dataclasses
import math
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: tuple
    axes: tuple                      # logical axis names, len == len(shape)
    init: str = "normal"             # normal | zeros | ones | mamba_a | mamba_dt
    scale: float = 0.02

    def make(self, key: jax.Array, dtype) -> jax.Array:
        if self.init == "zeros":
            return jnp.zeros(self.shape, dtype)
        if self.init == "ones":
            return jnp.ones(self.shape, dtype)
        if self.init == "mamba_a":        # A_log = log(1..N) per channel
            n = self.shape[-1]
            a = jnp.tile(jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)),
                         self.shape[:-1] + (1,))
            return a.astype(dtype)
        if self.init == "mamba_dt":       # dt bias ~ softplus^-1(0.001..0.1)
            lo, hi = 1e-3, 1e-1
            u = jax.random.uniform(key, self.shape, jnp.float32)
            dt = jnp.exp(u * (math.log(hi) - math.log(lo)) + math.log(lo))
            return jnp.log(jnp.expm1(dt)).astype(dtype)
        return (jax.random.normal(key, self.shape, jnp.float32)
                * self.scale).astype(dtype)


# ------------------------------------------------------------ block kinds
def mla_specs(cfg: ArchConfig) -> dict:
    """Multi-head latent attention without a query latent: per-head
    queries of head_dim + qk_rope_dim, one projection to the latent and
    the shared rotary key, the latent's norm, its expansions to per-head
    keys and values, and the output."""
    d, h, dh = cfg.d_model, cfg.n_heads, cfg.head_dim
    r, dr = cfg.kv_lora_rank, cfg.qk_rope_dim
    return {
        "attn_norm": ParamSpec((d,), ("embed",), "ones"),
        "wq": ParamSpec((d, h, dh + dr), ("embed", "heads", "head_dim")),
        "w_kv_a": ParamSpec((d, r + dr), ("embed", None)),
        "kv_norm": ParamSpec((r,), (None,), "ones"),
        "wk_b": ParamSpec((r, h, dh), (None, "heads", "head_dim")),
        "wv_b": ParamSpec((r, h, dh), (None, "heads", "head_dim")),
        "wo": ParamSpec((h, dh, d), ("heads", "head_dim", "embed")),
    }


def attn_specs(cfg: ArchConfig, cross: bool = False) -> dict:
    if cfg.kv_lora_rank and not cross:
        return mla_specs(cfg)
    d, h, kh, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    pfx = "x" if cross else ""
    out = {
        f"{pfx}attn_norm": ParamSpec((d,), ("embed",), "ones"),
        f"{pfx}wq": ParamSpec((d, h, dh), ("embed", "heads", "head_dim")),
        f"{pfx}wk": ParamSpec((d, kh, dh), ("embed", "kv_heads", "head_dim")),
        f"{pfx}wv": ParamSpec((d, kh, dh), ("embed", "kv_heads", "head_dim")),
        f"{pfx}wo": ParamSpec((h, dh, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias and not cross:
        out[f"{pfx}bq"] = ParamSpec((h, dh), ("heads", "head_dim"), "zeros")
        out[f"{pfx}bk"] = ParamSpec((kh, dh), ("kv_heads", "head_dim"), "zeros")
        out[f"{pfx}bv"] = ParamSpec((kh, dh), ("kv_heads", "head_dim"), "zeros")
    return out


def mlp_specs(cfg: ArchConfig, ff: int) -> dict:
    d = cfg.d_model
    return {
        "mlp_norm": ParamSpec((d,), ("embed",), "ones"),
        "w_gate": ParamSpec((d, ff), ("embed", "ff")),
        "w_up": ParamSpec((d, ff), ("embed", "ff")),
        "w_down": ParamSpec((ff, d), ("ff", "embed")),
    }


def gelu_mlp_specs(cfg: ArchConfig, ff: int) -> dict:
    d = cfg.d_model
    return {
        "mlp_norm": ParamSpec((d,), ("embed",), "ones"),
        "mlp_norm_b": ParamSpec((d,), ("embed",), "zeros"),
        "w_up": ParamSpec((d, ff), ("embed", "ff")),
        "b_up": ParamSpec((ff,), ("ff",), "zeros"),
        "w_down": ParamSpec((ff, d), ("ff", "embed")),
        "b_down": ParamSpec((d,), ("embed",), "zeros"),
    }


def moe_specs(cfg: ArchConfig) -> dict:
    """The router over all ``moe_experts``, the weights of the experts
    this chip holds, and the shared experts where the model has them."""
    d, f, e = cfg.d_model, cfg.expert_ff, cfg.moe_experts
    n = cfg.held[1]
    out = {
        "moe_norm": ParamSpec((d,), ("embed",), "ones"),
        "router": ParamSpec((d, e), ("embed", None)),
        "we_gate": ParamSpec((n, d, f), ("experts", "embed", "ff")),
        "we_up": ParamSpec((n, d, f), ("experts", "embed", "ff")),
        "we_down": ParamSpec((n, f, d), ("experts", "ff", "embed")),
    }
    if cfg.moe_router == "sigmoid":
        out["router_bias"] = ParamSpec((e,), (None,), "zeros")
    if cfg.shared_ff:
        s = cfg.shared_ff
        out["ws_gate"] = ParamSpec((d, s), ("embed", "ff"))
        out["ws_up"] = ParamSpec((d, s), ("embed", "ff"))
        out["ws_down"] = ParamSpec((s, d), ("ff", "embed"))
    return out


def mamba_specs(cfg: ArchConfig) -> dict:
    d, di = cfg.d_model, cfg.d_inner
    n, dtr, cw = cfg.ssm_state, cfg.ssm_dt_rank, cfg.ssm_conv
    return {
        "m_norm": ParamSpec((d,), ("embed",), "ones"),
        "in_proj": ParamSpec((d, 2 * di), ("embed", "ff")),
        "conv_w": ParamSpec((cw, di), (None, "ff")),
        "conv_b": ParamSpec((di,), ("ff",), "zeros"),
        "x_proj": ParamSpec((di, dtr + 2 * n), ("ff", None)),
        "dt_w": ParamSpec((dtr, di), (None, "ff")),
        "dt_b": ParamSpec((di,), ("ff",), "mamba_dt"),
        "A_log": ParamSpec((di, n), ("ff", None), "mamba_a"),
        "Dskip": ParamSpec((di,), ("ff",), "ones"),
        "out_proj": ParamSpec((di, d), ("ff", "embed")),
    }


def mlstm_specs(cfg: ArchConfig) -> dict:
    """mLSTM block operating in a ``ssm_expand``×-projected space
    (the xLSTM paper's projection factor; di = expand·d)."""
    d, nh = cfg.d_model, cfg.n_heads
    di = cfg.ssm_expand * d
    dh = di // nh
    return {
        "m_norm": ParamSpec((d,), ("embed",), "ones"),
        "wq": ParamSpec((d, nh, dh), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((d, nh, dh), ("embed", "heads", "head_dim")),
        "wv": ParamSpec((d, nh, dh), ("embed", "heads", "head_dim")),
        "w_if": ParamSpec((d, 2, nh), ("embed", None, "heads")),
        "w_og": ParamSpec((d, di), ("embed", "ff")),
        "w_out": ParamSpec((di, d), ("ff", "embed")),
    }


def slstm_specs(cfg: ArchConfig) -> dict:
    d, nh = cfg.d_model, cfg.n_heads
    dh = d // nh
    return {
        "s_norm": ParamSpec((d,), ("embed",), "ones"),
        "w_izfo": ParamSpec((d, 4, nh, dh), ("embed", None, "heads", "head_dim")),
        "r_izfo": ParamSpec((4, nh, dh, dh), (None, "heads", "head_dim", None),
                            scale=0.01),
        "b_izfo": ParamSpec((4, nh, dh), (None, "heads", "head_dim"), "zeros"),
        "w_sout": ParamSpec((d, d), ("ff", "embed")),
    }


# ----------------------------------------------------------- block layout
def block_pattern(cfg: ArchConfig) -> list[str]:
    """The per-super-block sequence of block kinds; homogeneous across
    super-blocks so lax.scan applies. Kinds:
      attn+mlp | attn+moe | mamba+mlp | mamba+moe | mlstm | slstm
    """
    if cfg.xlstm:
        pat = []
        for i in range(cfg.slstm_every):
            pat.append("slstm" if (i + 1) % cfg.slstm_every == 0 else "mlstm")
        assert cfg.n_layers % len(pat) == 0
        return pat
    period = max(cfg.attn_every, 1) if cfg.attn_every else 1
    period = np.lcm(period, cfg.moe_every if cfg.moe_experts else 1)
    pat = []
    for i in range(period):
        mixer = "attn" if cfg.is_attn_layer(i) else "mamba"
        ffn = "moe" if cfg.is_moe_layer(i) else "mlp"
        pat.append(f"{mixer}+{ffn}")
    assert cfg.pattern_layers % period == 0, (cfg.name, cfg.n_layers, period)
    return pat


LEAD = "attn+mlp"              # the kind of every leading dense layer


def block_key(bi: int, kind: str, lead: bool = False) -> str:
    return f"{'l' if lead else 'b'}{bi}_{kind.replace('+', '_')}"


def block_specs(cfg: ArchConfig, kind: str) -> dict:
    if kind == "mlstm":
        return mlstm_specs(cfg)
    if kind == "slstm":
        return slstm_specs(cfg)
    mixer, ffn = kind.split("+")
    out = {}
    out.update(attn_specs(cfg) if mixer == "attn" else mamba_specs(cfg))
    if ffn == "moe":
        out.update(moe_specs(cfg))
    else:
        ff = cfg.dense_ff if cfg.dense_ff else cfg.d_ff
        out.update(mlp_specs(cfg, ff))
    return out


def lead_specs(cfg: ArchConfig) -> dict:
    """The leading dense layers: attention and a d_ff SwiGLU."""
    out = dict(attn_specs(cfg))
    out.update(mlp_specs(cfg, cfg.d_ff))
    return out


def _stack(specs: dict, n: int) -> dict:
    """Add the scanned leading 'layers' axis to every spec in the block."""
    return {k: ParamSpec((n,) + s.shape, ("layers",) + s.axes, s.init, s.scale)
            for k, s in specs.items()}


def param_schema(cfg: ArchConfig) -> dict:
    """Full model schema: nested dict name → ParamSpec."""
    d, vp = cfg.d_model, cfg.padded_vocab
    schema: dict = {
        "embed": ParamSpec((vp, d), ("vocab", "embed"), scale=0.02),
        "final_norm": ParamSpec((d,), ("embed",), "ones"),
    }
    if not cfg.tie_embeddings:
        schema["lm_head"] = ParamSpec((d, vp), ("embed", "vocab"))

    pattern = block_pattern(cfg)
    n_super = cfg.pattern_layers // len(pattern)
    blocks = {}
    for bi, kind in enumerate(pattern):
        blocks[block_key(bi, kind)] = _stack(block_specs(cfg, kind), n_super)
    schema["blocks"] = blocks
    if cfg.n_dense_layers:
        schema["lead_blocks"] = {block_key(0, LEAD, lead=True): _stack(
            lead_specs(cfg), cfg.n_dense_layers)}

    if cfg.is_encdec:
        enc_blocks = {}
        enc_specs = {}
        enc_specs.update(attn_specs(cfg))
        enc_specs.update(gelu_mlp_specs(cfg, cfg.d_ff))
        enc_blocks["enc"] = _stack(enc_specs, cfg.n_enc_layers)
        # decoder cross-attention, one per decoder layer
        cross = _stack(attn_specs(cfg, cross=True), n_super)
        for bi, kind in enumerate(pattern):
            blocks[block_key(bi, kind)].update(cross)
        schema["enc_blocks"] = enc_blocks
        schema["enc_final_norm"] = ParamSpec((d,), ("embed",), "ones")
    if cfg.frontend == "vision_stub":
        schema["vision_proj"] = ParamSpec((1280, d), (None, "embed"))
    if cfg.frontend == "audio_stub":
        schema["audio_proj"] = ParamSpec((128, d), (None, "embed"))
    return schema


# -------------------------------------------------------------- utilities
def init_params(cfg: ArchConfig, key: jax.Array) -> dict:
    dtype = jnp.dtype(cfg.param_dtype)
    flat = {}

    def walk(tree, prefix):
        for k, v in tree.items():
            if isinstance(v, dict):
                walk(v, prefix + (k,))
            else:
                flat[prefix + (k,)] = v

    schema = param_schema(cfg)
    walk(schema, ())
    keys = jax.random.split(key, len(flat))
    out: dict = {}
    for (path, spec), sk in zip(sorted(flat.items()), keys):
        node = out
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = spec.make(sk, dtype)
    return out


def param_count(cfg: ArchConfig, padded: bool = False) -> int:
    """Total parameter count from the schema (vocab padding excluded by
    default so the number matches the published size)."""
    total = 0
    vp, v = cfg.padded_vocab, cfg.vocab

    def walk(tree):
        nonlocal total
        for key, s in tree.items():
            if isinstance(s, dict):
                walk(s)
                continue
            n = int(np.prod(s.shape))
            if not padded and key in ("embed", "lm_head"):
                n = n // vp * v
            total += n

    walk(param_schema(cfg))
    return total


def active_param_count(cfg: ArchConfig) -> int:
    """Parameters touched per token (MoE: only top-k experts active)."""
    total = param_count(cfg)
    if cfg.moe_experts:
        per_expert = 3 * cfg.d_model * cfg.expert_ff
        n_moe = sum(1 for i in range(cfg.pattern_layers)
                    if cfg.is_moe_layer(i))
        total -= n_moe * (cfg.held[1] - cfg.moe_topk) * per_expert
    return total
