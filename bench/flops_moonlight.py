"""Operations and bytes of a DeepSeek-V3 prefill (Moonlight-16B-A3B's
block) on the chip that holds a share of its routed experts, from its
shapes.

Counted as the algorithm needs them, as ``flops.py`` counts the dense
model: the valid rows only, causal attention only below the diagonal,
the output head at the last position only, and the routed experts at
the rows the held experts compute, which the program counts
(``moe.rows_held`` of ``moe.assignments``, the held share). So a share
of a peak computed from these counts can only understate what the
device did, and never passes 100 %.

``cfg`` is a configuration file's dict (published key names, with
``n_routed_experts`` the experts held here and ``published`` the
router's full count).
"""
from __future__ import annotations


def _dims(cfg: dict) -> dict:
    return {
        "d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
        "dn": cfg["qk_nope_head_dim"], "dr": cfg["qk_rope_head_dim"],
        "dv": cfg["v_head_dim"], "r": cfg["kv_lora_rank"],
        "ff": cfg["intermediate_size"], "f": cfg["moe_intermediate_size"],
        "shared": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        "experts": cfg["published"]["n_routed_experts"],
        "held": cfg["n_routed_experts"], "k": cfg["num_experts_per_tok"],
        "dense": cfg["first_k_dense_replace"],
        "moe": cfg["num_hidden_layers"] - cfg["first_k_dense_replace"],
        "vocab": cfg["vocab_size"]}


def moe_layers(cfg: dict) -> int:
    return _dims(cfg)["moe"]


def _attn_weights(m: dict) -> int:
    d, h, dn, dr, dv, r = (m[k] for k in ("d", "h", "dn", "dr", "dv", "r"))
    return (d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv)
            + h * dv * d)


def expert_weights(cfg: dict) -> int:
    """Parameters of the held routed experts of one MoE layer."""
    m = _dims(cfg)
    return m["held"] * 3 * m["d"] * m["f"]


def weight_count(cfg: dict) -> int:
    """Parameters this chip holds and a prefill reads: every layer's
    attention, the dense layers' MLP, the MoE layers' router, shared
    experts and held experts, the embedding rows and the head."""
    m = _dims(cfg)
    d = m["d"]
    attn = _attn_weights(m) + 2 * d + m["r"]
    dense = attn + 3 * d * m["ff"]
    moe = attn + d * m["experts"] + m["experts"] + 3 * d * m["shared"] \
        + expert_weights(cfg)
    return m["dense"] * dense + m["moe"] * moe + 2 * m["vocab"] * d + d


def token_flops(cfg: dict, seq: int) -> float:
    """Useful FLOPs of one token of a ``seq``-token prompt outside the
    routed experts and the head: the projections, causal attention (a
    token's mean (seq + 1) / 2 keys), the dense MLP, the routers and the
    shared experts."""
    m = _dims(cfg)
    d, h = m["d"], m["h"]
    attn = 2 * _attn_weights(m) \
        + 2 * h * (m["dn"] + m["dr"] + m["dv"]) * (seq + 1) / 2
    return (m["dense"] * (attn + 6 * d * m["ff"])
            + m["moe"] * (attn + 2 * d * m["experts"] + 6 * d * m["shared"]))


def routed_row_flops(cfg: dict) -> float:
    """FLOPs of one token-expert row: the expert's three matmuls."""
    m = _dims(cfg)
    return 6.0 * m["d"] * m["f"]


def assignments(cfg: dict, rows: int, seq: int) -> int:
    """Token-expert assignments the router makes for ``rows`` prompts."""
    m = _dims(cfg)
    return rows * seq * m["k"] * m["moe"]


def prefill_flops(cfg: dict, rows: int, seq: int, held_share: float
                  ) -> float:
    """Useful FLOPs of prefilling ``rows`` prompts of ``seq`` tokens,
    with ``held_share`` of the assignments landing on held experts."""
    m = _dims(cfg)
    routed = assignments(cfg, rows, seq) * held_share * routed_row_flops(cfg)
    return rows * (seq * token_flops(cfg, seq) + 2 * m["d"] * m["vocab"]) \
        + routed


def prefill_bytes(cfg: dict, weight_bytes: int = 2) -> float:
    """Least HBM traffic of a prefill: the weights read once (it keeps
    no cache)."""
    return float(weight_count(cfg) * weight_bytes)


def expert_bytes(cfg: dict, pieces: int, weight_bytes: int = 2) -> float:
    """The held experts' weights, read once a layer by each prefill."""
    return float(pieces * moe_layers(cfg) * expert_weights(cfg)
                 * weight_bytes)
