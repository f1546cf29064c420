"""Operations and bytes that the served work needs, from its shapes.

Counted as the algorithm needs them, not as the program happens to do
them: a prefill counts the valid rows only, causal attention only below
the diagonal, and the output head at the last position only; a lookup
counts one exhaustive scan of the cached keys at one byte per feature.
So a share of a peak computed from these counts can only understate
what the device did, and never passes 100 %.

``cfg`` is a configuration file's dict (published key names).
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``. A device that
    is not in the table is an error, never a default."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; add them with their source")
    return table[device_kind]


def _dims(cfg: dict):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return (d, cfg["intermediate_size"], cfg["num_hidden_layers"], h,
            cfg["num_key_value_heads"], d // h, cfg["vocab_size"])


def weight_count(cfg: dict) -> int:
    """Parameters a prefill reads: every layer and the tied embedding."""
    d, f, n_layers, h, kh, dh, v = _dims(cfg)
    layer = d * h * dh * 2 + 2 * d * kh * dh + 3 * d * f + 2 * d
    return n_layers * layer + v * d + d


def prefill_flops(cfg: dict, rows: int, seq: int) -> float:
    """Useful FLOPs of prefilling ``rows`` prompts of ``seq`` tokens."""
    d, f, n_layers, h, kh, dh, v = _dims(cfg)
    dense = 2 * (d * h * dh * 2 + 2 * d * kh * dh + 3 * d * f)
    attn = 2 * 2 * h * dh * seq * (seq + 1) / 2
    return rows * (n_layers * (seq * dense + attn) + 2 * d * v)


def prefill_bytes(cfg: dict, rows: int, seq: int,
                  weight_bytes: int = 2, kv_bytes: int = 2) -> float:
    """Least HBM traffic of that prefill: the weights read once and the
    keys and values written once."""
    d, f, n_layers, h, kh, dh, v = _dims(cfg)
    kv = rows * seq * n_layers * 2 * kh * dh * kv_bytes
    return weight_count(cfg) * weight_bytes + kv


def lookup_flops(queries: int, keys: int, dim: int) -> float:
    """One exhaustive scan: a multiply and an add per feature per pair."""
    return 2.0 * queries * keys * dim


def lookup_bytes(keys: int, dim: int) -> float:
    """The keys read once, at one byte per feature."""
    return float(keys) * dim


def time_floor(flops: float, nbytes: float, flops_per_s: float,
               bytes_per_s: float) -> float:
    """Least seconds: the larger of the compute and the memory bound."""
    return max(flops / flops_per_s, nbytes / bytes_per_s)
