"""Shared by the benchmark's own tests: the paths (imported first, it
puts ``bench/`` and ``src/`` on ``sys.path``), and the ``tiny_root``
fixture: a temporary checkout laid out as the real one, with the
benchmark and two tiny cells (a two-layer model, a 4,000-object
catalog) that run on the CPU.

Not named ``conftest.py``: the repository's own ``tests/conftest.py``
is imported by that name."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest


BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
for p in (str(BENCH), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_CONFIG = {
    "source": "https://huggingface.co/ibm-granite/granite-3.0-2b-base",
    "model_type": "granite", "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "vocab_size": 250, "rms_norm_eps": 1e-05,
    "rope_theta": 10000.0, "tie_word_embeddings": True,
    "attention_multiplier": 0.25, "embedding_multiplier": 1.0,
    "residual_multiplier": 1.0, "logits_scaling": 1.0,
    "reference": "granite",
    "program": {"arch": {
        "name": "tiny", "family": "dense", "n_layers": 2, "d_model": 64,
        "n_heads": 4, "n_kv_heads": 2, "d_ff": 128, "vocab": 250,
        "tie_embeddings": True, "rope_theta": 10000.0, "norm_eps": 1e-05,
        "param_dtype": "bfloat16", "compute_dtype": "bfloat16"}},
    "cache": {"catalog_objects": 4000, "embedding_dim": 128,
              "levels": [32, 64, 128], "h": [0.0, 0.47, 4.7],
              "h_model": 47.0, "gamma": 1.0, "metric": "l2"},
}
TINY_MIXES = {
    "tiny-sat": {"arrival": "saturate", "zipf_alpha": 0.8, "prompt_len": 8,
                 "batch": 16, "trace_seconds": 0.5, "check_batches": 4},
    "tiny-poisson": {"arrival": "poisson", "rate": 300.0, "drain_s": 5.0,
                     "zipf_alpha": 1.2, "prompt_len": 8, "batch": 16,
                     "trace_seconds": 0.5, "check_batches": 4},
}
# readings of the tiny cells on the CPU (the program computes in bf16
# against the float32 reference) set these; see test_bench_run.py
TINY_LIMITS = {"lookup_mismatch": 0, "cost_err": 2.0, "logit_gap": 0.02,
               "logit_err": 0.015,
               "requests_diff": 0, "hits_diff": 0, "cost_sum_rel": 1e-6,
               "unanswered": 0, "allocation_errors": 0}


def make_root(tmp: Path) -> Path:
    """A checkout holding the real benchmark plus two tiny cells."""
    root = tmp / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (root / "src").symlink_to(REPO / "src")
    bm = json.loads((REPO / "BENCHMARK.json").read_text())
    (root / "bench/configs/tiny.json").write_text(json.dumps(TINY_CONFIG))
    bm["configs"].append({"name": "tiny", "source": TINY_CONFIG["source"],
                          "file": "bench/configs/tiny.json", "reduced": [],
                          "why": "test size"})
    for mix, body in TINY_MIXES.items():
        (root / f"bench/traffic/{mix}.json").write_text(json.dumps(body))
        cell = f"tiny.{mix}"
        (root / f"bench/limits/{cell}.json").write_text(
            json.dumps(TINY_LIMITS))
        bm["workloads"].append({"name": cell, "config": "tiny",
                                "traffic": mix, "chips": 1, "why": "test"})
    for m in bm["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("tiny.tiny-poisson")
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return root


@pytest.fixture
def tiny_root(tmp_path) -> Path:
    return make_root(tmp_path)
