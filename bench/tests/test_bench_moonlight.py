"""The Moonlight cell's arithmetic and readers: the FLOP and byte counts
of ``flops_moonlight.py`` against the program's own parameter layout,
and the six per-layer metrics on hand-built contexts, on a program that
keeps no MoE counters (they read nothing), and on counts that make a
roofline share read exactly its floor over the device time."""
from __future__ import annotations

import collections
import json
from types import SimpleNamespace

import numpy as np
import pytest

from bench_fixtures import BENCH
import flops  # noqa: E402
import flops_moonlight as fm  # noqa: E402
import harness  # noqa: E402
import tracing  # noqa: E402

CFG = json.loads((BENCH / "configs/moonlight-16b-a3b.resp-1m.json")
                 .read_text())
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
METRICS = ("serve_mfu.moonlight", "prefill_roofline.moonlight",
           "experts_roofline", "experts_held_share", "experts_load_skew")


def test_weights_counted_are_the_programs():
    """The bytes floor reads the parameters the program holds: its
    schema's count at the configuration's share of the experts."""
    from repro.configs.base import ArchConfig
    from repro.models import schema
    arch = ArchConfig(**CFG["program"]["arch"])
    assert fm.weight_count(CFG) == schema.param_count(arch)
    assert fm.expert_weights(CFG) * fm.moe_layers(CFG) == sum(
        int(np.prod(s.shape)) for k, s in
        schema.param_schema(arch)["blocks"]["b0_attn_moe"].items()
        if k.startswith("we_"))


def test_prefill_flops_split_into_dense_head_and_held_routed_work():
    seq, rows = 1024, 3
    dense = rows * (seq * fm.token_flops(CFG, seq) + 2 * 2048 * 163840)
    assert fm.prefill_flops(CFG, rows, seq, 0.0) == pytest.approx(dense)
    routed = fm.prefill_flops(CFG, rows, seq, 0.125) - dense
    assert routed == pytest.approx(rows * seq * 6 * 26 * 0.125 * 6 * 2048
                                   * 1408)
    # ~2.27 GFLOP a token at 1,024 tokens with an eighth of the rows held
    per_token = fm.prefill_flops(CFG, 1, seq, 0.125) / seq
    assert 2.2e9 < per_token < 2.35e9


def _ctx(counts, trace=None, traced=()):
    return SimpleNamespace(cfg=CFG, mix={"prompt_len": 1024}, chips=1,
                           peaks=PEAKS, modules={"prefill": "jit_prefill"},
                           keys=86016, dim=128, trace=trace,
                           traced=list(traced), window_compiles=0,
                           flops=flops)


def _events(rows):
    names, s, e = zip(*rows) if rows else ((), (), ())
    return tracing.Events(np.array(names, dtype=object),
                          np.array(s, float), np.array(e, float))


def test_readers_read_nothing_without_the_counters(monkeypatch):
    from repro import tracecount
    monkeypatch.setattr(tracecount, "COUNTS", collections.Counter())
    trace = tracing.Trace(ops=_events([]), modules=_events([]),
                          host=_events([("bench.traced", 0.0, 1.0)]),
                          span=(0.0, 1.0))
    ctx = _ctx({}, trace, [{"n": 32, "misses": 20}])
    assert all(harness.reader(m)(ctx) is None for m in METRICS)
    assert harness.reader("window_compiles.moonlight")(ctx) == 0.0


def test_readers_on_known_counts_and_device_times(monkeypatch):
    from repro import tracecount
    monkeypatch.setattr(tracecount, "COUNTS", collections.Counter({
        "moe.assignments": 8000, "moe.rows_held": 1000,
        "moe.rows_busiest": 150}))
    batches = [{"n": 32, "misses": 20}, {"n": 32, "misses": 0}]
    # 20 misses run as pieces of 16 and 8 rows
    held_rows = 24 * 1024 * 6 * 26 * 0.125
    expert_floor = max(held_rows * 6 * 2048 * 1408 / 197e12,
                       2 * 26 * 8 * 3 * 2048 * 1408 * 2 / 819e9)
    prefill_floor = fm.prefill_flops(CFG, 20, 1024, 0.125) / 197e12
    ops = [("%ragged-dot-none.1 = bf16[...] custom-call()", 0.1,
            0.1 + expert_floor / 2),
           ("%ragged-dot-none.2 = bf16[...] custom-call()", 0.3,
            0.3 + expert_floor / 2),
           ("%ragged-dot-metadata = s32[...]", 0.5, 0.6),
           ("%fusion.1 = bf16[...] fusion()", 0.6, 0.7)]
    trace = tracing.Trace(
        ops=_events(ops), modules=_events([("jit_prefill(7)", 0.0,
                                            2 * prefill_floor)]),
        host=_events([("bench.traced", 0.0, 1.0)]), span=(0.0, 1.0))
    ctx = _ctx({}, trace, batches)
    read = {m: harness.reader(m)(ctx) for m in METRICS}
    assert read["experts_held_share"] == pytest.approx(12.5)
    # the busiest of 8 held experts computed 150 rows, the mean 125
    assert read["experts_load_skew"] == pytest.approx(20.0)
    assert read["experts_roofline"] == pytest.approx(100.0)
    assert read["prefill_roofline.moonlight"] == pytest.approx(50.0)
    lookup = 64 * flops.lookup_flops(1, 86016, 128)
    assert read["serve_mfu.moonlight"] == pytest.approx(
        100.0 * (prefill_floor * 197e12 + lookup) / 197e12)
