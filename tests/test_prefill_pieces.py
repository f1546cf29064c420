"""The miss prefill's piece plan: a miss sub-batch runs as power-of-two
prefills no smaller than the weight-streaming floor, which may lie below
the smallest bucket, never more rows than its single bucket, with the
same tokens as that bucket and no compile once every bucket has run."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracecount
from repro.configs.registry import get_smoke_config
from repro.core import catalog as catalog_api
from repro.models import model as model_api
from repro.serve import (EngineConfig, SimCacheEngine, bucket_size,
                         prefill_pieces)
from repro.serve.engine import PIECE_TOKENS, SMALL_PIECE_TOKENS, piece_floor

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
SEQ = 32            # 8-row piece floor: ~40 misses split as 32 + 8
LONG = 1024         # 1-row piece floor: 7 misses split as 4 + 2 + 1


def _tiny_engine(bucket=True):
    cfg = dataclasses.replace(get_smoke_config("granite-3-2b"),
                              n_layers=2, d_model=64, n_heads=4,
                              n_kv_heads=2, head_dim=16, d_ff=128,
                              vocab=256)
    cat = catalog_api.embedding_catalog(n=300, dim=16, seed=1)
    ecfg = EngineConfig(k_device=8, k_pod=12, k_global=16, h_ici=1.0,
                        h_dcn=10.0, h_model=100.0, metric="l2",
                        algo="greedy", bucket=bucket)
    eng = SimCacheEngine(cfg, model_api.init_params(cfg, 0), ecfg,
                         cat.coords)
    return eng, cfg, cat


def _prompts(cfg, n, seed, seq=SEQ):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab, (n, seq)).astype(np.int32)


def _is_pow2(x):
    return x > 0 and x & (x - 1) == 0


@pytest.mark.parametrize("lo", [8, 16])
@pytest.mark.parametrize("seq", [16, 128, 1024])
@pytest.mark.parametrize("n", [1, 8, 9, 37, 43, 44, 50, 57, 64, 700])
def test_prefill_pieces_plan(n, seq, lo):
    pieces = prefill_pieces(n, seq, lo)
    b, f = bucket_size(n, lo), piece_floor(seq, lo)
    # the floor: the smallest power of two holding PIECE_TOKENS tokens,
    # and SMALL_PIECE_TOKENS where it has fewer rows than the bucket
    def holds(rows):
        return rows * seq >= (PIECE_TOKENS if rows >= lo
                              else SMALL_PIECE_TOKENS)
    assert _is_pow2(f) and holds(f)
    assert f == 1 or not holds(f // 2)
    assert all(_is_pow2(p) for p in pieces)
    assert all(a > c for a, c in zip(pieces, pieces[1:]))   # descending
    assert n <= sum(pieces) <= b
    if pieces != [b]:
        assert pieces[0] <= b // 2       # the first piece is the largest
        assert all(p >= f for p in pieces)
        assert sum(pieces) < n + f


@pytest.mark.parametrize("n,seq,lo,plan", [
    (44, 128, 8, [32, 16]),      # resp-1m.mix's typical miss batch
    (50, 128, 8, [32, 16, 8]),
    (57, 128, 8, [64]),          # 64 rows: the bucket itself
    (37, 16, 8, [32, 16]),       # retr-1m's 16-row floor
    (50, 16, 8, [64]),
    (11, 16, 8, [16]),
    (700, 16, 8, [512, 128, 64]),
    (3, 512, 1, [2, 1]),         # a floor of 1 row at 512 tokens
    (1, 1024, 8, [1]),           # moonlight-resp.p1k: below the bucket
    (5, 1024, 8, [4, 1]),
    (7, 1024, 8, [4, 2, 1]),
    (9, 1024, 8, [8, 1]),
    (12, 1024, 8, [8, 4]),
])
def test_prefill_pieces_known_plans(n, seq, lo, plan):
    assert prefill_pieces(n, seq, lo) == plan


@pytest.mark.parametrize("n,seq", [(20, SEQ), (37, SEQ), (50, SEQ),
                                   (7, LONG)])
def test_split_batch_serves_the_bucket_tokens(n, seq):
    """A cold batch of ``n`` misses runs as several pieces; its tokens are
    those of the same prompts prefilled at their one bucket, and its
    logits agree with the bucket's to bf16 precision."""
    eng, cfg, _ = _tiny_engine()
    pieces = prefill_pieces(n, seq, eng.ecfg.min_bucket)
    b = bucket_size(n)
    assert len(pieces) > 1 and sum(pieces) < b
    prompts = _prompts(cfg, n, seed=n, seq=seq)
    ref, _ = eng._prefill(eng.params, {"tokens": np.concatenate(
        [prompts, np.repeat(prompts[:1], b - n, axis=0)])})
    ref = np.asarray(ref[:n], np.float32)

    padded = np.concatenate([prompts, np.repeat(prompts[:1],
                                                sum(pieces) - n, axis=0)])
    got = eng.prefill(padded)
    assert got.shape == (b, cfg.vocab)
    got = np.asarray(got, np.float32)
    np.testing.assert_allclose(got[:n], ref, rtol=0,
                               atol=2.0 ** -7 * np.abs(ref).max())
    assert np.all(got[sum(pieces):] == 0)

    with tracecount.snapshot() as s:
        out, _ = eng.serve(np.arange(n), prompts)
    assert s.delta("prefill.rows") == sum(pieces)
    assert s.delta("prefill.pieces") == len(pieces)
    np.testing.assert_array_equal(np.concatenate(out), ref.argmax(-1))


def test_unbucketed_prefill_runs_the_rows_as_given():
    eng, cfg, _ = _tiny_engine(bucket=False)
    prompts = _prompts(cfg, 40, seed=3)
    assert eng.prefill(prompts).shape == (40, cfg.vocab)
    with tracecount.snapshot() as s:
        eng.serve(np.arange(40), prompts)
    assert s.delta("prefill.rows") == 40
    assert s.delta("prefill.pieces") == 1


@pytest.mark.parametrize("seq,batch", [(SEQ, 64), (LONG, 32)])
def test_no_compile_after_bucket_warm_up(seq, batch):
    """Warm up as the benchmark does (the prefill at every power-of-two
    bucket, then one served batch), then serve batches of ``batch``
    requests with 1 … ``batch`` misses: the split prefills compile
    nothing, those with pieces below the smallest bucket included."""
    eng, cfg, cat = _tiny_engine()
    warm = np.random.default_rng(0).integers(0, len(cat.coords), batch)
    eng.serve(warm, _prompts(cfg, batch, seed=0, seq=seq))
    eng.refresh_placement()
    hit = np.asarray(eng.simcache.lookup(jnp.asarray(eng.coords)).hit)
    hits, misses = np.nonzero(hit)[0], np.nonzero(~hit)[0]
    assert len(hits) and len(misses)
    b = eng.ecfg.min_bucket
    while b <= batch:
        np.asarray(jnp.argmax(eng.prefill(np.zeros((b, seq), np.int32)),
                              axis=-1))
        b *= 2
    eng.serve(warm, _prompts(cfg, batch, seed=1, seq=seq))

    rng = np.random.default_rng(2)
    compiles = []

    def on(event, duration, **_):
        if event == BACKEND_COMPILE:
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        with tracecount.snapshot() as s:
            for m in range(1, batch + 1):
                ids = np.concatenate([rng.choice(misses, m),
                                      rng.choice(hits, batch - m)])
                hits0 = eng.stats.n_hits
                eng.serve(ids, _prompts(cfg, batch, seed=100 + m, seq=seq))
                assert eng.stats.n_hits - hits0 == batch - m
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
    assert compiles == []
    plans = [prefill_pieces(m, seq) for m in range(1, batch + 1)]
    assert s.delta("prefill.batches") == batch
    assert s.delta("prefill.pieces") == sum(map(len, plans))
    assert s.delta("prefill.rows") == sum(map(sum, plans))
    assert s.delta("prefill.rows_valid") == sum(range(1, batch + 1))
    assert any(len(p) > 1 for p in plans)
    assert (seq == LONG) == any(min(p) < eng.ecfg.min_bucket for p in plans)
