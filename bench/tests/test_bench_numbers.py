"""The benchmark's arithmetic: generators, end-to-end statistics, FLOP
and byte counts, the peaks table, the interval algebra of the trace
reduction."""
from __future__ import annotations

import numpy as np
import pytest

import bench_fixtures  # noqa: F401  (puts bench/ and src/ on the path)
import flops
import serving
import traffic
import tracing

GRANITE = {"hidden_size": 2048, "intermediate_size": 8192,
           "num_hidden_layers": 40, "num_attention_heads": 32,
           "num_key_value_heads": 8, "vocab_size": 49155}
BIG_SEED = 2 ** 31 + 12345


@pytest.mark.parametrize("what", ["catalog", "cdf", "objects", "history",
                                  "prompts", "arrivals"])
def test_generators_repeat_from_the_seed(what):
    def make(seed):
        if what == "catalog":
            return traffic.catalog(500, 16, seed)
        cdf = traffic.zipf_cdf(1000, 0.9, seed)
        if what == "cdf":
            return cdf
        if what == "objects":
            return traffic.iid_objects(cdf, 300, traffic.rng(seed, "w"))
        if what == "history":
            return traffic.history(cdf, 64, seed)
        if what == "prompts":
            return traffic.prompts(np.arange(50), 8, 1000, seed)
        return traffic.arrivals(500.0, 2.0, traffic.rng(seed, "w"))
    a, b, c = make(BIG_SEED), make(BIG_SEED), make(BIG_SEED + 1)
    np.testing.assert_array_equal(a, b)
    # another seed draws other values (and, for arrivals, another count)
    assert (what == "arrivals" or a.shape == c.shape) \
        and not np.array_equal(a, c)


def test_each_block_draws_every_popularity_stratum_once():
    # under equal popularity the 1/64 quantiles are runs of 100 ids
    cdf = traffic.zipf_cdf(6400, 0.0, 7)
    objs = traffic.draw_objects(cdf, 8 * 64, 64, traffic.rng(7, "w"))
    for block in objs.reshape(8, 64):
        np.testing.assert_array_equal(np.sort(block // 100), np.arange(64))


def test_arrivals_are_a_poisson_stream():
    # 200,000 expected: the count within 5 sigma, exponential gaps
    n = [len(traffic.arrivals(1e4, 20.0, traffic.rng(s, "w")))
         for s in (1, 2)]
    assert n[0] != n[1] and all(abs(k - 2e5) < 5 * np.sqrt(2e5) for k in n)
    a = traffic.arrivals(1e4, 20.0, traffic.rng(1, "w"))
    gaps = np.diff(a)
    assert np.all(gaps > 0) and a.min() >= 0 and a.max() < 20.0
    assert np.mean(gaps) == pytest.approx(1e-4, rel=0.01)
    assert np.std(gaps) == pytest.approx(1e-4, rel=0.02)


def test_window_draws_are_independent_not_stratified():
    # under equal popularity, blocks of 64 draws i.i.d. leave some of
    # the 64 quantiles empty; the history's stratified blocks never do
    cdf = traffic.zipf_cdf(6400, 0.0, 7)
    sched = traffic.Schedule(cdf, 4, 100, 7, 64)
    sched.draw(8 * 64)
    blocks = sched.objects.reshape(8, 64) // 100
    assert all(len(np.unique(b)) < 64 for b in blocks)
    n = traffic.HISTORY_REQUESTS
    assert n == 1 << 20 and len(traffic.history(cdf, 64, 7)) == n


def test_saturating_schedule_never_runs_out_and_blocks_keep_their_seed():
    cdf = traffic.zipf_cdf(1000, 1.2, 3)
    a = traffic.schedule({"arrival": "saturate", "batch": 32,
                          "prompt_len": 4}, cdf, 500, BIG_SEED, 1.0)
    b = traffic.Schedule(cdf, 4, 500, BIG_SEED, 32)
    for k in range(0, 5000):           # far past any ceiling
        objs, toks = a.take(32 * k, 32 * k + 32)
    b.draw(32 * 4000)
    np.testing.assert_array_equal(objs, b.take(32 * 4999, 32 * 5000)[0])
    np.testing.assert_array_equal(a.objects[:32 * 4000], b.objects[:32 * 4000])
    np.testing.assert_array_equal(toks, traffic.prompts(objs, 4, 500,
                                                        BIG_SEED))
    np.testing.assert_array_equal(a.take(40, 90)[0], b.objects[40:90])


def test_prompts_are_a_function_of_the_object():
    p = traffic.prompts(np.array([5, 9, 5]), 16, 49155, 3)
    np.testing.assert_array_equal(p[0], p[2])
    assert p.dtype == np.int32 and p.min() >= 0 and p.max() < 49155


def _window(due=None):
    # three batches: 4 requests back at 0.5 s, 4 at 1.0 s, 2 at 2.5 s
    bs = [serving.Batch(0, 4, 0.0, 0.5, [None] * 4),
          serving.Batch(4, 8, 0.5, 1.0, [None] * 4),
          serving.Batch(8, 10, 1.0, 2.5, [None] * 2)]
    return serving.Window(seconds=2.0, batches=bs,
                          offered=10 if due is None else len(due), due=due)


def test_rate_counts_all_work_and_all_time_of_the_window():
    out = serving.end_to_end(_window())
    assert out == {"req_per_s": 10 / 2.5}


def test_percentiles_are_over_every_request_not_every_batch():
    due = np.array([0.0, 0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9])
    out = serving.end_to_end(_window(due))
    lat = np.array([500, 400, 300, 200, 600, 500, 400, 300, 1700, 1600])
    assert out["p50_ms"] == pytest.approx(np.percentile(lat, 50,
                                                        method="higher"))
    assert out["p95_ms"] == pytest.approx(1700)


def test_a_request_never_served_is_slower_than_all():
    due = np.arange(12) * 0.05
    out = serving.end_to_end(_window(due))     # 2 of 12 never returned
    assert out["p95_ms"] == np.inf


def test_stall_summary_tells_a_few_stalls_from_slow_batches():
    out = serving.stall_summary(_window())
    assert out.startswith("median 500.000 ms") and "max 1500.000 ms" in out
    assert "1 over twice the median, losing 1.000 s" in out


def test_prefill_flops_and_bytes_of_granite_3_2b_by_hand():
    # per layer and token: q, o 2·2048·2048, k, v 2·2048·512, MLP
    # 3·2048·8192 multiply-adds; causal attention 4·32·64·(16·17/2) per
    # layer; the head 2·2048·49155 once
    dense = 2 * (2 * 2048 * 2048 + 2 * 2048 * 512 + 3 * 2048 * 8192)
    assert dense == 121_634_816
    want = 40 * (16 * dense + 4 * 32 * 64 * 136) + 2 * 2048 * 49155
    assert flops.prefill_flops(GRANITE, 1, 16) == want == 78_092_185_600
    assert flops.weight_count(GRANITE) == 2_533_531_648
    kv = 16 * 40 * 2 * 8 * 64 * 2
    assert flops.prefill_bytes(GRANITE, 1, 16) == 2 * 2_533_531_648 + kv


def test_lookup_flops_and_bytes_by_hand():
    assert flops.lookup_flops(1024, 458_752, 128) == 120_259_084_288
    assert flops.lookup_bytes(458_752, 128) == 58_720_256
    assert flops.time_floor(2.0, 10.0, 1.0, 5.0) == 2.0


def test_peaks_of_the_v5e_and_an_unknown_device():
    p = flops.peaks("TPU v5 lite")
    assert (p["bf16_flops_per_s"], p["int8_ops_per_s"],
            p["hbm_bytes_per_s"]) == (197e12, 393e12, 819e9)
    with pytest.raises(KeyError, match="no peaks"):
        flops.peaks("TPU v9 imaginary")


def test_union_and_gaps_of_intervals():
    s = np.array([0.0, 1.0, 1.5, 5.0])
    e = np.array([2.0, 1.2, 3.0, 6.0])
    assert tracing.union_length(s, e) == pytest.approx(4.0)
    assert tracing.gaps(s, e, 0.0, 7.0) == [(3.0, 5.0), (6.0, 7.0)]
    assert tracing.union_length(np.zeros(0), np.zeros(0)) == 0.0
