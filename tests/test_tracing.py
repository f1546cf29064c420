"""The program's spans and per-call counters (repro.tracecount): the
always-on span table, the counters' snapshot deltas, thread safety, and
on a tiny engine the served path's spans in a profiler capture, the
miss prefill's padding counters, and the durations ``ServeStats`` and
the swap stall now take from the spans."""
import glob
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracecount
from repro.serve import bucket_size, prefill_pieces

from test_streaming import make_engine, mixed_batches

SERVED_PATH = ("serve.demand", "serve.queries", "simcache.lookup",
               "serve.fetch_lookup", "serve.respond_hits",
               "DuelPlane.observe", "serve.miss_gather", "engine.prefill",
               "serve.fetch_prefill", "serve.respond_misses")


def _row(name):
    return tracecount.summary()["spans"].get(
        name, {"count": 0, "total_ms": 0.0, "max_ms": 0.0})


def test_span_table_counts_nests_and_keeps_the_max():
    before_o, before_i = _row("t.outer"), _row("t.inner")
    inner_ns = []
    with tracecount.span("t.outer", batch=3, n=2) as outer:
        for d in (0.002, 0.006):
            with tracecount.span("t.inner") as sp:
                time.sleep(d)
            inner_ns.append(sp.ns)
    o, i = _row("t.outer"), _row("t.inner")
    assert o["count"] - before_o["count"] == 1
    assert i["count"] - before_i["count"] == 2
    assert i["total_ms"] - before_i["total_ms"] == pytest.approx(
        sum(inner_ns) * 1e-6)
    assert i["max_ms"] >= max(inner_ns) * 1e-6 >= 6.0
    assert outer.ns >= sum(inner_ns)
    assert o["mean_ms"] == pytest.approx(o["total_ms"] / o["count"])


def test_counters_share_the_snapshot_delta_with_trace_counts():
    with tracecount.snapshot() as s:
        tracecount.add("t.rows", 16)
        tracecount.add("t.rows")
        tracecount.bump("t.trace")
    assert s.delta("t.rows") == 17 and s.delta("t.trace") == 1
    assert tracecount.summary()["counts"]["t.rows"] >= 17


def test_span_table_and_counters_are_safe_across_threads():
    """Threads (more than the cores, switching every microsecond) update
    one span's row and one counter, as the placement-refresh thread and
    the serving thread do: no update is lost."""
    n_threads, n = 4 * (os.cpu_count() or 1), 500
    before = _row("t.thread")["count"]

    def work():
        for _ in range(n):
            with tracecount.span("t.thread"):
                tracecount.add("t.thread_adds")

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with tracecount.snapshot() as s:
            threads = [threading.Thread(target=work)
                       for _ in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert _row("t.thread")["count"] - before == n_threads * n
    assert s.delta("t.thread_adds") == n_threads * n


@pytest.fixture(scope="module")
def warm_engine():
    """A tiny engine with a placement and the duel plane armed, every
    shape of the batches below compiled."""
    eng, cfg, cat = make_engine(netduel=True)
    for ids, prompts in mixed_batches(cat, cfg, [16] * 4, seed=9):
        eng.serve(ids, prompts)
    eng.refresh_placement()
    batches = _uniform_batches(cat, cfg, [24, 24], seed=5)
    for ids, prompts in batches:
        eng.serve(ids, prompts)
    return eng, cfg, cat


def _uniform_batches(cat, cfg, sizes, seed):
    """Requests drawn uniformly over the catalog: a 36-slot cache over
    300 objects misses on most of them."""
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, len(cat.coords), k),
             jnp.asarray(rng.integers(0, cfg.vocab, (k, 8)).astype(
                 np.int32))) for k in sizes]


def _host_events(path):
    """Every event of the /host:CPU plane, as (line, name, start, end,
    stats) tuples."""
    from jax.profiler import ProfileData
    (f,) = glob.glob(f"{path}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(f).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                out.append((line.name, e.name, e.start_ns,
                            e.start_ns + e.duration_ns, dict(e.stats)))
    return out


def test_each_served_batch_emits_every_span_inside_engine_serve(
        warm_engine, tmp_path):
    eng, cfg, cat = warm_engine
    batches = _uniform_batches(cat, cfg, [24, 24], seed=6)
    first = tracecount.get("serve.batches")
    jax.profiler.start_trace(str(tmp_path))
    try:
        for ids, prompts in batches:
            eng.serve(ids, prompts)
    finally:
        jax.profiler.stop_trace()
    ev = _host_events(tmp_path)
    serves = [e for e in ev if e[1] == "engine.serve"]
    assert [e[4]["batch"] for e in serves] == [first, first + 1]
    assert [e[4]["n"] for e in serves] == [24, 24]
    for line, _, lo, hi, _ in serves:
        for name in SERVED_PATH:
            inside = [e for e in ev if e[1] == name and e[0] == line
                      and lo <= e[2] and e[3] <= hi]
            assert len(inside) == 1, name
            assert inside[0][4] == {}, name
    # the phases of one batch follow each other, never overlap
    for line, _, lo, hi, _ in serves:
        ph = sorted((e[2], e[3]) for e in ev if e[0] == line
                    and e[1] in SERVED_PATH and lo <= e[2] and e[3] <= hi)
        assert all(b[0] >= a[1] for a, b in zip(ph, ph[1:]))


def test_prefill_counters_count_the_bucket_and_the_misses(warm_engine):
    eng, cfg, cat = warm_engine
    (ids, prompts), = _uniform_batches(cat, cfg, [40], seed=7)
    hits0, n_req0 = eng.stats.n_hits, eng.stats.n_requests
    with tracecount.snapshot() as s:
        eng.serve(ids, prompts)
    misses = 40 - (eng.stats.n_hits - hits0)
    assert 0 < misses and eng.stats.n_requests - n_req0 == 40
    lo = eng.ecfg.min_bucket
    pieces = prefill_pieces(misses, prompts.shape[1], lo)
    assert s.delta("prefill.rows") == sum(pieces)
    assert s.delta("prefill.rows_valid") == misses
    assert s.delta("prefill.batches") == 1
    assert s.delta("prefill.pieces") == len(pieces)
    assert s.delta("lookup.rows") == bucket_size(40, lo) == 64
    assert s.delta("lookup.rows_valid") == 40
    assert s.delta("serve.batches") == 1
    assert s.delta("serve.requests") == 40
    assert s.delta("serve.copies") == 5
    # bool hit, int32 payload, f32 cost and approx cost at the bucket;
    # one int32 token a miss row
    assert s.delta("serve.copy_bytes") == 64 * (1 + 4 + 4 + 4) \
        + 4 * bucket_size(misses, lo)


def test_batch_latency_is_the_engine_serve_span(warm_engine):
    eng, cfg, cat = warm_engine
    ring = eng.stats.batch_latencies_ms
    n0, total0 = len(ring), _row("engine.serve")["total_ms"]
    for ids, prompts in _uniform_batches(cat, cfg, [8, 16, 24], seed=8):
        eng.serve(ids, prompts)
    assert len(ring) == n0 + 3
    new = list(ring)[-3:]
    assert all(v > 0.0 for v in new)
    assert sum(new) == pytest.approx(
        _row("engine.serve")["total_ms"] - total0)


def test_swap_stall_is_the_engine_swap_span():
    eng, cfg, cat = make_engine(netduel=False)
    for ids, prompts in mixed_batches(cat, cfg, [16] * 4):
        eng.serve(ids, prompts)
    eng.refresh_placement()
    total0, solves0 = _row("engine.swap")["total_ms"], \
        _row("engine.solve")["count"]
    stalls = []
    for _ in range(2):
        assert eng.request_refresh()
        assert eng.wait_refresh(timeout=120)
        assert eng.poll_refresh()
        stalls.append(eng.last_swap_stall_s)
    assert eng.swap_count == 2
    assert all(s > 0.0 for s in stalls)
    assert eng.swap_stall_s == pytest.approx(sum(stalls))
    assert eng.max_swap_stall_s == max(stalls)
    assert eng.swap_stall_s * 1e3 == pytest.approx(
        _row("engine.swap")["total_ms"] - total0)
    # the background solves ran on the refresh thread, into one table
    assert _row("engine.solve")["count"] - solves0 == 2


def test_phase_table_prints_every_span_and_counter(warm_engine, capsys):
    from repro.launch.serve import print_phase_table
    eng, cfg, cat = warm_engine
    for ids, prompts in _uniform_batches(cat, cfg, [24], seed=10):
        eng.serve(ids, prompts)
    summary = tracecount.summary()
    print_phase_table()
    rows = {line.split()[1]: line.split()[2:]
            for line in capsys.readouterr().out.splitlines()}
    for name, row in summary["spans"].items():
        assert int(rows[name][0]) >= row["count"]
    for name in ("serve.batches", "serve.requests", "lookup.rows",
                 "lookup.rows_valid", "prefill.rows", "prefill.rows_valid",
                 "serve.copies", "serve.copy_bytes"):
        assert int(rows[name][0]) >= summary["counts"][name] > 0


@pytest.mark.parametrize("misses,seq,plan", [
    (24, 64, [16, 8]),        # pieces of whole buckets
    (7, 1024, [4, 2, 1]),     # a 1-row floor: pieces below the bucket
])
def test_moe_counters_count_the_routed_and_held_rows(warm_engine, misses,
                                                     seq, plan):
    """A MoE model's served prefill counts the router's assignments on
    the device, the rows its held experts computed and the busiest held
    expert's rows per layer; the prefill counters keep their meaning. A
    dense model's serve counts no MoE rows."""
    import dataclasses
    from repro.configs.registry import get_smoke_config
    from repro.models import model as model_api
    from repro.serve import EngineConfig, SimCacheEngine
    dense, dense_cfg, cat = warm_engine
    cfg = dataclasses.replace(get_smoke_config("moonlight-16b-a3b"),
                              experts_held=(2, 4))
    eng = SimCacheEngine(cfg, model_api.init_params(cfg, 0),
                         EngineConfig(h_model=100.0), cat.coords)
    prompts = jnp.asarray(np.random.default_rng(11).integers(
        0, cfg.vocab, (misses, seq)).astype(np.int32))
    with tracecount.snapshot() as s:
        eng.serve(np.arange(misses), prompts)
    pieces = prefill_pieces(misses, seq, eng.ecfg.min_bucket)
    assert pieces == plan
    rows = [np.asarray(r) for r in eng._expert_rows]
    assert [r.shape for r in rows] == [(2, 4)] * len(plan)
    assert s.delta("moe.assignments") == sum(pieces) * seq * 3 * 2
    assert s.delta("moe.rows_held") == sum(int(r.sum()) for r in rows)
    assert 0 < s.delta("moe.rows_held") < s.delta("moe.assignments")
    assert s.delta("moe.rows_busiest") == sum(
        int(r.max(axis=1).sum()) for r in rows)
    assert s.delta("prefill.rows") == sum(pieces)
    assert s.delta("prefill.rows_valid") == misses
    assert s.delta("prefill.batches") == 1
    assert s.delta("prefill.pieces") == len(pieces)
    # the argmax of the misses' bucket and each piece's row counts
    assert s.delta("serve.copy_bytes") == (4 * bucket_size(misses)
                                           + len(plan) * 2 * 4 * 4)
    (ids, prompts), = _uniform_batches(cat, dense_cfg, [24], seed=12)
    with tracecount.snapshot() as s:
        dense.serve(ids, prompts)
    assert s.delta("prefill.rows") > 0
    assert all(s.delta(k) == 0 for k in ("moe.assignments",
                                         "moe.rows_held",
                                         "moe.rows_busiest"))
