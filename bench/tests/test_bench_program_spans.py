"""The per-layer metrics that read the program's own spans and counters
(``host_copy_ms``, ``host_loop_ms``, ``host_input_ms``, their per-batch
medians ``host_*_p50_ms``, the ``.rate`` twins of all six,
``prefill_pad_share``): on hand-built traces with known busy
intervals, on a program without the spans or counters, and on a trace
of retr-1m.hot recorded on a TPU v5e.

The recorded case's expected numbers were computed from the same
recording's Chrome-format export by a separate, plain reduction: for
each named span of the host's Python thread inside ``bench.traced``,
its length less the union of the ``XLA Ops`` events of
``/device:TPU:0`` inside it, summed by metric and divided by the
number of ``engine.serve`` spans; for the medians, the same sum batch
by batch over the spans each ``engine.serve`` holds. That export rounds to the
microsecond, hence the tolerance."""
from __future__ import annotations

import collections
import json
from types import SimpleNamespace

import numpy as np
import pytest

from bench_fixtures import BENCH
import harness  # noqa: E402
import tracing  # noqa: E402

HOST = ("host_copy_ms", "host_loop_ms", "host_input_ms")
P50 = tuple(m.replace("_ms", "_p50_ms") for m in HOST)
DATA = BENCH / "tests" / "data"
RECORDED = "trace_retr-1m.hot"
US = 1e-6


def _events(rows):
    names, s, e = zip(*rows) if rows else ((), (), ())
    return tracing.Events(np.array(names, dtype=object),
                          np.array(s, float), np.array(e, float))


def _trace(host, ops):
    host = [("bench.traced", 0.0, 1.0)] + host
    return tracing.Trace(ops=_events([("op", a, b) for a, b in ops]),
                         modules=_events([]), host=_events(host),
                         span=(0.0, 1.0))


# two batches: the first with misses, the second all hits; a third
# outside the traced span is not counted
BATCHES = [
    ("engine.serve", 0.10, 0.40), ("serve.demand", 0.10, 0.12),
    ("serve.queries", 0.12, 0.14), ("simcache.lookup", 0.14, 0.15),
    ("serve.fetch_lookup", 0.15, 0.25), ("serve.respond_hits", 0.25, 0.27),
    ("serve.miss_gather", 0.27, 0.28), ("engine.prefill", 0.28, 0.29),
    ("serve.fetch_prefill", 0.29, 0.38),
    ("serve.respond_misses", 0.38, 0.40),
    ("engine.serve", 0.50, 0.90), ("serve.demand", 0.50, 0.52),
    ("serve.queries", 0.52, 0.55), ("simcache.lookup", 0.55, 0.56),
    ("serve.fetch_lookup", 0.56, 0.70), ("serve.respond_hits", 0.70, 0.80),
    ("engine.serve", 1.10, 1.20), ("serve.fetch_lookup", 1.11, 1.19),
]
BUSY = [(0.145, 0.23), (0.285, 0.37), (0.555, 0.69)]
# device idle inside each metric's phases, seconds, over both batches:
# copies 0.02 + 0.01 + 0.01; loops 0.02 + 0.02 + 0.02 + 0.02 + 0.10;
# inputs 0.02 + 0.005 + 0.01 + 0.005 + 0.03 + 0.005
WANT_MS = {"host_copy_ms": 20.0, "host_loop_ms": 90.0,
           "host_input_ms": 37.5}


@pytest.mark.parametrize("name", HOST + tuple(f"{m}.rate" for m in HOST))
def test_host_metrics_sum_idle_inside_their_phases(name):
    ctx = SimpleNamespace(trace=_trace(BATCHES, BUSY))
    got = harness.reader(name)(ctx)
    assert got == pytest.approx(WANT_MS[name.split(".")[0]], abs=1e-9)


def test_host_metrics_split_serve_host_ms_and_leave_the_gaps():
    """With the benchmark's serve spans where the program's are, the
    three metrics sum to ``serve_host_ms`` less the unspanned time: the
    second batch's last 0.1 s, 50 ms a batch."""
    serves = [("bench.serve", a, b) for n, a, b in BATCHES
              if n == "engine.serve"]
    ctx = SimpleNamespace(trace=_trace(BATCHES + serves, BUSY))
    whole = harness.reader("serve_host_ms")(ctx)
    parts = sum(harness.reader(m)(ctx) for m in HOST)
    assert whole == pytest.approx(197.5)
    assert whole - parts == pytest.approx(50.0)


# three batches, the third paused 100 ms in its copy, and a direct
# prefill between two batches: per batch, idle ms copies 5, 7, 100;
# loops 1, 0, 10; inputs 5, 20, 10 (the 30 ms prefill outside any batch
# is not counted)
PAUSED = [
    ("engine.serve", 0.10, 0.20), ("serve.fetch_lookup", 0.10, 0.105),
    ("serve.respond_hits", 0.11, 0.111), ("serve.queries", 0.12, 0.13),
    ("engine.serve", 0.30, 0.40), ("serve.fetch_lookup", 0.30, 0.305),
    ("serve.fetch_prefill", 0.31, 0.312), ("simcache.lookup", 0.32, 0.34),
    ("engine.serve", 0.50, 0.80), ("serve.fetch_lookup", 0.50, 0.60),
    ("serve.demand", 0.61, 0.62), ("engine.prefill", 0.63, 0.64),
    ("engine.prefill", 0.25, 0.28),
]
PAUSED_BUSY = [(0.12, 0.125)]
WANT_P50_MS = {"host_copy_p50_ms": 7.0, "host_loop_p50_ms": 1.0,
               "host_input_p50_ms": 10.0}


@pytest.mark.parametrize("name", P50 + tuple(f"{m}.rate" for m in P50))
def test_host_medians_take_the_batch_by_batch_median(name):
    """The median is the middle batch's idle time: the pause that sets
    the mean (copies 112 / 3 ms) does not move it."""
    ctx = SimpleNamespace(trace=_trace(PAUSED, PAUSED_BUSY))
    got = harness.reader(name)(ctx)
    assert got == pytest.approx(WANT_P50_MS[name.split(".")[0]], abs=1e-9)
    if name == "host_copy_p50_ms":
        assert harness.reader("host_copy_ms")(ctx) == pytest.approx(
            112.0 / 3)


@pytest.mark.parametrize(
    "name", HOST + P50 + ("host_copy_ms.rate", "host_copy_p50_ms.rate"))
def test_host_metrics_read_nothing_without_program_spans(name):
    """A program without the spans gives no value, and raises nothing."""
    no_spans = [("bench.serve", 0.1, 0.4)]
    assert harness.reader(name)(
        SimpleNamespace(trace=_trace(no_spans, BUSY))) is None
    assert harness.reader(name)(SimpleNamespace(trace=None)) is None


def test_prefill_pad_share_reads_the_program_counters(monkeypatch):
    from repro import tracecount
    read = harness.reader("prefill_pad_share")
    monkeypatch.setattr(tracecount, "COUNTS", collections.Counter(
        {"prefill.rows": 64 + 16, "prefill.rows_valid": 44 + 12}))
    assert read(None) == pytest.approx(30.0)
    monkeypatch.setattr(tracecount, "COUNTS", collections.Counter())
    assert read(None) is None


@pytest.fixture(scope="module")
def recorded():
    want = json.loads((DATA / f"{RECORDED}.expected.json").read_text())
    tr = tracing.Trace.load(str(DATA / f"{RECORDED}.xplane.pb.gz"))
    return SimpleNamespace(trace=tr), want


def test_recorded_trace_has_program_spans_on_the_device_clock(recorded):
    ctx, want = recorded
    tr = ctx.trace
    serves = tr.spans("engine.serve")
    assert len(serves) == want["engine_serve_spans"] == \
        len(tr.spans("bench.serve"))
    # each batch's device work (its lookup and its prefill) runs inside
    # its engine.serve span
    for a, b in serves:
        assert tr.busy_s(a, b) > 0.5 * (b - a)
    labels = {g[0] for g in tr.idle_gaps()}
    assert labels and "bench.serve" not in labels


def test_recorded_host_metrics_match_and_split_serve_host_ms(recorded):
    ctx, want = recorded
    n = want["engine_serve_spans"]
    parts = {}
    for m in HOST:
        parts[m] = harness.reader(m)(ctx)
        n_spans = want["phase_spans"][m]
        assert parts[m] == pytest.approx(want[m], abs=2e3 * n_spans * US / n)
    whole = harness.reader("serve_host_ms")(ctx)
    assert whole == pytest.approx(want["serve_host_ms"], abs=1e-2)
    residue = whole - sum(parts.values())
    assert 0.0 <= residue <= 0.10 * whole
    assert residue == pytest.approx(want["residue_ms"], abs=2e-2)


@pytest.mark.parametrize("name", P50)
def test_recorded_host_medians_match(recorded, name):
    ctx, want = recorded
    assert harness.reader(name)(ctx) == pytest.approx(want[name], abs=1e-2)
