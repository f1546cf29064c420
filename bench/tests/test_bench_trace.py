"""The trace reduction against a trace recorded on a TPU v5e with
``bench/run.py --trace 1`` (retr-1m.hot, the first seconds of its window).
The expected numbers beside it were computed from the same recording's
Chrome-format export by a separate, plain reduction: union of the
``XLA Ops`` events of ``/device:TPU:0`` inside the ``bench.traced`` span
of the host's Python thread, module events summed by jit name. That
export rounds to the microsecond, hence the tolerance."""
from __future__ import annotations

import json

import pytest

from bench_fixtures import BENCH
import tracing  # noqa: E402

DATA = BENCH / "tests" / "data"
CASES = sorted(p.name[:-len(".expected.json")]
               for p in DATA.glob("*.expected.json"))
US = 1e-6


@pytest.fixture(params=CASES)
def case(request):
    want = json.loads((DATA / f"{request.param}.expected.json").read_text())
    return tracing.Trace.load(str(DATA / f"{request.param}.xplane.pb.gz")), \
        want


def test_there_is_a_recorded_trace():
    assert CASES


def test_busy_and_window(case):
    tr, want = case
    assert tr.window_s == pytest.approx(want["window_s"], abs=US)
    n_ops = want["ops_in_span"]
    assert tr.busy_s() == pytest.approx(want["busy_s"], abs=n_ops * US)
    idle = 100 * (1 - tr.busy_s() / tr.window_s)
    assert idle == pytest.approx(want["device_idle_pct"], abs=0.01)


def test_device_time_per_program(case):
    tr, want = case
    modules = json.loads((BENCH / "modules.json").read_text())
    for name, secs in want["module_s"].items():
        n = want["module_count"][name]
        assert tr.module_count(name) == n
        assert tr.module_s(name) == pytest.approx(secs, abs=n * US)
    assert set(modules.values()) <= set(want["module_s"])


def test_serve_spans_and_breakdown(case):
    tr, want = case
    assert len(tr.spans("bench.serve")) == want["serve_spans"]
    ops = tr.top_ops()
    assert len(ops) == 10 and ops[0][0] == want["top_op"]
    assert all(a[1] >= b[1] for a, b in zip(ops, ops[1:]))
    gaps = tr.idle_gaps()
    assert 0 < len(gaps) <= 10
    assert sum(g[1] for g in gaps) <= tr.window_s - tr.busy_s() + US
