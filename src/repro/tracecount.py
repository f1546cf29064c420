"""The program's own counts and spans, in one registry.

Two kinds of count share ``COUNTS`` and the ``snapshot`` delta:

- per trace: a ``bump(name)`` placed inside a jitted function body is a
  Python side effect that runs once per *trace* (a new static-argument
  combination or a new input shape/dtype), never per call. It counts
  what batch bucketing is supposed to bound: the compiled
  specializations a serving workload forces out of the fused lookup,
  the duel scan and the prefill. Zero cost on the executed path.
- per call: an ``add(name, n)`` in host code counts work on every call,
  e.g. the rows a served batch sends to the lookup and to the miss
  prefill, and the blocking device→host copies and their bytes.

A ``span(name, **stats)`` marks a phase of host code. It opens a
``jax.profiler.TraceAnnotation``, which records only while the profiler
records, on the host line of the calling thread and on the device
trace's clock, so an idle gap of the device falls under the phase that
was running. It also adds its wall duration to an always-on per-name
table (count, total, max) of fixed size. Counts and table are updated
under one lock, so the placement-refresh thread's spans and traces land
in them beside the serving thread's. ``summary()`` returns that table
and the counts.

Readers: tests/test_streaming.py and tests/test_tracing.py,
benchmarks/serving_bench.py, the phase table of ``repro.launch.serve``,
and the benchmark's per-layer metrics under ``bench/metrics/``.
"""
from __future__ import annotations

import collections
import functools
import threading
import time

from jax.profiler import TraceAnnotation

COUNTS: collections.Counter = collections.Counter()
# span name → [count, total ns, max ns]
SPANS: dict[str, list] = {}
_LOCK = threading.Lock()       # guards COUNTS and SPANS
_now = time.perf_counter_ns


def bump(name: str) -> None:
    """Record one trace of ``name`` (call from inside the jitted body)."""
    with _LOCK:
        COUNTS[name] += 1


def add(name: str, n: int = 1) -> None:
    """Count ``n`` units of work of ``name`` (call from host code)."""
    with _LOCK:
        COUNTS[name] += n


def get(name: str) -> int:
    return COUNTS[name]


def reset() -> None:
    with _LOCK:
        COUNTS.clear()
        SPANS.clear()


class snapshot:
    """Context manager: ``with snapshot() as s: ...; s.delta("name")``
    gives counts since entry without resetting the global counters."""

    def __enter__(self) -> "snapshot":
        self._at_entry = dict(COUNTS)
        return self

    def __exit__(self, *exc) -> None:
        pass

    def delta(self, name: str) -> int:
        return COUNTS[name] - self._at_entry.get(name, 0)


class span(TraceAnnotation):
    """``with span("serve.queries"): ...`` — a profiler annotation that
    also adds its wall time to ``SPANS[name]``; after the block, ``ns``
    holds the duration. Keyword stats go to the profiler event only.
    The span is the annotation object itself: no other allocation."""

    __slots__ = ("name", "ns", "_t0")

    def __init__(self, name: str, **stats):
        TraceAnnotation.__init__(self, name, **stats)
        self.name = name
        self.ns = 0

    def __enter__(self) -> "span":
        TraceAnnotation.__enter__(self)
        self._t0 = _now()
        return self

    def __exit__(self, *exc) -> None:
        self.ns = ns = _now() - self._t0
        TraceAnnotation.__exit__(self, *exc)
        with _LOCK:
            row = SPANS.get(self.name)
            if row is None:
                SPANS[self.name] = [1, ns, ns]
            else:
                row[0] += 1
                row[1] += ns
                if ns > row[2]:
                    row[2] = ns


def spanned(name: str):
    """Decorator: every call of the function runs inside ``span(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


def summary() -> dict:
    """``{"spans": {name: {count, total_ms, mean_ms, max_ms}},
    "counts": {name: n}}`` since start or the last ``reset``."""
    with _LOCK:
        rows = [(k, *row) for k, row in SPANS.items()]
        counts = dict(COUNTS)
    spans = {k: {"count": c, "total_ms": t * 1e-6, "mean_ms": t * 1e-6 / c,
                 "max_ms": m * 1e-6} for k, c, t, m in rows}
    return {"spans": spans, "counts": counts}
